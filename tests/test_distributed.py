"""Distributed layer: elastic master task queue (go/master parity),
DistributeTranspiler facade, sharded embeddings."""
import os
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import MasterClient, MasterService
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.framework import Program, program_guard


def _shards(tmp_path, n_files=6, per_file=5):
    from paddle_tpu.native.recordio import RecordIOWriter

    paths = []
    for i in range(n_files):
        p = str(tmp_path / f"shard-{i:02d}.rio")
        w = RecordIOWriter(p)
        for j in range(per_file):
            w.write(f"{i}:{j}".encode())
        w.close()
        paths.append(p)
    return paths


def test_master_lease_and_finish(tmp_path):
    svc = MasterService(chunks_per_task=2, lease_timeout=60)
    svc.set_dataset(_shards(tmp_path))
    seen = []
    while True:
        t = svc.get_task()
        if t is None:
            break
        seen.append(tuple(t.paths))
        svc.task_finished(t.id)
    assert svc.all_done()
    assert len(seen) == 3  # 6 shards / 2 per task
    assert svc.stats()["done"] == 3


def test_master_lease_timeout_requeues(tmp_path):
    svc = MasterService(chunks_per_task=6, lease_timeout=0.2, failure_max=5)
    svc.set_dataset(_shards(tmp_path))
    t1 = svc.get_task()
    assert t1 is not None
    assert svc.get_task() is None  # leased, nothing else to hand out
    time.sleep(0.25)
    t2 = svc.get_task()  # expired lease requeued
    assert t2 is not None and t2.id == t1.id
    assert t2.num_failures == 1
    # the stale holder cannot finish the re-leased task
    assert not svc.task_finished(t1.id, t1.epoch)


def test_master_failure_max_drops(tmp_path):
    svc = MasterService(chunks_per_task=6, lease_timeout=60, failure_max=2)
    svc.set_dataset(_shards(tmp_path))
    t = svc.get_task()
    svc.task_failed(t.id)
    t = svc.get_task()
    svc.task_failed(t.id)  # second failure -> dropped
    assert svc.get_task() is None
    assert svc.all_done()
    assert svc.stats()["dropped"] == 1


def test_master_snapshot_recovery(tmp_path):
    snap = str(tmp_path / "master.snap")
    svc = MasterService(chunks_per_task=2, lease_timeout=60,
                        snapshot_path=snap)
    svc.set_dataset(_shards(tmp_path))
    t = svc.get_task()
    done_one = svc.get_task()
    svc.task_finished(done_one.id)
    assert os.path.exists(snap)

    # "master crashes"; a new one recovers from the snapshot: the pending
    # lease comes back as todo, done stays done
    svc2 = MasterService(chunks_per_task=2, lease_timeout=60,
                         snapshot_path=snap)
    st = svc2.stats()
    assert st["done"] == 1
    assert st["todo"] == 2  # 1 remaining + 1 recovered lease
    ids = set()
    while True:
        task = svc2.get_task()
        if task is None:
            break
        ids.add(task.id)
        svc2.task_finished(task.id)
    assert t.id in ids
    assert svc2.all_done()


def test_master_set_dataset_idempotent_after_recover(tmp_path):
    """The set_dataset idempotency guard must survive a restart (ADVICE
    r4, master.py:97): after recovery, the first worker re-registering the
    UNCHANGED shard list must not reset the queues — a reset would
    invalidate in-flight leases and re-serve finished tasks. The pass
    counter survives too."""
    snap = str(tmp_path / "master.snap")
    shards = _shards(tmp_path)
    svc = MasterService(chunks_per_task=2, lease_timeout=60,
                        snapshot_path=snap)
    svc.set_dataset(shards)
    done_one = svc.get_task()
    svc.task_finished(done_one.id)

    svc2 = MasterService(chunks_per_task=2, lease_timeout=60,
                         snapshot_path=snap)
    before = svc2.stats()
    assert before["done"] == 1
    svc2.set_dataset(shards)  # worker (re)joining after the restart
    assert svc2.stats() == before, "unchanged set_dataset reset the queues"
    # a CHANGED list still resets (that is a genuinely new dataset)
    svc2.set_dataset(shards[:2])
    assert svc2.stats()["done"] == 0 and svc2.stats()["todo"] == 1

    # pass counter survives recovery
    svc3 = MasterService(chunks_per_task=6, lease_timeout=60,
                         snapshot_path=str(tmp_path / "m2.snap"))
    svc3.set_dataset(shards)
    t = svc3.get_task()
    svc3.task_finished(t.id)
    assert svc3.new_pass()
    svc4 = MasterService(chunks_per_task=6, lease_timeout=60,
                         snapshot_path=str(tmp_path / "m2.snap"))
    assert svc4.stats()["pass"] == 1


def test_master_snapshot_corruption_detected(tmp_path):
    snap = str(tmp_path / "master.snap")
    svc = MasterService(snapshot_path=snap)
    svc.set_dataset(_shards(tmp_path))
    blob = bytearray(open(snap, "rb").read())
    blob[-1] ^= 0xFF
    open(snap, "wb").write(bytes(blob))
    with pytest.raises(IOError):
        MasterService(snapshot_path=snap)


def test_master_tcp_client_records(tmp_path):
    svc = MasterService(chunks_per_task=2, lease_timeout=60)
    addr = svc.serve()
    try:
        client = MasterClient(addr=addr)
        client.set_dataset(_shards(tmp_path))
        recs = sorted(client.records())
        expect = sorted(f"{i}:{j}".encode() for i in range(6)
                        for j in range(5))
        assert recs == expect
        assert client.all_done()
        client.close()
    finally:
        svc.shutdown()


def _build_mlp():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.fc(input=x, size=8, act="relu",
                      param_attr=fluid.ParamAttr(name="w1"),
                      bias_attr=fluid.ParamAttr(name="b1"))
        p = layers.fc(input=h, size=1,
                      param_attr=fluid.ParamAttr(name="w2"),
                      bias_attr=fluid.ParamAttr(name="b2"))
        cost = layers.mean(layers.square_error_cost(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
    return main, startup, cost


def test_distribute_transpiler_facade():
    main, startup, cost = _build_mlp()
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers="ps0:6174,ps1:6174", trainers=2)

    # trainer program is the SPMD program itself
    assert t.get_trainer_program() is main

    # every param is assigned to exactly one pserver
    all_params = {"w1", "b1", "w2", "b2"}
    assert set(t.param_assignment) == all_params
    assert set(t.param_assignment.values()) <= {"ps0:6174", "ps1:6174"}

    # pserver program slice: owns its params + the sgd ops updating them,
    # and nothing else (the reference's transpiler-rewrite assertion style)
    for ep in ("ps0:6174", "ps1:6174"):
        owned = {n for n, e in t.param_assignment.items() if e == ep}
        pp = t.get_pserver_program(ep)
        got_params = {n for n in pp.global_block().vars if n in all_params}
        assert got_params == owned
        for op in pp.global_block().ops:
            assert op.desc.type == "sgd"
            assert set(op.desc.output_names()) & owned
        sp = t.get_startup_program(ep, pp)
        # startup initializes everything the pserver program touches:
        # owned params AND their LR/accumulator globals (a pserver
        # missing its velocity/LR init cannot run — r3 fix)
        pserver_vars = set(pp.global_block().vars)
        for op in sp.global_block().ops:
            assert set(op.desc.output_names()) & pserver_vars
        initialized = {n for op in sp.global_block().ops
                       for n in op.desc.output_names()}
        assert owned <= initialized

    # hash_name split is stable across processes
    from paddle_tpu.fluid.distribute_transpiler import hash_name

    a1 = hash_name(sorted(all_params), ["a", "b"])
    a2 = hash_name(sorted(all_params), ["a", "b"])
    assert a1 == a2


def test_transpiler_mesh_and_plan_run():
    """The TPU-native handles: transpile -> mesh()+sharding_plan() ->
    ParallelExecutor trains data-parallel over 8 devices."""
    from paddle_tpu.fluid import unique_name

    with unique_name.guard():
        main, startup, cost = _build_mlp()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        t = fluid.DistributeTranspiler()
        t.transpile(trainer_id=0, program=main, trainers=8)
        pe = fluid.ParallelExecutor(
            loss_name=cost.name, main_program=main, mesh=t.mesh(),
            sharding_plan=t.sharding_plan(),
        )
        rng = np.random.RandomState(0)
        xs = rng.rand(64, 4).astype(np.float32)
        w = rng.rand(4, 1).astype(np.float32)
        ys = (xs @ w).astype(np.float32)
        losses = [pe.run(fetch_list=[cost], feed={"x": xs, "y": ys})[0].item()
                  for _ in range(10)]
        assert losses[-1] < losses[0]


def test_sharded_embedding_plan():
    """is_distributed embedding -> rows sharded over the mesh (the sparse
    pserver capability, distributed_lookup_table_design.md)."""
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.parallel import make_mesh

    with unique_name.guard():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            ids = layers.data(name="ids", shape=[1], dtype="int64")
            lbl = layers.data(name="lbl", shape=[8], dtype="float32")
            emb = layers.embedding(
                ids, size=[64, 8], is_distributed=True,
                param_attr=fluid.ParamAttr(name="table"))
            cost = layers.mean(layers.square_error_cost(input=emb, label=lbl))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        t = fluid.DistributeTranspiler()
        t.transpile(trainer_id=0, program=main, trainers=8)
        mesh = make_mesh({"dp": 8})
        pe = fluid.ParallelExecutor(
            loss_name=cost.name, main_program=main, mesh=mesh,
            sharding_plan=t.sharding_plan(embedding_axis="dp"),
        )
        rng = np.random.RandomState(1)
        ids_np = rng.randint(0, 64, size=(16, 1)).astype(np.int64)
        lbl_np = rng.rand(16, 8).astype(np.float32)
        losses = [pe.run(fetch_list=[cost],
                         feed={"ids": ids_np, "lbl": lbl_np})[0].item()
                  for _ in range(5)]
        assert losses[-1] < losses[0]
        # the transpiler found the distributed table and the plan sharded
        # its rows over the mesh (check the spec, not the mesh repr)
        assert t._embedding_rules == ["table"]
        table = scope.find_var("table")
        assert tuple(table.sharding.spec) == ("dp",), table.sharding


def test_checkpoint_resume_with_rotation(tmp_path):
    """Train, checkpoint every step with max_to_keep=2, corrupt nothing:
    resume restores params + optimizer accumulators mid-training."""
    from paddle_tpu.fluid import unique_name

    def build():
        with unique_name.guard():
            main, startup = Program(), Program()
            main.random_seed = startup.random_seed = 3
            with program_guard(main, startup):
                x = layers.data(name="x", shape=[4], dtype="float32")
                y = layers.data(name="y", shape=[1], dtype="float32")
                p = layers.fc(input=x, size=1,
                              param_attr=fluid.ParamAttr(name="w"),
                              bias_attr=fluid.ParamAttr(name="b"))
                cost = layers.mean(
                    layers.square_error_cost(input=p, label=y))
                fluid.optimizer.Momentum(learning_rate=0.05,
                                         momentum=0.9).minimize(cost)
        return main, startup, cost

    rng = np.random.RandomState(0)
    xs = rng.rand(32, 4).astype(np.float32)
    ys = (xs @ rng.rand(4, 1)).astype(np.float32)
    ckdir = str(tmp_path / "ck")

    main, startup, cost = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ref_losses = []
        for step in range(6):
            ref_losses.append(exe.run(main, feed={"x": xs, "y": ys},
                                      fetch_list=[cost])[0].item())
            if step == 2:
                fluid.save_checkpoint(ckdir, main, step=step, scope=scope,
                                      max_to_keep=2)

    # rotation kept at most 2 payloads
    import os as _os
    kept = [f for f in _os.listdir(ckdir) if f.endswith(".npz")]
    assert len(kept) <= 2

    # fresh process state; resume from step 2 and replay steps 3..5
    main2, startup2, cost2 = build()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor()
        exe.run(startup2)
        step = fluid.load_checkpoint(ckdir, main2, scope=scope2)
        assert step == 2
        resumed = [exe.run(main2, feed={"x": xs, "y": ys},
                           fetch_list=[cost2])[0].item()
                   for _ in range(3)]
    np.testing.assert_allclose(resumed, ref_losses[3:], rtol=1e-5)


def test_master_stale_lease_rejected(tmp_path):
    """A trainer whose lease expired cannot finish/fail the re-leased task
    (epoch guard, go/master parity)."""
    svc = MasterService(chunks_per_task=6, lease_timeout=0.2, failure_max=10)
    svc.set_dataset(_shards(tmp_path))
    stale = svc.get_task()
    time.sleep(0.25)  # lease expires
    fresh = svc.get_task()
    assert fresh.id == stale.id and fresh.epoch != stale.epoch
    # stale holder reports back — must be ignored
    assert not svc.task_finished(stale.id, stale.epoch)
    assert not svc.task_failed(stale.id, stale.epoch)
    # current holder's report works
    assert svc.task_finished(fresh.id, fresh.epoch)
    assert svc.all_done()


# --- leader election / HA (election.py; reference go/master/etcd_client.go,
# go/pserver/etcd_client.go TTL leases) ----------------------------------


def test_file_lease_mutual_exclusion(tmp_path):
    from paddle_tpu.distributed import FileLease

    lp = str(tmp_path / "lease")
    a = FileLease(lp, "a", ttl=60)
    b = FileLease(lp, "b", ttl=60)
    assert a.try_acquire(("h", 1))
    assert not b.try_acquire(("h", 2))       # held
    assert a.renew(("h", 1))
    assert not b.renew(("h", 2))             # not the holder
    a.release()
    assert b.try_acquire(("h", 2))           # free after release
    assert not a.renew(("h", 1))             # a lost it


def test_file_lease_expiry_allows_takeover(tmp_path):
    from paddle_tpu.distributed import FileLease

    lp = str(tmp_path / "lease")
    a = FileLease(lp, "a", ttl=0.2)
    b = FileLease(lp, "b", ttl=60)
    assert a.try_acquire(("h", 1))
    assert not b.try_acquire(("h", 2))
    time.sleep(0.3)                          # a's lease expires (no renew)
    assert b.try_acquire(("h", 2))
    assert not a.renew(("h", 1))


def test_master_crash_standby_takeover_mid_epoch(tmp_path):
    """Kill the leader mid-epoch: the standby must take over from the
    shared snapshot, the client must re-resolve + reconnect, and every
    record must still be delivered (leases the dead master handed out
    simply time out and requeue)."""
    from paddle_tpu.distributed import (
        ElectedMaster, MasterClient, endpoint_resolver,
    )

    lease = str(tmp_path / "master.lease")
    snap = str(tmp_path / "master.snap")
    shards = _shards(tmp_path, n_files=6, per_file=5)

    a = ElectedMaster(lease, snap, holder_id="A", ttl=0.5,
                      chunks_per_task=1, lease_timeout=1.0)
    b = ElectedMaster(lease, snap, holder_id="B", ttl=0.5,
                      chunks_per_task=1, lease_timeout=1.0)
    a.start()
    assert a.wait_leader(5)
    b.start()
    time.sleep(0.2)
    assert not b.is_leader.is_set()          # standby while A holds

    client = MasterClient(addr_resolver=endpoint_resolver(lease),
                          reconnect_retries=30, reconnect_backoff=0.1)
    try:
        client.set_dataset(shards)
        recs = []
        it = client.records()
        for _ in range(7):                   # partway through the epoch
            recs.append(next(it))
        a.crash()                            # die WITHOUT releasing: B must
                                             # wait out the TTL (real crash)
        for r in it:                         # client rides the takeover
            recs.append(r)
        assert b.wait_leader(10)
        expect = sorted(f"{i}:{j}".encode() for i in range(6)
                        for j in range(5))
        # every record delivered at least once; interrupted tasks may
        # legitimately replay after requeue (same at-least-once contract as
        # the reference master)
        assert sorted(set(recs)) == expect
        assert client.all_done()
        client.close()
    finally:
        a.crash()
        b.stop()


def test_deposed_master_snapshot_write_fenced(tmp_path):
    """A stale leader must not overwrite the new leader's snapshot: its
    fenced snapshot commit raises MasterDeposed once the lease moves."""
    from paddle_tpu.distributed import FileLease, MasterService
    from paddle_tpu.distributed.master import MasterDeposed

    lp, snap = str(tmp_path / "lease"), str(tmp_path / "snap")
    a = FileLease(lp, "a", ttl=0.2)
    b = FileLease(lp, "b", ttl=60)
    assert a.try_acquire(("h", 1))
    svc = MasterService(chunks_per_task=1, snapshot_path=snap,
                        snapshot_fence=a.fenced)
    svc.set_dataset(_shards(tmp_path))          # snapshots fine while held
    time.sleep(0.3)
    assert b.try_acquire(("h", 2))              # lease moved to b
    with pytest.raises(MasterDeposed):
        svc.get_task()                          # mutation -> fenced write


def test_election_failed_leadership_is_surfaced(tmp_path):
    """A candidate that wins the lease but cannot start (corrupt snapshot)
    must release the lease and record the failure instead of wedging
    silently with the lease held."""
    from paddle_tpu.distributed import ElectedMaster

    lease = str(tmp_path / "lease")
    snap = str(tmp_path / "snap")
    with open(snap, "wb") as f:
        f.write(b"\x00" * 16)                   # corrupt (bad crc)
    em = ElectedMaster(lease, snap, holder_id="A", ttl=0.5)
    em.start()
    try:
        assert not em.wait_leader(1.5)
        assert isinstance(em.last_error, IOError)
        # the lease was released, not leaked: a healthy candidate can win
        os.remove(snap)
        assert em.wait_leader(5)                # A itself recovers too
    finally:
        em.stop()


def test_deposed_master_severs_client_connections(tmp_path):
    """shutdown() must close ESTABLISHED connections, not just the
    listener — otherwise clients of a deposed leader never re-resolve."""
    svc = MasterService(chunks_per_task=1, lease_timeout=60)
    addr = svc.serve()
    client = MasterClient(addr=addr, reconnect_retries=0)
    client.set_dataset(_shards(tmp_path))       # opens the connection
    svc.shutdown()
    with pytest.raises(ConnectionError):
        client.stats()
    client.close()


def test_pserver_program_includes_lr_decay_chain():
    """The pserver slice must contain the optimize ops AND their LR-decay
    dependency chain (reference moves decay ops to the pserver,
    distribute_transpiler.py:263); forward/backward ops and anything
    consuming gradients stay trainer-side."""
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.fluid.framework import program_guard

    with unique_name.guard():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.data(name="y", shape=[1], dtype="float32")
            h = layers.fc(input=x, size=8, act="relu",
                          param_attr=fluid.ParamAttr(name="pw1"),
                          bias_attr=fluid.ParamAttr(name="pb1"))
            p = layers.fc(input=h, size=1,
                          param_attr=fluid.ParamAttr(name="pw2"),
                          bias_attr=fluid.ParamAttr(name="pb2"))
            cost = layers.mean(layers.square_error_cost(input=p, label=y))
            lr = layers.exponential_decay(learning_rate=0.1, decay_steps=10,
                                          decay_rate=0.9, staircase=True)
            fluid.optimizer.Momentum(learning_rate=lr,
                                     momentum=0.9).minimize(cost)

        t = fluid.DistributeTranspiler()
        eps = ["ps0:6174", "ps1:6174"]
        t.transpile(trainer_id=0, program=main, startup_program=startup,
                    pservers=",".join(eps), trainers=2)

        all_owned = []
        for ep in eps:
            prog = t.get_pserver_program(ep)
            ops = [op.desc.type for op in prog.global_block().ops]
            owned = {n for n, e in t.param_assignment.items() if e == ep}
            all_owned.extend(owned)
            assert owned, ep
            # optimizer ops for every owned param
            assert ops.count("momentum") == len(owned), (ep, ops)
            # the LR-decay chain came along (counter + decay arithmetic)
            assert "increment" in ops or "autoincreased_step_counter" in ops \
                or any("decay" in o or o in ("elementwise_div", "floor",
                                             "elementwise_pow", "scale")
                       for o in ops), ops
            # no forward / backward ops leak in
            assert "mul" not in ops and "square_error_cost" not in ops
            assert not any(o.endswith("_grad") for o in ops)
        assert sorted(all_owned) == sorted(
            ["pw1", "pb1", "pw2", "pb2"])


def test_worker_registry_elastic_membership(tmp_path):
    """Elastic membership (reference go/pserver/etcd_client.go Register:70):
    workers claim TTL slots, listers see only live members, a dead worker's
    slot expires and is reclaimed by a newcomer."""
    from paddle_tpu.distributed import WorkerRegistry

    root = str(tmp_path / "workers")
    a = WorkerRegistry(root, "trainer-a", ttl=0.5)
    b = WorkerRegistry(root, "trainer-b", ttl=0.5)
    assert a.register() == 0
    assert b.register() == 1
    assert a.wait_for(2) == ["trainer-a", "trainer-b"]
    assert a.is_registered() and b.is_registered()

    # crash a (heartbeat stops without release): slot 0 expires...
    a._stop.set()
    a._thread.join(timeout=5)
    time.sleep(0.8)
    assert b.members() == {1: "trainer-b"}
    # ...and a newcomer reclaims the lowest free slot
    c = WorkerRegistry(root, "trainer-c", ttl=0.5)
    assert c.register() == 0
    assert b.wait_for(2) == ["trainer-c", "trainer-b"]

    # clean departure disappears immediately
    b.deregister()
    assert c.members() == {0: "trainer-c"}
    c.deregister()
    assert c.members() == {}


def test_worker_registry_same_id_two_processes_get_two_slots(tmp_path):
    """Two registry instances with the SAME worker_id (restart race) must
    claim different slots — never silently share one lease."""
    from paddle_tpu.distributed import WorkerRegistry

    root = str(tmp_path / "workers")
    a = WorkerRegistry(root, "trainer-x", ttl=60)
    b = WorkerRegistry(root, "trainer-x", ttl=60)
    sa, sb = a.register(), b.register()
    assert sa != sb
    assert a.is_registered() and b.is_registered()
    # old instance departing must not evict the new one
    a.deregister()
    assert b.is_registered()
    assert list(b.members().values()) == ["trainer-x"]
    b.deregister()


def test_master_client_timeout_sec(tmp_path):
    """`timeout_sec` must be a real dial+RPC deadline (reference ctypes
    client honored it, python/paddle/v2/master/client.py:25): a master
    that accepts but never replies surfaces as a bounded ConnectionError,
    not a hang."""
    import socket as _socket
    import time as _time

    from paddle_tpu.distributed.master import MasterClient
    from paddle_tpu.v2.master import client as v2c

    silent = _socket.socket()
    silent.bind(("127.0.0.1", 0))
    silent.listen(8)
    try:
        c = MasterClient(addr=silent.getsockname(), timeout=0.5,
                         reconnect_retries=0)
        t0 = _time.monotonic()
        with pytest.raises(ConnectionError):
            c.get_task()
        assert _time.monotonic() - t0 < 5.0
        # the v2 facade threads timeout_sec through to the socket deadline
        fc = v2c(silent.getsockname(), timeout_sec=3)
        assert fc._client._timeout == 3.0
    finally:
        silent.close()


def test_v2_master_client_buf_size_prefetch(tmp_path):
    """buf_size > 0 prefetches records through a BOUNDED background queue
    (role of the reference Go client's buffered record channel) — every
    record still arrives exactly once, across multiple passes."""
    import pickle as _p

    from paddle_tpu.fluid.recordio_writer import (
        convert_reader_to_recordio_file,
    )
    from paddle_tpu.v2.master import client as v2c

    shards = []
    for i in range(3):
        p = str(tmp_path / f"buf_{i}.recordio")
        convert_reader_to_recordio_file(
            p, lambda i=i: iter([i * 10 + j for j in range(4)]))
        shards.append(p)
    svc = MasterService(chunks_per_task=1, lease_timeout=60)
    addr = svc.serve()
    try:
        c = v2c(addr, buf_size=2)
        c.set_dataset(shards)
        assert c._pump is not None and c._pump.q.maxsize == 2
        pass0 = []
        while True:
            r = c.next_record()
            if r is None:
                break
            pass0.append(_p.loads(r))
        assert sorted(pass0) == sorted(i * 10 + j
                                       for i in range(3) for j in range(4))
        # after end of pass, further calls keep returning None (same
        # contract as the unbuffered path — not a RuntimeError)
        assert c.next_record() is None
        assert c.next_record() is None
        # second pass through the same bounded-queue path
        c.paddle_start_get_records(1)
        pass1 = []
        while True:
            r = c.next_record()
            if r is None:
                break
            pass1.append(_p.loads(r))
        assert sorted(pass1) == sorted(pass0)
        # starting a pass with records UNCONSUMED must neither deadlock
        # nor stream the leftovers over the wire: the pump stops at its
        # next queue-put and RELEASES its in-flight lease (no failure
        # mark, immediate requeue)
        import time as _time

        c.paddle_start_get_records(2)
        assert c.next_record() is not None
        t0 = _time.monotonic()
        c.paddle_start_get_records(3)
        assert _time.monotonic() - t0 < 2.0, "abandon streamed the pass"
        assert c.next_record() is not None
        st = svc.stats()
        assert st["dropped"] == 0
        # a NEW dataset mid-pass retires the old pump before the reset —
        # two pumps must never lease from the same client concurrently
        shards2 = []
        for i in range(2):
            p = str(tmp_path / f"buf2_{i}.recordio")
            convert_reader_to_recordio_file(
                p, lambda i=i: iter([100 + i * 10 + j for j in range(4)]))
            shards2.append(p)
        c.set_dataset(shards2)
        got = []
        while True:
            r = c.next_record()
            if r is None:
                break
            got.append(_p.loads(r))
        assert sorted(got) == [100, 101, 102, 103, 110, 111, 112, 113]
        c.release()
    finally:
        svc.shutdown()


def test_v2_master_client_prefetch_error_surfaces(tmp_path):
    """A reader error inside the prefetch pump must re-raise from
    next_record(), NOT read as a silent end-of-pass (truncated training
    data)."""
    from paddle_tpu.fluid.recordio_writer import (
        convert_reader_to_recordio_file,
    )
    from paddle_tpu.v2.master import client as v2c

    good = str(tmp_path / "good.recordio")
    convert_reader_to_recordio_file(good, lambda: iter(range(4)))
    corrupt = str(tmp_path / "corrupt.recordio")
    with open(corrupt, "wb") as f:
        f.write(b"\x00not a recordio file\xff" * 16)
    # corrupt shard FIRST: the failure path must fire before any records
    svc = MasterService(chunks_per_task=2, lease_timeout=60, failure_max=1)
    addr = svc.serve()
    try:
        c = v2c(addr, buf_size=4)
        c.set_dataset([corrupt, good])
        with pytest.raises(Exception):
            while c.next_record() is not None:
                pass
        c.release()
    finally:
        svc.shutdown()


def test_cloud_reader_creator(tmp_path):
    """reader.creator.cloud_reader drains a master-managed dataset
    (reference v2 cloud_reader over the Go master, here over
    MasterService TCP)."""
    from paddle_tpu.fluid.recordio_writer import (
        convert_reader_to_recordio_file,
    )
    from paddle_tpu.reader import creator

    shards = []
    for i in range(3):
        p = str(tmp_path / f"cloud_{i}.recordio")
        convert_reader_to_recordio_file(
            p, lambda i=i: iter([(i, j) for j in range(4)]))
        shards.append(p)
    svc = MasterService(chunks_per_task=1, lease_timeout=60)
    addr = svc.serve()
    try:
        ep = f"{addr[0]}:{addr[1]}"
        rows = sorted(creator.cloud_reader(shards, ep)())
        assert rows == sorted((i, j) for i in range(3) for j in range(4))
    finally:
        svc.shutdown()


def test_v2_master_client_facade(tmp_path):
    """paddle.v2.master.client parity surface over the TCP master."""
    from paddle_tpu.fluid.recordio_writer import (
        convert_reader_to_recordio_file,
    )
    from paddle_tpu.v2.master import client as v2_master_client

    p = str(tmp_path / "v2m.recordio")
    convert_reader_to_recordio_file(p, lambda: iter(range(5)))
    svc = MasterService(chunks_per_task=1, lease_timeout=60)
    addr = svc.serve()
    try:
        c = v2_master_client(f"{addr[0]}:{addr[1]}", timeout_sec=5)
        c.set_dataset([p])
        import pickle

        got = []
        while True:
            r = c.next_record()
            if r is None:
                break
            got.append(pickle.loads(r))
        assert sorted(got) == [0, 1, 2, 3, 4]
        assert c.request_save_model(0, 100) == 1
        assert c.request_save_model(1, 100) == 0
        c.release()
    finally:
        svc.shutdown()


def test_master_multi_pass_and_idempotent_set_dataset(tmp_path):
    """(review findings) set_dataset with an unchanged shard list must NOT
    reset the queues out from under the fleet; new_pass re-queues a
    finished pass so epochs after the first see data."""
    from paddle_tpu.fluid.recordio_writer import (
        convert_reader_to_recordio_file,
    )
    from paddle_tpu.v2.master import client as v2c

    shards = []
    for i in range(2):
        p = str(tmp_path / f"mp_{i}.recordio")
        convert_reader_to_recordio_file(p, lambda i=i: iter([i * 10, i * 10 + 1]))
        shards.append(p)
    svc = MasterService(chunks_per_task=1, lease_timeout=60)
    addr = svc.serve()
    try:
        c = v2c(addr)  # tuple endpoint form
        c.set_dataset(shards)
        # a second worker registering the SAME dataset must not reset
        t1 = c._client.get_task()
        c2 = v2c(addr)
        c2.set_dataset(shards)
        assert svc.stats()["pending"] == 1  # the lease survived
        assert c._client.task_finished(t1.id, t1.epoch)  # still valid
        # drain the remainder of pass 0
        import pickle as _p

        [_p.loads(x) for x in c._client.records()]
        assert svc.all_done()
        # pass 1: explicit roll, full dataset again
        assert c._client.new_pass()
        assert not c._client.new_pass()  # idempotent mid-pass... queues full
        pass1 = sorted(_p.loads(x) for x in c._client.records())
        assert pass1 == [0, 1, 10, 11]
        assert svc.stats()["pass"] == 1
        # v2 facade: paddle_start_get_records starts the next epoch
        c.paddle_start_get_records(2)
        seen = []
        while True:
            r = c.next_record()
            if r is None:
                break
            seen.append(_p.loads(r))
        assert sorted(seen) == [0, 1, 10, 11]
        c.release()
        c2.release()
    finally:
        svc.shutdown()


def test_file_lease_adversarial_swap_steps_down(tmp_path):
    """round-4 review weak 6: on storage where the lease state can change
    under the holder (NFS oddities, an operator's manual edit, a
    split-brain writer), the holder must fail SAFE: an adversarial
    rename-in of a foreign lease makes renew() report loss (-> leader
    steps down) and fenced() raise instead of committing."""
    import json as _json

    from paddle_tpu.distributed import FileLease
    from paddle_tpu.distributed.master import MasterDeposed

    lp = str(tmp_path / "lease")
    a = FileLease(lp, "a", ttl=60)
    assert a.try_acquire(("h", 1))

    # adversary atomically renames a foreign, live lease over ours —
    # bypassing the flock protocol entirely (what a broken lock manager
    # permits)
    evil = str(tmp_path / "evil")
    with open(evil, "w") as f:
        _json.dump({"holder": "intruder", "deadline": time.time() + 60,
                    "endpoint": ["h", 9]}, f)
    os.replace(evil, lp)

    assert not a.renew(("h", 1))             # loss observed -> step down
    committed = []
    with pytest.raises(MasterDeposed):
        a.fenced(lambda: committed.append(1))
    assert not committed                     # nothing clobbered
    # and the resolver now points at the intruder's endpoint, not ours
    from paddle_tpu.distributed import endpoint_resolver

    assert endpoint_resolver(lp)() == ("h", 9)


def test_tcp_lease_mutual_exclusion_expiry_and_fencing():
    """tcp_lease.TcpLease: the FileLease contract over a LeaseServer
    (the etcd-role coordination point for storage without trustworthy
    POSIX locks)."""
    from paddle_tpu.distributed.master import MasterDeposed
    from paddle_tpu.distributed.tcp_lease import LeaseServer, TcpLease

    srv = LeaseServer()
    host, port = srv.serve()
    try:
        a = TcpLease((host, port), "m", "a", ttl=60)
        b = TcpLease((host, port), "m", "b", ttl=60)
        assert a.try_acquire(("h", 1))
        assert not b.try_acquire(("h", 2))       # held
        assert a.renew(("h", 1))
        assert not b.renew(("h", 2))             # not the holder
        a.fenced(lambda: None)                   # holder commits fine
        a.release()
        assert b.try_acquire(("h", 2))           # free after release
        assert not a.renew(("h", 1))
        with pytest.raises(MasterDeposed):
            a.fenced(lambda: None)               # deposed holder fenced out

        # expiry: a short-TTL holder that stops renewing loses the lease
        c = TcpLease((host, port), "m2", "c", ttl=0.2)
        d = TcpLease((host, port), "m2", "d", ttl=60)
        assert c.try_acquire()
        assert not d.try_acquire()
        time.sleep(0.3)
        assert d.try_acquire()
        with pytest.raises(MasterDeposed):
            c.fenced(lambda: None)

        # stale TERM is fenced even if the same holder re-acquires later:
        # the term captured before losing the lease no longer verifies
        e = TcpLease((host, port), "m3", "e", ttl=0.2)
        assert e.try_acquire()
        stale_term = e._term
        time.sleep(0.3)
        f = TcpLease((host, port), "m3", "f", ttl=0.2)
        assert f.try_acquire()                   # term bumps
        time.sleep(0.3)
        assert e.try_acquire()                   # e again, later term
        e._term = stale_term
        with pytest.raises(MasterDeposed):
            e.fenced(lambda: None)
    finally:
        srv.shutdown()


def test_snapshot_term_guard_refuses_stale_leader_write(tmp_path):
    """The fencing-TOKEN backstop for TcpLease's check-then-commit window:
    a deposed leader whose fence check passed BEFORE it stalled cannot
    replace the new leader's higher-term snapshot — the commit itself
    compares terms (MasterService._snapshot_locked) and raises, and the
    snapshot on disk keeps the new leader's state."""
    from paddle_tpu.distributed.master import MasterDeposed, MasterService

    snap = str(tmp_path / "m.snap")
    # old leader elected at term 3: its fence never fires (simulating a
    # check that passed before the stall — the exact race window)
    old = MasterService(chunks_per_task=1, snapshot_path=snap,
                        snapshot_term=3)
    # new leader at term 5 recovers and commits its own state
    new = MasterService(chunks_per_task=1, snapshot_path=snap,
                        snapshot_term=5)
    new.set_dataset(["s1", "s2"])  # snapshots at term 5
    with pytest.raises(MasterDeposed):
        old.set_dataset(["stale1"])  # stale rename refused by term guard
    # disk still holds the term-5 state: a recovery sees the new leader's
    # dataset, not the stale one
    rec = MasterService(chunks_per_task=1, snapshot_path=snap,
                        snapshot_term=6)
    assert rec._dataset_paths == ["s1", "s2"]
    # and equal/higher terms still commit (the guard is strictly >)
    rec.set_dataset(["s1", "s2", "s3"])


def test_legacy_snapshot_format_recovers_and_recommits(tmp_path):
    """Pre-term (crc|payload) snapshots written by earlier releases must
    recover (term 0) and remain committable — no manual file surgery on
    upgrade."""
    import pickle
    import struct
    import zlib

    from paddle_tpu.distributed.master import MasterService

    state = {"todo": [], "pending": [], "done": [], "dropped": [],
             "next_id": 0, "epoch": 0, "dataset_paths": ["a", "b"],
             "pass": 0}
    payload = pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL)
    snap = str(tmp_path / "legacy.snap")
    with open(snap, "wb") as f:
        f.write(struct.pack("<I", zlib.crc32(payload)) + payload)
    svc = MasterService(chunks_per_task=1, snapshot_path=snap)
    assert svc._dataset_paths == ["a", "b"]
    svc.set_dataset(["a", "b", "c"])  # re-commits in the new format
    svc2 = MasterService(chunks_per_task=1, snapshot_path=snap)
    assert svc2._dataset_paths == ["a", "b", "c"]


def test_standalone_service_adopts_higher_snapshot_term(tmp_path):
    """A standalone (term 0) or post-lease-server-restart (low-term)
    service over a higher-term snapshot adopts the on-disk term instead
    of raising MasterDeposed on every mutation forever."""
    from paddle_tpu.distributed.master import MasterService

    snap = str(tmp_path / "m.snap")
    leader = MasterService(chunks_per_task=1, snapshot_path=snap,
                           snapshot_term=7)
    leader.set_dataset(["x"])
    standalone = MasterService(chunks_per_task=1, snapshot_path=snap)
    assert standalone._snapshot_term == 7
    standalone.set_dataset(["x", "y"])  # commits (adopted term)


def test_lease_server_persists_terms_across_restart(tmp_path):
    """LeaseServer(state_path=...) carries fencing terms across restarts,
    so term-stamped snapshots never outrank a freshly-elected leader."""
    from paddle_tpu.distributed.tcp_lease import LeaseServer, TcpLease

    state = str(tmp_path / "leases.json")
    srv = LeaseServer(state_path=state)
    addr = srv.serve()
    try:
        a = TcpLease(addr, "m", "a", ttl=60)
        assert a.try_acquire()
        term_before = a.term
        assert term_before >= 1
    finally:
        srv.shutdown()

    srv2 = LeaseServer(state_path=state)
    addr2 = srv2.serve()
    try:
        b = TcpLease(addr2, "m", "b", ttl=60)
        assert b.try_acquire()
        assert b.term == term_before + 1  # monotonic across restart
    finally:
        srv2.shutdown()


def test_master_crash_takeover_over_tcp_lease(tmp_path):
    """End-to-end HA over the TCP lease backend: leader crash, standby
    takeover from the shared snapshot, client re-resolve through the
    lease server — FileLease semantics, no filesystem locks involved."""
    from paddle_tpu.distributed import ElectedMaster, MasterClient
    from paddle_tpu.distributed.tcp_lease import (LeaseServer, TcpLease,
                                                  tcp_endpoint_resolver)

    srv = LeaseServer()
    addr = srv.serve()
    snap = str(tmp_path / "master.snap")
    shards = _shards(tmp_path, n_files=6, per_file=5)

    a = ElectedMaster(None, snap, ttl=0.5, chunks_per_task=1,
                      lease_timeout=1.0,
                      lease=TcpLease(addr, "master", "A", ttl=0.5))
    b = ElectedMaster(None, snap, ttl=0.5, chunks_per_task=1,
                      lease_timeout=1.0,
                      lease=TcpLease(addr, "master", "B", ttl=0.5))
    a.start()
    try:
        assert a.wait_leader(5)
        b.start()
        time.sleep(0.2)
        assert not b.is_leader.is_set()

        client = MasterClient(
            addr_resolver=tcp_endpoint_resolver(addr, "master"),
            reconnect_retries=30, reconnect_backoff=0.1)
        client.set_dataset(shards)
        recs = []
        it = client.records()
        for _ in range(7):
            recs.append(next(it))
        a.crash()                            # no release: B waits out TTL
        for r in it:
            recs.append(r)
        assert b.wait_leader(10)
        expect = sorted(f"{i}:{j}".encode() for i in range(6)
                        for j in range(5))
        assert sorted(set(recs)) == expect
        assert client.all_done()
        client.close()
    finally:
        a.crash()
        b.stop()
        srv.shutdown()
