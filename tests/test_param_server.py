"""Executable parameter server (reference listen_and_serv_op.cc:78-192,
send_op.cc, recv_op.cc, test_recv_op.py:26): the pserver program produced
by DistributeTranspiler.get_pserver_program actually RUNS behind RPC, with
trainer-side send/recv ops the Executor executes as host ops around the
jitted step. Includes the 2-process localhost async-SGD test (round-2 review
item 3's done-bar)."""
import os
import socket
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.distribute_transpiler import DistributeTranspiler
from paddle_tpu.fluid.framework import Program, program_guard


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _linear_model(seed=5):
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = seed
    with program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        # explicit param names: the pserver process builds this model
        # independently, and unique_name counters are process-global.
        # DETERMINISTIC zero init (not the default Xavier draw): the
        # program's RNG salt hashes the program BYTES, which embed
        # process-global unique_name counters — so the random init (and
        # therefore the loss trajectory the threshold asserts on) used
        # to depend on which tests ran before this one in the process.
        # From w=b=0 the trajectory is identical in every ordering:
        # loss 1.32 -> 0.17 over 20 steps (ratio 0.13, bar is 0.5).
        pred = layers.fc(
            input=x, size=1,
            param_attr=fluid.ParamAttr(
                name="psrv.w",
                initializer=fluid.initializer.ConstantInitializer(0.0)),
            bias_attr=fluid.ParamAttr(
                name="psrv.b",
                initializer=fluid.initializer.ConstantInitializer(0.0)))
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)
    return main, startup, cost


def _feed(step=0):
    rng = np.random.RandomState(100 + step)
    x = rng.rand(8, 4).astype(np.float32)
    y = (x @ np.array([[1.0], [2.0], [-1.0], [0.5]], dtype=np.float32)
         + 0.3).astype(np.float32)
    return {"x": x, "y": y}


def test_pserver_program_executes_in_process():
    """Two pservers split the params; the trainer's send/recv ops move
    grads/params; every optimize step runs in the pserver scopes."""
    ports = _free_ports(2)
    eps = f"127.0.0.1:{ports[0]},127.0.0.1:{ports[1]}"
    main, startup, cost = _linear_model()
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=eps, trainers=1, sync_mode=False)
    servers = [
        t.start_pserver(ep, port=int(ep.rsplit(":", 1)[1]))
        for ep in t.pserver_endpoints
    ]
    try:
        # both endpoints own at least one param (round robin over 2 vars)
        owned = [s.owned_params() for s in servers]
        assert all(owned), owned
        trainer_prog = t.get_trainer_program(send_recv=True)
        types = [op.type for op in trainer_prog.global_block().ops]
        assert types[0] == "recv" and types[-1] == "send"
        assert "sgd" not in types  # optimize moved to the pserver

        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            losses = []
            for i in range(20):
                (l,) = exe.run(trainer_prog, feed=_feed(i),
                               fetch_list=[cost])
                losses.append(float(l.ravel()[0]))
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
        # the updates provably happened server-side
        from paddle_tpu.distributed.param_server import get_client

        from paddle_tpu.distributed.param_server import ParameterClient

        # the final send updated the pserver after the trainer's last
        # recv — pull once more, then trainer state == pserver state
        ParameterClient(t.param_assignment).pull_all(scope)
        total_steps = 0
        for ep, s in zip(t.pserver_endpoints, servers):
            st = get_client(ep).call("stats")
            total_steps += st["steps"]
            for p in s.owned_params():
                np.testing.assert_allclose(
                    np.asarray(scope.find_var(p)),
                    get_client(ep).call("get_param", p), rtol=1e-6)
        assert total_steps == 20 * 2  # 2 params x 20 steps
    finally:
        for s in servers:
            s.shutdown()


def test_pserver_sparse_selected_rows_grad():
    """SelectedRows grads ride the wire and apply row-wise on the pserver
    (reference listen_and_serv sparse branch :181-192)."""
    from paddle_tpu.distributed.param_server import ParameterServer
    from paddle_tpu.fluid.selected_rows import SelectedRows

    vocab, dim = 40, 4
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 3
    with program_guard(main, startup):
        ids = layers.data(name="ids", shape=[1], dtype="int64")
        emb = layers.embedding(input=ids, size=[vocab, dim], is_sparse=True)
        cost = layers.mean(emb)
        fluid.optimizer.SGD(learning_rate=1.0).minimize(cost)
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=ep, trainers=1, sync_mode=False)
    ps = t.start_pserver(ep, port=port)
    try:
        from paddle_tpu.distributed.param_server import ParameterClient

        (w_name,) = ps.owned_params()
        before = ps.get_param(w_name).copy()
        client = ParameterClient(t.param_assignment)
        rows = np.array([3, 7, 3], dtype=np.int32)  # duplicate row 3
        vals = np.ones((3, dim), dtype=np.float32)
        client.send_grad(w_name, SelectedRows(rows, vals, vocab))
        after = client.get_param(w_name)
        # lr=1.0 sgd: row3 -= 2.0 (dup summed), row7 -= 1.0, others frozen
        np.testing.assert_allclose(after[3], before[3] - 2.0, rtol=1e-5)
        np.testing.assert_allclose(after[7], before[7] - 1.0, rtol=1e-5)
        untouched = [i for i in range(vocab) if i not in (3, 7)]
        np.testing.assert_allclose(after[untouched], before[untouched])
    finally:
        ps.shutdown()


def test_pserver_sync_mode_barrier():
    """sync_mode accumulates all trainers' grads, applies the sum once per
    round (reference listen_and_serv sync barrier)."""
    from paddle_tpu.distributed.param_server import ParameterClient

    main, startup, cost = _linear_model()
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=ep, trainers=2, sync_mode=True)
    ps = t.start_pserver(ep, port=port)
    try:
        owned = ps.owned_params()
        before = {p: ps.get_param(p).copy() for p in owned}
        grads = {p: np.ones_like(before[p]) for p in owned}

        def trainer(tid):
            # rounds complete on DISTINCT trainer ids (a duplicate push
            # from one trainer must not phantom-complete a round)
            client = ParameterClient(t.param_assignment, trainer_id=tid)
            for p in owned:
                client.send_grad(p, grads[p])

        threads = [threading.Thread(target=trainer, args=(i,))
                   for i in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
        # round complete -> barrier returns immediately
        ParameterClient(t.param_assignment).barrier()
        stats = ps.stats()
        assert stats["round"] == 1 and stats["steps"] == len(owned)
        for p in owned:
            # one applied update of the SUMMED grad: p -= lr * 2
            np.testing.assert_allclose(
                ps.get_param(p), before[p] - 0.05 * 2.0, rtol=1e-5)
    finally:
        ps.shutdown()


_PSERVER_PROC = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.environ["REPO_ROOT"])
    sys.path.insert(0, os.environ["REPO_ROOT"] + "/tests")
    from test_param_server import _linear_model
    from paddle_tpu.fluid.distribute_transpiler import DistributeTranspiler

    ep = os.environ["PSERVER_EP"]
    main, startup, cost = _linear_model()
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=ep, trainers=1, sync_mode=False)
    ps = t.start_pserver(ep, port=int(ep.rsplit(":", 1)[1]))
    print("PSERVER_READY", flush=True)
    import time
    deadline = time.time() + 120
    while time.time() < deadline:
        time.sleep(0.5)
""")


def test_two_process_async_sgd():
    """THE done-bar: a separate OS process runs the pserver program; this
    process trains via send/recv ops; the trainer's params provably come
    back updated by the pserver process."""
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["PSERVER_EP"] = ep
    env["REPO_ROOT"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    proc = subprocess.Popen([sys.executable, "-c", _PSERVER_PROC], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    try:
        line = proc.stdout.readline()
        assert "PSERVER_READY" in line, (line, proc.stderr.read()[-2000:])

        main, startup, cost = _linear_model()
        t = DistributeTranspiler()
        t.transpile(trainer_id=0, program=main, startup_program=startup,
                    pservers=ep, trainers=1, sync_mode=False)
        trainer_prog = t.get_trainer_program(send_recv=True)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            init_params = {
                p: np.asarray(scope.find_var(p)).copy()
                for p in t.param_assignment
            }
            losses = []
            for i in range(20):
                (l,) = exe.run(trainer_prog, feed=_feed(i),
                               fetch_list=[cost])
                losses.append(float(l.ravel()[0]))
            assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])

            from paddle_tpu.distributed.param_server import get_client

            client = get_client(ep)
            stats = client.call("stats")
            assert stats["steps"] == 20 * len(init_params)
            from paddle_tpu.distributed.param_server import (
                ParameterClient,
            )

            # final send lands after the last recv: pull once more, then
            # the trainer's params ARE the pserver process's params
            ParameterClient(t.param_assignment).pull_all(scope)
            for p in t.param_assignment:
                remote = client.call("get_param", p)
                local = np.asarray(scope.find_var(p))
                np.testing.assert_allclose(local, remote, rtol=1e-6)
                # ...and the pserver moved them off the trainer's init
                assert np.abs(remote - init_params[p]).max() > 1e-4
    finally:
        proc.kill()
        proc.wait()


def test_pserver_lr_decay_advances_once_per_round():
    """The shared LR-decay step counter advances once per ROUND on the
    pserver, not once per param push (reference: ONE lr_decay sub-block in
    listen_and_serv, run per round — a 2-param pserver must not decay at
    2x speed)."""
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 9
    with program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(input=x, size=1,
                         param_attr=fluid.ParamAttr(name="lrd.w"),
                         bias_attr=fluid.ParamAttr(name="lrd.b"))
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        lr = layers.exponential_decay(learning_rate=0.1, decay_steps=1,
                                      decay_rate=0.5, staircase=True)
        fluid.optimizer.SGD(learning_rate=lr).minimize(cost)
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=ep, trainers=1, sync_mode=False)
    ps = t.start_pserver(ep, port=port)
    try:
        from paddle_tpu.distributed.param_server import ParameterClient

        assert ps._shared_prog is not None  # the counter chain was split out
        owned = ps.owned_params()
        assert len(owned) == 2
        client = ParameterClient(t.param_assignment)
        before = {p: client.get_param(p).copy() for p in owned}
        # round 1: one grad per param -> counter must advance ONCE
        for p in owned:
            client.send_grad(p, np.ones_like(before[p]))
        step_var = next(n for n in ps._shared_prog.global_block().vars
                        if "step" in n.lower() or "counter" in n.lower())
        s1 = float(np.asarray(ps._scope.find_var(step_var)).ravel()[0])
        for p in owned:
            client.send_grad(p, np.ones_like(before[p]))
        s2 = float(np.asarray(ps._scope.find_var(step_var)).ravel()[0])
        assert s2 - s1 == 1.0, (s1, s2)  # once per round, not per push
        # and params did move
        for p in owned:
            assert np.abs(client.get_param(p) - before[p]).max() > 1e-6
    finally:
        ps.shutdown()


def test_sync_two_trainers_through_executor_ops():
    """Two trainer THREADS run sync-mode send/recv/send_barrier programs
    (get_trainer_program(send_recv=True)) against one pserver: rounds
    complete, barriers release (no deadlock via the round-number wait +
    dedicated barrier channel), and both trainers see identical params."""
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    main, startup, cost = _linear_model(seed=21)
    t0 = DistributeTranspiler()
    t0.transpile(trainer_id=0, program=main, startup_program=startup,
                 pservers=ep, trainers=2, sync_mode=True)
    ps = t0.start_pserver(ep, port=port)
    try:
        progs = []
        for tid in range(2):
            t = DistributeTranspiler()
            t.transpile(trainer_id=tid, program=main,
                        startup_program=startup, pservers=ep, trainers=2,
                        sync_mode=True)
            progs.append(t.get_trainer_program(send_recv=True))
        types = [op.type for op in progs[0].global_block().ops]
        assert types[-1] == "send_barrier" and types[-2] == "send"

        results = {}

        def trainer(tid):
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor()
                exe.run(startup)
                losses = []
                for i in range(6):
                    (l,) = exe.run(progs[tid], feed=_feed(i),
                                   fetch_list=[cost])
                    losses.append(float(l.ravel()[0]))
                results[tid] = (losses, {
                    p: np.asarray(scope.find_var(p)).copy()
                    for p in t0.param_assignment})

        threads = [threading.Thread(target=trainer, args=(i,))
                   for i in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert set(results) == {0, 1}, "a trainer thread died or hung"
        stats = ps.stats()
        # 6 lockstep rounds, one merged apply per param per round
        assert stats["round"] == 6, stats
        assert stats["steps"] == 6 * len(t0.param_assignment), stats
        # sync SGD: both trainers recv'd identical params each round
        for p in t0.param_assignment:
            np.testing.assert_allclose(results[0][1][p], results[1][1][p],
                                       rtol=1e-6)
        assert results[0][0][-1] < results[0][0][0], results[0][0]
    finally:
        ps.shutdown()


def test_listen_and_serv_send_recv_layers():
    """The reference's Send/Recv/ListenAndServ layer API (layers/io.py:107,
    173, 205; test_recv_op.py:26 pattern): a server block captured with
    do() serves behind RPC; the client program's Send pushes a grad and
    pulls the updated param back."""
    import jax.numpy as jnp

    from paddle_tpu.fluid.framework import Program, program_guard

    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"

    server_prog, server_startup = Program(), Program()
    with program_guard(server_prog, server_startup):
        w = layers.create_parameter(shape=[4], dtype="float32", name="ls.w")
        g = server_prog.global_block().create_var(
            name="ls.w@GRAD", shape=[4], dtype="float32")
        serv = layers.ListenAndServ(ep, inputs=[g], fan_in=1)
        with serv.do():
            server_prog.current_block().append_op(
                "sgd",
                inputs={"Param": ["ls.w"], "Grad": ["ls.w@GRAD"],
                        "LearningRate": ["ls.lr"]},
                outputs={"ParamOut": ["ls.w"]},
            )
        assert serv.get_params_and_grads() == (["ls.w"], ["ls.w@GRAD"])

    scope = fluid.Scope()
    scope.set_var("ls.w", jnp.asarray(np.ones(4, np.float32)))
    scope.set_var("ls.lr", jnp.asarray(np.float32(0.5)))
    ps = serv.run(scope=scope, port=port)
    try:
        client_prog, _ = Program(), Program()
        with program_guard(client_prog, Program()):
            gvar = client_prog.global_block().create_var(
                name="ls.w@GRAD", shape=[4], dtype="float32")
            wvar = client_prog.global_block().create_var(
                name="ls.w", shape=[4], dtype="float32", persistable=True)
            layers.Send(ep, [gvar], get_vars=[wvar])
        cscope = fluid.Scope()
        with fluid.scope_guard(cscope):
            exe = fluid.Executor()
            exe.run(client_prog,
                    feed={"ls.w@GRAD": np.full((4,), 2.0, np.float32)})
        # server applied w -= 0.5 * 2.0; Send's get_vars pulled it back
        np.testing.assert_allclose(
            np.asarray(cscope.find_var("ls.w")), np.zeros(4), atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(scope.find_var("ls.w")), np.zeros(4), atol=1e-6)
    finally:
        ps.shutdown()


def test_rpc_binary_segment_framing_roundtrip():
    """Tensors ride as RAW segments after the JSON header (reference
    sendrecvop_utils.cc zero-copy intent), not base64 — and the legacy
    base64 form still decodes."""
    import io

    from paddle_tpu.distributed.rpc import (
        from_wire, read_msg, to_wire, write_msg,
    )
    from paddle_tpu.fluid.selected_rows import SelectedRows

    arr = np.arange(4096, dtype=np.float32).reshape(64, 64)
    sr = SelectedRows(np.array([1, 5], np.int64),
                      np.ones((2, 3), np.float32), 10)
    msg = {"method": "push", "args": [arr, sr, "name", 7]}
    buf = io.BytesIO()
    write_msg(buf, msg)
    wire_bytes = buf.getvalue()
    # the raw f32 bytes appear verbatim on the wire (no base64 inflation):
    assert arr.tobytes() in wire_bytes
    # header stays small — the 16 KiB tensor didn't inflate the JSON part
    import struct as _struct

    (hdr_len,) = _struct.unpack("<I", wire_bytes[:4])
    assert hdr_len < 2048
    buf.seek(0)
    obj, segs = read_msg(buf)
    got = from_wire(obj, segs)
    np.testing.assert_array_equal(got["args"][0], arr)
    np.testing.assert_array_equal(got["args"][1].rows, sr.rows)
    np.testing.assert_array_equal(got["args"][1].value, sr.value)
    assert got["args"][1].height == 10 and got["args"][2:] == ["name", 7]
    # legacy inline-base64 (no segs) still decodes
    legacy = to_wire({"a": arr})
    np.testing.assert_array_equal(from_wire(legacy)["a"], arr)


def test_rpc_oversized_response_reports_error_frame():
    """An oversized response must surface as an RPC error on the client,
    not an opaque dropped connection (ADVICE r3, rpc.py:96)."""
    import unittest.mock as mock

    from paddle_tpu.distributed import rpc as rpc_mod
    from paddle_tpu.distributed.rpc import RpcClient, RpcServer

    big = np.zeros(1024, np.float32)
    server = RpcServer({"big": lambda: big})
    addr = server.serve()
    try:
        client = RpcClient(addr)
        # sanity: fits normally
        np.testing.assert_array_equal(client.call("big"), big)
        with mock.patch.object(rpc_mod, "MAX_SEGMENT_BYTES", 1024):
            with pytest.raises(RuntimeError, match="exceeding"):
                client.call("big")
        # connection survived and still serves
        np.testing.assert_array_equal(client.call("big"), big)
    finally:
        server.shutdown()


def test_rpc_bad_header_closes_connection():
    """A header frame that fails JSON decode may be followed by raw
    __segs__ bytes the server cannot skip — it must reply with one error
    frame and CLOSE, never read the tensor bytes as the next length prefix
    (ADVICE r4, rpc.py:186)."""
    import socket
    import struct as _struct

    from paddle_tpu.distributed.rpc import RpcServer, read_frame

    server = RpcServer({"ping": lambda: "pong"})
    host, port = server.serve()
    try:
        sock = socket.create_connection((host, port), timeout=10)
        try:
            # well-framed but unparseable header, followed by 64 raw bytes
            # that WOULD desync the stream if the server kept reading
            bad = b'{"method": "push", "__segs__": [64]'  # truncated JSON
            sock.sendall(_struct.pack("<I", len(bad)) + bad)
            sock.sendall(b"\x00" * 64)
            rf = sock.makefile("rb")
            resp = read_frame(rf)
            assert resp["ok"] is False and "bad frame" in resp["error"]
            # server closed: next read hits EOF, no desynced second reply
            assert rf.read(4) == b""
        finally:
            sock.close()
        # invalid-UTF-8 header (tensor bytes misread as a header — the
        # likeliest real-world shape of a desynced stream) gets the same
        # error-then-close treatment, not an uncaught UnicodeDecodeError
        sock = socket.create_connection((host, port), timeout=10)
        try:
            raw = b"\xff\xfe\x00garbage"
            sock.sendall(_struct.pack("<I", len(raw)) + raw)
            rf = sock.makefile("rb")
            resp = read_frame(rf)
            assert resp["ok"] is False and "bad frame" in resp["error"]
            assert rf.read(4) == b""
        finally:
            sock.close()
    finally:
        server.shutdown()


def _emb_model(vocab=100_000, dim=16, seed=7):
    """≥100k-vocab distributed embedding model (reference
    distributed_lookup_table_design.md scale target)."""
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = seed
    with program_guard(main, startup):
        ids = layers.data(name="ids", shape=[1], dtype="int64")
        y = layers.data(name="y", shape=[1], dtype="float32")
        emb = layers.embedding(input=ids, size=[vocab, dim], is_sparse=True,
                               is_distributed=True,
                               param_attr=fluid.ParamAttr(name="demb.w"))
        pred = layers.fc(input=emb, size=1,
                         param_attr=fluid.ParamAttr(name="demb.fc.w"),
                         bias_attr=fluid.ParamAttr(name="demb.fc.b"))
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
    return main, startup, cost


_EMB_PSERVER_PROC = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.environ["REPO_ROOT"])
    sys.path.insert(0, os.environ["REPO_ROOT"] + "/tests")
    from test_param_server import _emb_model
    from paddle_tpu.fluid.distribute_transpiler import DistributeTranspiler

    ep = os.environ["PSERVER_EP"]
    main, startup, cost = _emb_model()
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=ep, trainers=1, sync_mode=False)
    ps = t.start_pserver(ep, port=int(ep.rsplit(":", 1)[1]))
    print("PSERVER_READY", flush=True)
    import time
    deadline = time.time() + 180
    while time.time() < deadline:
        time.sleep(0.5)
""")


def test_two_process_distributed_embedding_prefetch():
    """round-3 review item 3's done-bar: a separate-process pserver owns a
    100k-vocab table; the trainer pulls ONLY the batch's rows (prefetch op)
    and pushes SelectedRows grads back; traffic is proportional to batch
    ids, never to the table; loss decreases."""
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["PSERVER_EP"] = ep
    env["REPO_ROOT"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    proc = subprocess.Popen([sys.executable, "-c", _EMB_PSERVER_PROC],
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert "PSERVER_READY" in line, (line, proc.stderr.read()[-2000:])

        vocab = 100_000
        main, startup, cost = _emb_model(vocab=vocab)
        t = DistributeTranspiler()
        t.transpile(trainer_id=0, program=main, startup_program=startup,
                    pservers=ep, trainers=1, sync_mode=False)
        prog = t.get_trainer_program(send_recv=True)
        types = [op.type for op in prog.global_block().ops]
        assert types[0] == "prefetch" and types[-1] == "send"
        # the embedding is NOT in the dense recv pull
        recv_op = next(op for op in prog.global_block().ops
                       if op.type == "recv")
        assert "demb.w" not in recv_op.desc.outputs["Out"]

        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            # trainer startup never materializes the [100k, 16] table
            exe.run(t.get_trainer_startup_program())
            assert scope.find_var("demb.w") is None
            rng = np.random.RandomState(0)
            target = rng.rand(vocab).astype(np.float32)
            losses = []
            steps, batch = 30, 8
            for i in range(steps):
                b = rng.randint(0, 200, size=(batch, 1)).astype(np.int64)
                (l,) = exe.run(prog, feed={"ids": b,
                                           "y": target[b[:, 0]][:, None]},
                               fetch_list=[cost])
                losses.append(float(np.ravel(l)[0]))
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])

        from paddle_tpu.distributed.param_server import get_client

        st = get_client(ep).call("stats")
        # row-granular: exactly batch ids' worth of rows per step rode the
        # wire for the table; the dense fc params (17 rows/step) are the
        # only full pulls — nothing ever shipped 100k rows
        assert st["prefetch_rows"] == steps * batch, st
        assert st["full_pull_rows"] < vocab // 50, st
    finally:
        proc.kill()
        proc.wait()


def _big_model(seed=11):
    """One ≥16 MiB dense param: fc [2048, 2048] f32 = 16.8 MB."""
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = seed
    with program_guard(main, startup):
        x = layers.data(name="x", shape=[2048], dtype="float32")
        y = layers.data(name="y", shape=[2048], dtype="float32")
        pred = layers.fc(input=x, size=2048,
                         param_attr=fluid.ParamAttr(name="big.w"),
                         bias_attr=False)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    return main, startup, cost


_BIG_TRAINER_PROC = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.environ["REPO_ROOT"])
    sys.path.insert(0, os.environ["REPO_ROOT"] + "/tests")
    import numpy as np
    from paddle_tpu.distributed.param_server import ParameterClient

    ep = os.environ["PSERVER_EP"]
    tid = int(os.environ["TRAINER_ID"])
    steps = int(os.environ["STEPS"])
    client = ParameterClient({"big.w": ep}, trainer_id=tid)
    w0 = client.get_param("big.w")
    nbytes = w0.nbytes
    t_total = 0.0
    for s in range(steps):
        g = np.full(w0.shape, float(tid + 1), np.float32)
        t0 = time.perf_counter()
        client.send_grad("big.w", g)
        client.barrier()
        w = client.get_param("big.w")
        t_total += time.perf_counter() - t0
    mb_s = nbytes * 2 * steps / t_total / 1e6  # push+pull per step
    print(f"TRAINER_DONE {tid} {mb_s:.1f} {float(w.sum()):.6e}", flush=True)
""")


def test_four_trainer_processes_16mb_sync_rounds():
    """round-3 review item 4's done-bar: four trainer PROCESSES push a 16.8 MB
    dense grad each, sync rounds merge all four, and the binary framing
    moves it at wire speed (bytes/s reported and sanity-gated)."""
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    main, startup, cost = _big_model()
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=ep, trainers=4, sync_mode=True)
    ps = t.start_pserver(ep, port=port)
    try:
        w_before = ps.get_param("big.w").copy()
        env_base = dict(os.environ)
        env_base["PSERVER_EP"] = ep
        env_base["REPO_ROOT"] = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        steps = 3
        procs = []
        for tid in range(4):
            env = dict(env_base)
            env["TRAINER_ID"] = str(tid)
            env["STEPS"] = str(steps)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _BIG_TRAINER_PROC], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        rates = []
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, err[-2000:]
            done = [ln for ln in out.splitlines()
                    if ln.startswith("TRAINER_DONE")]
            assert done, (out, err[-1000:])
            rates.append(float(done[0].split()[2]))
        st = ps.stats()
        assert st["round"] == steps, st
        # each round merged the SUM of the 4 trainers' grads:
        # w -= lr * (1+2+3+4) per round
        expect = w_before - 0.01 * 10.0 * steps
        np.testing.assert_allclose(ps.get_param("big.w"), expect, rtol=1e-5)
        # binary framing moves 16.8 MB frames at wire speed — base64 JSON
        # lists topped out at ~1-3 MB/s, which is what this floor guards
        # against (sanity floor, not a benchmark: 4 concurrent trainers on
        # a loaded shared host have measured as low as 18 MB/s, so the
        # floor sits well under that while still 3x the failure mode)
        print("per-trainer MB/s:", rates)
        assert min(rates) > 6.0, rates
    finally:
        ps.shutdown()


def test_trainer_startup_prunes_table_and_accumulators():
    """A distributed table AND its vocab-sized optimizer accumulators must
    not be initialized on the trainer (the design's point is a vocab too
    large for trainer memory)."""
    vocab, dim = 50_000, 8
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 13
    with program_guard(main, startup):
        ids = layers.data(name="ids", shape=[1], dtype="int64")
        y = layers.data(name="y", shape=[1], dtype="float32")
        emb = layers.embedding(input=ids, size=[vocab, dim], is_sparse=True,
                               is_distributed=True,
                               param_attr=fluid.ParamAttr(name="padam.w"))
        pred = layers.fc(input=emb, size=1,
                         param_attr=fluid.ParamAttr(name="padam.fc.w"))
        # prefix-colliding UNRELATED param: shares the table's name as a
        # prefix but is a dense trainer-side param (ADVICE r4 — a wildcard
        # '<table>_*' prune would silently drop its initializer)
        pred = layers.fc(input=pred, size=1,
                         param_attr=fluid.ParamAttr(name="padam.w_proj"))
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(cost)
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers="127.0.0.1:9", trainers=1, sync_mode=False)
    ts = t.get_trainer_startup_program()
    names = set(ts.global_block().vars)
    assert "padam.w" not in names, sorted(names)
    assert not any(n.startswith("padam.w_moment") for n in names), \
        sorted(names)
    # the startup DID have vocab-sized accumulators before pruning
    orig = set(startup.global_block().vars)
    assert any(n.startswith("padam.w_moment") for n in orig), sorted(orig)
    # the dense fc param stays
    assert any(n.startswith("padam.fc.w") for n in names)
    # the prefix-colliding dense param and ITS initializer survive: pruning
    # is by exact optimize-op output names, not name prefix
    assert "padam.w_proj" in names, sorted(names)
    init_outs = {n for op in ts.global_block().ops
                 for n in op.desc.output_names()}
    assert "padam.w_proj" in init_outs


def test_sync_four_trainers_through_executor_ops():
    """Sync rounds scale past two trainers THROUGH the executor's
    send/recv/send_barrier host ops: four trainer threads, lockstep
    rounds, identical post-round params."""
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    main, startup, cost = _linear_model(seed=29)
    t0 = DistributeTranspiler()
    t0.transpile(trainer_id=0, program=main, startup_program=startup,
                 pservers=ep, trainers=4, sync_mode=True)
    ps = t0.start_pserver(ep, port=port)
    try:
        progs = []
        for tid in range(4):
            t = DistributeTranspiler()
            t.transpile(trainer_id=tid, program=main,
                        startup_program=startup, pservers=ep, trainers=4,
                        sync_mode=True)
            progs.append(t.get_trainer_program(send_recv=True))

        results = {}

        def trainer(tid):
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor()
                exe.run(startup)
                for i in range(4):
                    exe.run(progs[tid], feed=_feed(i), fetch_list=[cost])
                results[tid] = {
                    p: np.asarray(scope.find_var(p)).copy()
                    for p in t0.param_assignment}

        threads = [threading.Thread(target=trainer, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
        assert set(results) == {0, 1, 2, 3}, "a trainer thread died or hung"
        stats = ps.stats()
        assert stats["round"] == 4, stats
        assert stats["steps"] == 4 * len(t0.param_assignment), stats
        for p in t0.param_assignment:
            for tid in (1, 2, 3):
                np.testing.assert_allclose(results[0][p], results[tid][p],
                                           rtol=1e-6)
    finally:
        ps.shutdown()


def test_async_concurrent_cross_param_applies_are_exact():
    """Async applies serialize PER PARAM, not globally: eight threads
    hammer two params concurrently and every single gradient must land —
    final value == init - lr * pushes (a dropped read-modify-write would
    break the arithmetic)."""
    main, startup, cost = _linear_model(seed=51)
    port = _free_ports(1)[0]
    ep = f"127.0.0.1:{port}"
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=ep, trainers=1, sync_mode=False)
    ps = t.start_pserver(ep, port=port)
    try:
        from paddle_tpu.distributed.param_server import ParameterClient

        owned = ps.owned_params()
        assert len(owned) == 2
        before = {p: ps.get_param(p).copy() for p in owned}
        pushes_per_thread, n_threads = 25, 8
        errors = []

        def hammer(tid):
            try:
                client = ParameterClient(t.param_assignment, trainer_id=tid)
                for i in range(pushes_per_thread):
                    p = owned[(tid + i) % 2]
                    client.send_grad(p, np.ones_like(before[p]))
            except Exception as e:  # surface thread failures in the test
                errors.append(e)

        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not errors, errors
        stats = ps.stats()
        total = pushes_per_thread * n_threads
        assert stats["steps"] == total, stats
        counts = {p: sum(1 for tid in range(n_threads)
                         for i in range(pushes_per_thread)
                         if owned[(tid + i) % 2] == p) for p in owned}
        for p in owned:
            # lr=0.05 SGD, unit grads: every push must have landed exactly
            np.testing.assert_allclose(
                ps.get_param(p), before[p] - 0.05 * counts[p],
                rtol=1e-4, atol=1e-4)
    finally:
        ps.shutdown()
