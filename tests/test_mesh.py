"""paddle_tpu.mesh (ISSUE 15): MeshSpec/ShardingRules units, dp x tp x
fsdp sharded-vs-single-device transformer training numerics, mesh-
sharded decode serving (KV pool over the kv-head axis, churn with zero
post-warm compiles), sharded checkpoint round-trips, and the mesh
observability surface — all on the virtual 8-device CPU mesh."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddle_tpu.mesh import (MeshSpec, ShardingRules, decoder_rules,
                             mesh_status, shard_param_tree,
                             transformer_rules)
from paddle_tpu.observability import metrics


# --- MeshSpec ------------------------------------------------------------

def test_mesh_spec_parse_and_roundtrip():
    ms = MeshSpec.parse("dp=2, tp=2, fsdp=2")
    assert ms.axis_names == ("dp", "tp", "fsdp")
    assert ms.size == 8
    assert ms.axis_size("fsdp") == 2
    assert "tp" in ms and "sp" not in ms
    assert MeshSpec.from_dict(ms.to_dict()) == ms
    assert MeshSpec.coerce(str(ms)) == ms
    assert MeshSpec.coerce({"tp": 4}) == MeshSpec.parse("tp=4")


@pytest.mark.parametrize("bad", ["dp=0", "dp", "dp=x", "dp=2,dp=4",
                                 "2dp=2", ""])
def test_mesh_spec_refusals(bad):
    with pytest.raises(ValueError):
        MeshSpec.parse(bad)


def test_mesh_spec_build_needs_devices():
    # 16 > the 8 virtual devices: typed, names the fix
    with pytest.raises(ValueError, match="device_count"):
        MeshSpec.parse("dp=16").build()
    mesh = MeshSpec.parse("dp=2,tp=2").build()  # 4 of 8 devices is fine
    assert mesh.devices.size == 4
    assert mesh.axis_names == ("dp", "tp")


# --- ShardingRules -------------------------------------------------------

def test_transformer_rules_name_assignment():
    r = transformer_rules()
    assert tuple(r.spec_for("enc0.self.q.w", 2)) == ("fsdp", "tp")
    assert tuple(r.spec_for("dec1.cross.k.w", 2)) == ("fsdp", "tp")
    assert tuple(r.spec_for("enc0.self.out.w", 2)) == ("tp", "fsdp")
    assert tuple(r.spec_for("enc0.ff1.w", 2)) == ("fsdp", "tp")
    assert tuple(r.spec_for("enc0.ff2.w", 2)) == ("tp", "fsdp")
    assert tuple(r.spec_for("enc.emb", 2)) == ("tp", "fsdp")
    # optimizer accumulators inherit their param's spec via the name
    # tail; scalars replicate via the ndim guard
    assert tuple(r.spec_for("enc0.self.q.w_moment1_0", 2)) == \
        ("fsdp", "tp")
    assert tuple(r.spec_for("enc0.self.q.w_beta1_pow_acc_0", 0)) == ()
    # layer norms shard dim 0 over fsdp; feeds shard on batch
    assert tuple(r.spec_for("enc0.a.ln.scale", 1)) == ("fsdp",)
    assert tuple(r.feed_spec(2)) == ("dp", None)


def test_decoder_rules_and_serialization():
    d = decoder_rules()
    assert tuple(d.spec_for("layer0/wk", 2)) == (None, "tp")
    assert tuple(d.spec_for("layer3/wo", 2)) == ("tp", None)
    assert tuple(d.spec_for("tok_emb", 2)) == ("tp", None)
    assert tuple(d.spec_for("layer0/ln1/0", 1)) == ()
    rt = ShardingRules.from_dict(d.to_dict())
    assert tuple(rt.spec_for("layer0/wk", 2)) == (None, "tp")
    assert rt.to_dict() == d.to_dict()
    # unknown-axis rules are refused when a mesh is given to check
    with pytest.raises(ValueError, match="nope"):
        ShardingRules([(r"x", P("nope"))],
                      mesh_spec=MeshSpec.parse("tp=2"))


def test_rules_first_match_wins_and_with_rule():
    r = ShardingRules([(r"\.w$", P("tp", None))], batch_axis=None)
    r2 = r.with_rule(r".", P("fsdp"))
    assert tuple(r2.spec_for("a.w", 2)) == ("tp", None)  # earlier wins
    assert tuple(r2.spec_for("a.b", 1)) == ("fsdp",)
    assert tuple(r.spec_for("a.b", 1)) == ()  # original untouched


def test_shard_param_tree_by_name():
    mesh = MeshSpec.parse("tp=2").build()
    tree = {"layer0": {"wk": np.ones((8, 8), np.float32),
                       "ln1": (np.ones(8, np.float32),) * 2},
            "tok_emb": np.ones((9, 8), np.float32)}  # 9 % 2 != 0
    out = shard_param_tree(tree, mesh, decoder_rules())
    assert tuple(out["layer0"]["wk"].sharding.spec) == (None, "tp")
    assert isinstance(out["layer0"]["ln1"], tuple)
    # indivisible vocab best-efforts to replication instead of dying
    assert tuple(out["tok_emb"].sharding.spec) == ()
    strict = ShardingRules(decoder_rules().to_dict()["rules"],
                           batch_axis=None, best_effort=False)
    with pytest.raises(ValueError, match="tok_emb"):
        shard_param_tree(tree, mesh, strict)


# --- dp x tp x fsdp training ---------------------------------------------

def test_transformer_trains_dp_tp_fsdp_numerics_match():
    """THE training acceptance: the flagship transformer trains one
    Adam step on a dp=2 x tp=2 x fsdp=2 mesh; loss matches the
    single-device run on the SAME seeded initial state (f32 reduction
    reorder tolerance), params/accumulators actually shard, and the
    compiled step contains real collectives (counter evidence)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        src_vocab=40, trg_vocab=40, max_len=8, d_model=32, n_heads=4,
        d_ff=64, n_layers=1, dropout=0.0,
    )
    main, startup, scope = Program(), Program(), fluid.Scope()
    main.random_seed = startup.random_seed = 5
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            src = layers.data(name="src", shape=[cfg.max_len],
                              dtype="int64")
            trg = layers.data(name="trg", shape=[cfg.max_len],
                              dtype="int64")
            lbl = layers.data(name="lbl", shape=[cfg.max_len, 1],
                              dtype="int64")
            avg_cost, _ = transformer.build_train(cfg, src, trg, lbl)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
        exe = fluid.Executor()
        exe.run(startup)
        init_state = {n: np.array(scope.find_var(n))
                      for n in scope.var_names()}
        pe = fluid.ParallelExecutor(
            loss_name=avg_cost.name, main_program=main,
            mesh=MeshSpec.parse("dp=2,tp=2,fsdp=2"),
            sharding_plan=transformer_rules(),
        )
        rng = np.random.RandomState(0)
        s = rng.randint(3, 40, size=(8, cfg.max_len)).astype(np.int64)
        t = np.concatenate([np.zeros((8, 1), np.int64), s[:, :-1]],
                           axis=1)
        feed = {"src": s, "trg": t, "lbl": s[:, :, None]}
        (sh_loss,) = pe.run(fetch_list=[avg_cost], feed=feed)

        # the updated weight and its Adam moment both carry the rule's
        # sharding — FSDP is real, not a replicated fallback
        w = scope.find_var("enc0.self.q.w")
        assert tuple(w.sharding.spec) == ("fsdp", "tp"), w.sharding
        m = scope.find_var("enc0.self.q.w_moment1_0")
        assert tuple(m.sharding.spec) == ("fsdp", "tp"), m.sharding

        # single-device rerun of the SAME program on the SAME init
        for n, v in init_state.items():
            scope.set_var(n, v)
        (ref_loss,) = fluid.Executor().run(main, feed=feed,
                                           fetch_list=[avg_cost])
    l_sh = float(np.ravel(np.asarray(sh_loss))[0])
    l_1d = float(np.ravel(np.asarray(ref_loss))[0])
    rel = abs(l_sh - l_1d) / max(abs(l_1d), 1e-12)
    assert rel < 1e-3, f"sharded {l_sh} vs single {l_1d} (rel {rel:.2e})"

    snap = metrics.snapshot()
    assert snap["mesh.devices"] == 8
    assert snap["mesh.axis.fsdp"] == 2
    assert snap["mesh.sharded_steps"] >= 1
    assert snap["mesh.sharded_compiles"] >= 1
    # a dp training step that compiled no all-reduce did not actually
    # train data-parallel
    assert snap["mesh.collectives.all_reduce"] >= 1


def test_parallel_executor_mesh_from_flags():
    """FLAGS['mesh_axes'] is the no-code path: a PE built with no mesh
    argument trains on the flag's mesh."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.framework import Program, program_guard

    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[8], dtype="float32")
            y = layers.data(name="y", shape=[4], dtype="float32")
            out = layers.fc(input=x, size=4)
            loss = layers.mean(
                layers.square_error_cost(input=out, label=y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        set_flags({"mesh_axes": "dp=4,tp=2"})
        try:
            pe = fluid.ParallelExecutor(loss_name=loss.name,
                                        main_program=main)
        finally:
            set_flags({"mesh_axes": ""})
        assert pe._mesh.axis_names == ("dp", "tp")
        assert pe._mesh.devices.size == 8
        xs = np.random.RandomState(0).randn(8, 8).astype(np.float32)
        (lv,) = pe.run(fetch_list=[loss],
                       feed={"x": xs, "y": np.tanh(xs[:, :4])})
        assert np.isfinite(lv).all()


# --- mesh-sharded decode serving -----------------------------------------

def _small_spec(**kw):
    from paddle_tpu.serving.decode import DecoderSpec

    d = dict(vocab=32, d_model=32, n_heads=4, n_kv_heads=4, n_layers=2)
    d.update(kw)
    return DecoderSpec(**d)


def test_sharded_decode_tokens_match_single_chip():
    from paddle_tpu.fluid.flags import FLAGS, set_flags
    from paddle_tpu.serving.decode import DecodeEngine

    spec = _small_spec()
    e0 = DecodeEngine(spec, name="mref", slots=[1, 2], num_pages=32,
                      page_size=4, max_seq_len=32)
    assert e0.stats()["attention_route"] == ["paged_reference"]  # off-TPU
    ref = [e0.generate([3, 5, 7], max_new_tokens=8)["tokens"],
           e0.generate([9, 1], max_new_tokens=6,
                       temperature=0.7, top_k=8, seed=42)["tokens"]]
    e0.stop(drain=True)

    e1 = DecodeEngine(spec, name="mtp", slots=[1, 2], num_pages=32,
                      page_size=4, max_seq_len=32, mesh="tp=2")
    assert tuple(e1.cache.k.sharding.spec) == \
        (None, None, None, "tp", None)
    got = [e1.generate([3, 5, 7], max_new_tokens=8)["tokens"],
           e1.generate([9, 1], max_new_tokens=6,
                       temperature=0.7, top_k=8, seed=42)["tokens"]]
    assert got == ref, (got, ref)
    assert e1.stats()["mesh"] == {"tp": 2}
    e1.stop(drain=True)

    # with the kernels ON (what 'auto' means on a TPU) a single-chip
    # engine routes to the kernel and a mesh-spanning one names the
    # reference itself — a Mosaic kernel is never handed to GSPMD —
    # and says so where an operator looks
    prev = FLAGS["use_pallas_kernels"]
    set_flags({"use_pallas_kernels": True})
    try:
        kw = dict(slots=[1], num_pages=16, page_size=4, max_seq_len=16,
                  warm=False)
        e2 = DecodeEngine(spec, name="mk1", **kw)
        e3 = DecodeEngine(spec, name="mk2", mesh="tp=2", **kw)
        assert e2.stats()["attention_route"] == ["paged_kernel"]
        assert e3.stats()["attention_route"] == ["paged_reference"]
        base = metrics.counter("attention.route.paged_kernel").value()
        assert e3.generate([3, 5, 7], max_new_tokens=2)["tokens"] == \
            ref[0][:2]
        assert metrics.counter(
            "attention.route.paged_kernel").value() == base
        e2.stop(drain=False)
        e3.stop(drain=False)
    finally:
        set_flags({"use_pallas_kernels": prev})


def test_sharded_decode_churn_zero_post_warm_compiles():
    """Ragged churn on a tp=2 engine stays inside the warmed ladder:
    the sharded step fns' pinned out_shardings mean no input-sharding
    drift, so serving.decode.compiles is flat post-warm."""
    from paddle_tpu.serving.decode import DecodeEngine

    e = DecodeEngine(_small_spec(), name="mchurn", slots=[1, 2],
                     num_pages=32, page_size=4, max_seq_len=32,
                     mesh="tp=2")
    warm = metrics.snapshot()["serving.decode.compiles"]
    rng = np.random.RandomState(7)
    reqs = []
    for i in range(6):
        prompt = [int(x) for x in rng.randint(1, 30, rng.randint(1, 6))]
        reqs.append(e.submit(prompt,
                             max_new_tokens=int(rng.randint(1, 6))))
    for r in reqs:
        assert r.ev.wait(60.0)
        assert r.result is not None
    post = metrics.snapshot()["serving.decode.compiles"] - warm
    assert post == 0, f"sharded churn minted {post} post-warm compiles"
    e.stop(drain=True)


def test_sharded_decode_kv_divisibility_refused():
    from paddle_tpu.serving.decode import DecodeEngine

    with pytest.raises(ValueError, match="kv heads"):
        DecodeEngine(_small_spec(d_model=48, n_heads=6, n_kv_heads=3,
                                 n_layers=1),
                     name="mbad", mesh="tp=2", warm=False)
    # a mesh MISSING the axis the rules shard kv heads over is the
    # same class of config error — typed ValueError, never a KeyError
    # from deep inside construction
    with pytest.raises(ValueError, match="does not have"):
        DecodeEngine(_small_spec(n_layers=1), name="mbad2",
                     mesh="dp=2", warm=False)


def test_mesh_flag_default_for_decode_engine():
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.serving.decode import DecodeEngine

    set_flags({"serving_mesh_axes": "tp=2"})
    try:
        e = DecodeEngine(_small_spec(n_layers=1), name="mflag",
                         slots=[1], num_pages=16, page_size=4,
                         max_seq_len=16)
    finally:
        set_flags({"serving_mesh_axes": ""})
    assert e.stats()["mesh"] == {"tp": 2}
    # explicit '' pins single-chip over the flag
    set_flags({"serving_mesh_axes": "tp=2"})
    try:
        e2 = DecodeEngine(_small_spec(n_layers=1), name="mflag1",
                          slots=[1], num_pages=16, page_size=4,
                          max_seq_len=16, mesh="", warm=False)
    finally:
        set_flags({"serving_mesh_axes": ""})
    assert e2.stats()["mesh"] is None
    e2.stop(drain=False)
    e.stop(drain=True)


# --- sharded checkpoints -------------------------------------------------

def test_sharded_checkpoint_roundtrip(tmp_path):
    from paddle_tpu.checkpoint import (load_sharded_checkpoint,
                                       save_sharded_checkpoint)

    rng = np.random.RandomState(3)
    tree = {"layer0": {"wk": rng.randn(8, 16).astype(np.float32),
                       "ln1": (np.arange(8, dtype=np.float32),
                               np.zeros(8, np.float32))},
            "tok_emb": rng.randn(10, 8).astype(np.float32)}
    d = str(tmp_path / "ck")
    save_sharded_checkpoint(d, tree, shard_axis="tp",
                            mesh_spec="tp=4", rules=decoder_rules())
    names = sorted(os.listdir(d))
    assert sum(1 for n in names if n.endswith(".bin")) == 4
    full, manifest = load_sharded_checkpoint(d)
    assert manifest["shards"] == 4
    assert np.array_equal(full["layer0"]["wk"], tree["layer0"]["wk"])
    assert isinstance(full["layer0"]["ln1"], tuple)
    # per-shard load: wk slices columns; replicated tensors come whole
    for k in range(4):
        local, _ = load_sharded_checkpoint(d, shard=k)
        assert np.array_equal(local["layer0"]["wk"],
                              tree["layer0"]["wk"][:, 4 * k:4 * k + 4])
        # tok_emb: 10 rows don't divide by 4 -> replicated best-effort
        assert np.array_equal(local["tok_emb"], tree["tok_emb"])
    with pytest.raises(Exception, match="out of range"):
        load_sharded_checkpoint(d, shard=4)


def test_sharded_checkpoint_corrupt_shard_named(tmp_path):
    from paddle_tpu.checkpoint import (CheckpointCorruptError,
                                       load_sharded_checkpoint,
                                       save_sharded_checkpoint)

    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    d = str(tmp_path / "ck")
    save_sharded_checkpoint(
        d, tree, shard_axis="tp", mesh_spec="tp=2",
        rules=ShardingRules([(r"^w$", P(None, "tp"))], batch_axis=None))
    victim = [n for n in os.listdir(d) if n.endswith(".s1.bin")][0]
    with open(os.path.join(d, victim), "r+b") as f:
        f.seek(8)
        f.write(b"\xde\xad")
    with pytest.raises(CheckpointCorruptError) as ei:
        load_sharded_checkpoint(d)
    assert ei.value.tensor == "w"
    assert ".s1.bin" in str(ei.value)
    # shard 0 alone still verifies — per-shard loads touch only their
    # own file (plus replicated tensors)
    local, _ = load_sharded_checkpoint(d, shard=0)
    assert np.array_equal(local["w"], tree["w"][:, :4])


def test_torn_sharded_save_keeps_previous(tmp_path):
    """The format.py commit discipline holds for the sharded writer: a
    crash at the checkpoint.save fault site leaves the previous
    checkpoint fully loadable."""
    from paddle_tpu.checkpoint import (load_sharded_checkpoint,
                                       save_sharded_checkpoint)
    from paddle_tpu.distributed import faults

    rules = ShardingRules([(r".", P("tp"))], batch_axis=None)
    d = str(tmp_path / "ck")
    t1 = {"w": np.ones((4, 4), np.float32)}
    save_sharded_checkpoint(d, t1, shard_axis="tp", mesh_spec="tp=2",
                            rules=rules)
    t2 = {"w": np.full((4, 4), 7.0, np.float32)}
    with faults.scoped("crash@checkpoint.save:0"):
        with pytest.raises(faults.InjectedFault):
            save_sharded_checkpoint(d, t2, shard_axis="tp",
                                    mesh_spec="tp=2", rules=rules)
    full, _ = load_sharded_checkpoint(d)
    assert np.array_equal(full["w"], t1["w"])
    # next successful commit sweeps the crashed save's orphans
    save_sharded_checkpoint(d, t2, shard_axis="tp", mesh_spec="tp=2",
                            rules=rules)
    payloads = [n for n in os.listdir(d) if n.endswith(".bin")]
    assert len(payloads) == 2
    full2, _ = load_sharded_checkpoint(d)
    assert np.array_equal(full2["w"], t2["w"])


def test_mesh_recorded_checkpoint_deploys_sharded(tmp_path):
    """THE serving acceptance: a decoder exported with a recorded mesh
    + sharded payloads loads through load_decoder into a replica whose
    KV pool is sharded over the kv-head axis, greedy tokens bitwise
    equal to a single-chip deploy of the same artifact."""
    from paddle_tpu.checkpoint import save_decoder_checkpoint
    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.decode import build_decoder_params
    from paddle_tpu.serving.server import ServingServer

    spec = _small_spec(n_layers=1)
    params = build_decoder_params(spec)
    d = str(tmp_path / "ck")
    save_decoder_checkpoint(d, spec, params, mesh_axes="tp=2",
                            shard_axis="tp")

    srv = ServingServer()
    addr = srv.serve()
    try:
        cli = ServingClient(addr)
        st = cli.load_decoder("m", checkpoint_dir=d, slots=[1, 2],
                              page_size=4, num_pages=32, max_seq_len=32)
        assert st["mesh"] == {"tp": 2}
        assert cli.load_report()["models"]["m"]["mesh"] == {"tp": 2}
        out = cli.generate("m", [3, 5, 7], max_new_tokens=6)
        # same artifact, explicitly single-chip
        cli.load_decoder("m1", checkpoint_dir=d, slots=[1, 2],
                         page_size=4, num_pages=32, max_seq_len=32,
                         mesh_axes="")
        ref = cli.generate("m1", [3, 5, 7], max_new_tokens=6)
        assert out["tokens"] == ref["tokens"]
        # engine-side pool evidence
        eng = srv._registry.get("m")
        assert tuple(eng.cache.k.sharding.spec) == \
            (None, None, None, "tp", None)
    finally:
        srv.shutdown()


# --- observability -------------------------------------------------------

def test_mesh_statusz_section():
    mesh = MeshSpec.parse("dp=2,tp=4").build()
    from paddle_tpu.mesh import note_mesh

    note_mesh(mesh, label="testz")
    st = mesh_status()
    assert st["meshes"]["testz"] == {"dp": 2, "tp": 4}
    snap = metrics.snapshot()
    assert snap["mesh.devices"] == 8
    assert snap["mesh.axis.tp"] == 4


@pytest.mark.slow
def test_mesh_bench_smoke():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "benchmarks/mesh_bench.py", "--smoke"],
        capture_output=True, text=True, timeout=600, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    ev = json.loads(proc.stdout.strip().splitlines()[-1])
    assert ev["training"]["parity_rel_err_max"] < 1e-3
    assert ev["training"]["collectives_compiled"]["all_reduce"] >= 1
    assert ev["serving"]["tokens_bitwise_equal_sharded_vs_single"]
    assert ev["serving"]["post_warm_compiles"] == 0
    assert ev["serving"]["kv_pool_per_device_ratio"] == 2
    assert ev["sharded_checkpoint"]["payload_files"] == \
        ev["sharded_checkpoint"]["shards"]
