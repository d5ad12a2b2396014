"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two main paths once, at the full width of a model
the repo supports, through the entry points a user has:

  trainer   ResNet-50 / 1000 classes / 224x224 / batch 32 / Momentum /
            amp (the BASELINE.json configuration bench.py measures) through
            ``fluid.Executor.run`` on a device-resident synthetic batch.
  server    ``ServingServer().serve()`` answering ``ServingClient``s over
            loopback RPC with a seed-built decoder at vocab 50304,
            d_model 2048, 16 layers, 16/16 heads of 128 (float32), page 16,
            2048 pages, ladder slots [1, 8] x chunks [1, 16].
  four-chip (only where JAX sees four or more devices) dp=2,tp=2
            transformer training with single-device loss parity, and a
            tp=4 decode engine whose tokens equal the one-chip engine's.

Every check prints its value. Any check that fails raises, so the exit
code is non-zero and no result line is printed; nothing is skipped and
nothing falls back. Without a TPU the script exits non-zero before
anything compiles. The last line of stdout is the result:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

    python3 chip_smoke.py
"""
import gc
import importlib.metadata
import json
import math
import sys
import threading
import time

TRAINER = dict(batch=32, class_dim=1000, depth=50, steps=5)
DECODER = dict(vocab=50304, d_model=2048, n_layers=16, n_heads=16,
               n_kv_heads=16, seed=11)
SERVE = dict(slots=[1, 8], page_size=16, num_pages=2048, max_seq_len=1024,
             prefill_chunk=16)
# the mesh engine answers one long prompt: a one-slot, short-context
# ladder keeps its warm (every shape compiles for four chips) short. Its
# pool is sized for the mesh — 2 x 12 GiB, more than one 16 GB chip holds
# — so a pool first materialized whole on one device cannot pass
SERVE_TP4 = dict(slots=[1], page_size=16, num_pages=6144, max_seq_len=256,
                 prefill_chunk=16)
LONG_PROMPT, LONG_NEW = 200, 16
# greedy decode of a seed-built tied-embedding model repeats one token, so
# the engine is also asked for a seeded draw at a temperature that spreads
# it over the vocabulary. On ONE engine the draw must repeat exactly.
# Between the one-chip and the tp=4 engine it is printed, not checked:
# the engine's float32 matmuls run at the backend's default precision
# (ROADMAP S3), which XLA lowers differently for differently sharded
# shapes, and a near-uniform draw turns a 1e-3 logit difference into
# another token within a few steps (PR 21: 10 of 16 agreed).
SAMPLED = dict(temperature=3.0, top_k=0, seed=7)
# Pallas paged kernel vs paged_attention_reference, both float32 with the
# reference's dots at HIGHEST precision: the two differ by summation
# order and the exp lowering only. Outputs are convex mixes of N(0,1)
# values, so 2e-3 absolute is ~1e-3 of their scale and far under what a
# bf16 pass (4e-3 relative per product) or a wrong page would produce.
KERNEL_ATOL = 2e-3
LOSS_PARITY_RTOL = 1e-3     # sharded vs single-device f32 training loss

_cache_events = {"hits": 0, "misses": 0}


def check(name, ok, value):
    print(f"CHECK {name}: {value} -> {'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {name} = {value}")


def note(msg):
    print(f"# {msg}", flush=True)


def phase_trainer(cfg):
    """ResNet-50 train steps through fluid.Executor.run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.models import resnet

    set_flags({"matmul_precision": "default", "amp": True})
    batch = cfg["batch"]
    main_prog, startup, scope = Program(), Program(), fluid.Scope()
    main_prog.random_seed = startup.random_seed = 11
    with fluid.scope_guard(scope):
        with program_guard(main_prog, startup):
            img = layers.data(name="img", shape=[3, 224, 224],
                              dtype="float32")
            label = layers.data(name="label", shape=[1], dtype="int64")
            avg_cost, _acc, _ = resnet.build_train(
                img, label, class_dim=cfg["class_dim"], depth=cfg["depth"])
            fluid.optimizer.Momentum(learning_rate=0.01,
                                     momentum=0.9).minimize(avg_cost)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {
            "img": jnp.asarray(
                rng.rand(batch, 3, 224, 224).astype(np.float32)),
            "label": jnp.asarray(rng.randint(
                0, cfg["class_dim"], size=(batch, 1)).astype(np.int32)),
        }
        jax.block_until_ready(feed)
        a_param = main_prog.global_block().all_parameters()[0].name
        p_before = np.array(scope.find_var(a_param))
        losses, t_steps = [], []
        for _ in range(cfg["steps"]):
            t0 = time.perf_counter()
            (loss,) = exe.run(main_prog, feed=feed, fetch_list=[avg_cost])
            t_steps.append(time.perf_counter() - t0)
            losses.append(float(np.ravel(loss)[0]))
        p_after = np.array(scope.find_var(a_param))
    note(f"trainer: first step (trace+compile+run) {t_steps[0]:.1f}s, "
         f"later steps {[round(t * 1e3, 1) for t in t_steps[1:]]} ms "
         f"(host wall, fetch-synced)")
    ln_classes = math.log(cfg["class_dim"])
    check("trainer.losses_finite", all(math.isfinite(v) for v in losses),
          [round(v, 4) for v in losses])
    check("trainer.loss0_near_ln_classes",
          abs(losses[0] - ln_classes) < 1.5,
          f"{losses[0]:.4f} vs ln({cfg['class_dim']})={ln_classes:.4f}")
    check("trainer.losses_distinct",
          len({round(v, 6) for v in losses}) == len(losses), len(losses))
    moved = float(np.max(np.abs(p_after - p_before)))
    check("trainer.param_moved", moved > 0.0, f"{a_param} max|d|={moved:.3e}")
    set_flags({"matmul_precision": "highest", "amp": False})


def check_paged_kernel(heads, head_dim, page_size):
    """The Pallas paged kernel against the pure-jax reference ON THIS
    DEVICE at the served shapes: ragged lengths, partial chunks, a dead
    slot, garbage-padded tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.fluid.ops.pallas_kernels.paged_attention import (
        _paged_attention_pallas, paged_attention_reference)

    rng = np.random.RandomState(3)
    pages = 160
    k_pages = jnp.asarray(rng.randn(pages, page_size, heads, head_dim)
                          .astype(np.float32))
    v_pages = jnp.asarray(rng.randn(pages, page_size, heads, head_dim)
                          .astype(np.float32))
    for slots, chunk, width in ((8, 1, 64), (8, 16, 64), (1, 1, 16),
                                (1, 16, 16)):
        cap = width * page_size
        kv_lens = rng.randint(chunk, cap + 1, size=slots).astype(np.int32)
        kv_lens[0] = cap                      # one slot at full width
        q_lens = rng.randint(1, chunk + 1, size=slots).astype(np.int32)
        if slots > 1:
            kv_lens[-1] = q_lens[-1] = 0      # a dead slot
        tables = np.zeros((slots, width), np.int32)   # page 0 = garbage
        for i in range(slots):
            n = -(-int(kv_lens[i]) // page_size)
            tables[i, :n] = rng.choice(np.arange(1, pages), size=n,
                                       replace=False)
        q = jnp.asarray(rng.randn(slots, chunk, heads, head_dim)
                        .astype(np.float32))
        args = (q, k_pages, v_pages, jnp.asarray(tables),
                jnp.asarray(kv_lens))
        got = jax.jit(lambda *a: _paged_attention_pallas(
            *a[:5], q_lens=a[5]))(*args, jnp.asarray(q_lens))
        want = jax.jit(lambda *a: paged_attention_reference(
            *a[:5], q_lens=a[5]))(*args, jnp.asarray(q_lens))
        got, want = np.asarray(got), np.asarray(want)
        err = float(np.max(np.abs(got - want)))
        check(f"paged_kernel_vs_reference[slots={slots},C={chunk},"
              f"W={width}]", np.isfinite(got).all() and err < KERNEL_ATOL,
              f"max|d|={err:.2e} (atol {KERNEL_ATOL})")
        check(f"paged_kernel_dead_lanes_zero[slots={slots},C={chunk}]",
              all(not got[i, int(q_lens[i]):].any() for i in range(slots)),
              "exact zeros")


def phase_server(spec, serve, n_devices):
    """The decode server over loopback RPC; then, with four chips, the
    same model on a tp=4 mesh."""
    import numpy as np

    from paddle_tpu.fluid.flags import pallas_enabled, pallas_interpret
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import ServingClient, ServingServer

    check("kernels.enabled_and_compiled",
          pallas_enabled() and not pallas_interpret(),
          f"pallas_enabled={pallas_enabled()} "
          f"interpret={pallas_interpret()}")
    head_dim = spec["d_model"] // spec["n_heads"]
    check_paged_kernel(spec["n_heads"], head_dim, serve["page_size"])

    rng = np.random.RandomState(5)
    long_prompt = rng.randint(1, spec["vocab"], size=LONG_PROMPT).tolist()
    shorts = [rng.randint(1, spec["vocab"], size=5 + i).tolist()
              for i in range(8)]

    srv = ServingServer()
    ep = srv.serve()
    # the deploy RPC returns after the params are built and the whole
    # ladder is warm: minutes at this width, so the client waits that long
    cli = ServingClient(ep, timeout=1500.0)
    try:
        base_k = metrics.counter("attention.route.paged_kernel").value()
        base_r = metrics.counter("attention.route.paged_reference").value()
        t0 = time.perf_counter()
        st = cli.load_decoder("gen", spec, **serve)
        t_load = time.perf_counter() - t0
        warm_shapes = st["compiled_shapes"]
        note(f"server: load+warm {t_load:.1f}s, {len(warm_shapes)} shapes, "
             f"kv pool {st['kv']['pages_total']} pages x "
             f"{st['page_size']} tokens")
        rep = cli.load_report()["models"]["gen"]
        check("server.attention_route", rep["attention_route"] ==
              ["paged_kernel"], rep["attention_route"])

        t0 = time.perf_counter()
        r1 = cli.generate("gen", long_prompt, max_new_tokens=LONG_NEW)
        t_r1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        r2 = cli.generate("gen", long_prompt, max_new_tokens=LONG_NEW)
        t_r2 = time.perf_counter() - t0
        note(f"server: {LONG_PROMPT}-token prompt + {LONG_NEW} new: cold "
             f"{t_r1:.2f}s, repeat {t_r2:.2f}s (host wall)")
        check("server.long.tokens", len(r1["tokens"]) == LONG_NEW and
              all(0 <= t < spec["vocab"] for t in r1["tokens"]),
              r1["tokens"])
        check("server.long.repeat_same_tokens",
              r2["tokens"] == r1["tokens"], r2["tokens"])
        check("server.long.repeat_cached_tokens",
              r1["cached_tokens"] == 0 and r2["cached_tokens"] > 0,
              f"cold {r1['cached_tokens']}, repeat {r2['cached_tokens']}")
        s1 = cli.generate("gen", long_prompt, max_new_tokens=LONG_NEW,
                          **SAMPLED)
        s2 = cli.generate("gen", long_prompt, max_new_tokens=LONG_NEW,
                          **SAMPLED)
        check("server.long.sampled_tokens_seeded",
              s1["tokens"] == s2["tokens"] and
              len(set(s1["tokens"])) > 1, s1["tokens"])
        check("server.long.steps_to_first_token",
              r1["steps_to_first_token"] ==
              -(-LONG_PROMPT // serve["prefill_chunk"]) and
              r2["steps_to_first_token"] < r1["steps_to_first_token"],
              f"cold {r1['steps_to_first_token']}, "
              f"repeat {r2['steps_to_first_token']}")

        # eight concurrent short prompts, one connection each
        outs = [None] * len(shorts)

        def one(i):
            c = ServingClient(ep, timeout=600.0)
            try:
                outs[i] = c.generate("gen", shorts[i], max_new_tokens=8)
            finally:
                c.close()

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(shorts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(600.0)
        note(f"server: 8 concurrent short prompts in "
             f"{time.perf_counter() - t0:.2f}s (host wall)")
        check("server.concurrent.all_answered",
              all(o is not None and len(o["tokens"]) == 8 for o in outs),
              [None if o is None else len(o["tokens"]) for o in outs])

        stream = cli.generate("gen", shorts[0], max_new_tokens=8,
                              stream=True)
        streamed = list(stream)
        check("server.stream.tokens", len(streamed) == 8 and
              streamed == stream.result["tokens"], streamed)

        st = cli.list_models()["gen"]
        check("server.compiled_shapes_flat_after_warm",
              st["compiled_shapes"] == warm_shapes,
              f"{len(st['compiled_shapes'])} after vs "
              f"{len(warm_shapes)} at warm")
        check("server.kv_pages_returned", st["kv"]["pages_used"] == 0,
              st["kv"]["pages_used"])
        d_k = metrics.counter("attention.route.paged_kernel").value() - base_k
        d_r = metrics.counter(
            "attention.route.paged_reference").value() - base_r
        check("server.route_counters", d_k > 0 and d_r == 0,
              f"paged_kernel +{d_k}, paged_reference +{d_r}")

        if n_devices < 4:
            note(f"four-chip phase not run: JAX sees {n_devices} device(s)")
            return
        cli.unload_model("gen")
        gc.collect()
        phase_four_chip_training()
        t0 = time.perf_counter()
        st4 = cli.load_decoder("gen4", spec, mesh_axes="tp=4", **SERVE_TP4)
        note(f"four-chip: tp=4 load+warm {time.perf_counter() - t0:.1f}s, "
             f"{len(st4['compiled_shapes'])} shapes")
        rep4 = cli.load_report()["models"]["gen4"]
        check("four_chip.decode.mesh", rep4["mesh"] == {"tp": 4},
              rep4["mesh"])
        check("four_chip.decode.attention_route_named",
              rep4["attention_route"] == ["paged_reference"],
              rep4["attention_route"])
        eng = srv.registry.get("gen4")
        k_shards = {s.data.shape for s in eng.cache.k.addressable_shards}
        check("four_chip.decode.pool_sharded",
              k_shards == {(spec["n_layers"], SERVE_TP4["num_pages"],
                            SERVE_TP4["page_size"],
                            spec["n_kv_heads"] // 4, head_dim)},
              f"per-chip k shard {sorted(k_shards)} "
              f"spec {eng.cache.k.sharding.spec}")
        r4 = cli.generate("gen4", long_prompt, max_new_tokens=LONG_NEW)
        check("four_chip.decode.tokens_equal_one_chip",
              r4["tokens"] == r1["tokens"], r4["tokens"])
        s4 = cli.generate("gen4", long_prompt, max_new_tokens=LONG_NEW,
                          **SAMPLED)
        agree = next((i for i, (a, b) in enumerate(
            zip(s4["tokens"], s1["tokens"])) if a != b), LONG_NEW)
        note(f"four-chip: seeded draw agrees with the one-chip engine for "
             f"the first {agree} of {LONG_NEW} tokens (not a check: "
             f"matmul precision is unstated, ROADMAP S3)")
        st4 = cli.list_models()["gen4"]
        check("four_chip.decode.kv_pages_returned",
              st4["kv"]["pages_used"] == 0, st4["kv"]["pages_used"])
    finally:
        cli.close()
        srv.shutdown(drain=False)


def phase_four_chip_training():
    """The flagship transformer on a dp=2,tp=2 mesh through
    ParallelExecutor: a few Adam steps whose losses match the
    single-device run from the same seeded state (float32, flags at
    their defaults)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.mesh import MeshSpec, transformer_rules
    from paddle_tpu.models import transformer
    from paddle_tpu.observability import metrics

    cfg = transformer.TransformerConfig(
        src_vocab=8000, trg_vocab=8000, max_len=128, d_model=512,
        n_heads=8, d_ff=2048, n_layers=2, dropout=0.0)
    batch, steps = 8, 3
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
        fluid.Executor().run(startup)
        init_state = {n: np.array(scope.find_var(n))
                      for n in scope.var_names()}
        rng = np.random.RandomState(0)
        s = rng.randint(3, cfg.src_vocab,
                        size=(batch, cfg.max_len)).astype(np.int64)
        t = np.concatenate([np.zeros((batch, 1), np.int64), s[:, :-1]],
                           axis=1)
        feed = {"src": s, "trg": t, "lbl": s[:, :, None]}
        pe = fluid.ParallelExecutor(
            loss_name=avg_cost.name, main_program=main,
            mesh=MeshSpec.parse("dp=2,tp=2"),
            sharding_plan=transformer_rules(fsdp=None))
        t0 = time.perf_counter()
        sharded = [float(np.ravel(pe.run(fetch_list=[avg_cost],
                                         feed=feed)[0])[0])
                   for _ in range(steps)]
        note(f"four-chip: dp=2,tp=2 transformer {steps} steps in "
             f"{time.perf_counter() - t0:.1f}s (compile included)")
        w = scope.find_var("enc0.self.q.w")
        check("four_chip.train.weight_sharded",
              "tp" in str(w.sharding.spec), str(w.sharding.spec))
        for n, v in init_state.items():
            scope.set_var(n, v)
        exe = fluid.Executor()
        single = [float(np.ravel(exe.run(main, feed=feed,
                                         fetch_list=[avg_cost])[0])[0])
                  for _ in range(steps)]
    rel = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    check("four_chip.train.loss_parity",
          all(math.isfinite(v) for v in sharded) and rel < LOSS_PARITY_RTOL,
          f"sharded {[round(v, 5) for v in sharded]} vs single "
          f"{[round(v, 5) for v in single]} (max rel {rel:.2e}, "
          f"rtol {LOSS_PARITY_RTOL})")
    check("four_chip.train.losses_fall", sharded[-1] < sharded[0], sharded)
    snap = metrics.snapshot()
    check("four_chip.train.all_reduce_compiled",
          snap["mesh.collectives.all_reduce"] >= 1,
          snap["mesh.collectives.all_reduce"])


def main():
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"chip_smoke: platform={dev.platform} "
          f"device_kind={dev.device_kind!r} devices={len(devices)} "
          f"jax={jax.__version__} "
          f"jaxlib={importlib.metadata.version('jaxlib')} "
          f"libtpu={importlib.metadata.version('libtpu')}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1

    import jax.monitoring

    def _on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            _cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            _cache_events["misses"] += 1

    jax.monitoring.register_event_listener(_on_event)

    import paddle_tpu  # places the compile cache before anything compiles

    note(f"compile cache: {jax.config.jax_compilation_cache_dir}")

    t0 = time.perf_counter()
    phase_trainer(TRAINER)
    t_train = time.perf_counter() - t0
    gc.collect()    # the trainer's scope and batch leave the chip here

    t0 = time.perf_counter()
    phase_server(DECODER, SERVE, len(devices))
    t_serve = time.perf_counter() - t0

    note(f"wall: trainer {t_train:.1f}s, server {t_serve:.1f}s, total "
         f"{time.perf_counter() - t_start:.1f}s; compile cache hits "
         f"{_cache_events['hits']}, misses {_cache_events['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
