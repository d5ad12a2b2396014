"""Transformer training throughput on one TPU chip through the full
framework stack (Program IR -> Executor), with MFU computed from XLA's own
cost analysis of the compiled step. Prints one JSON line per config."""
import json
import os
import sys
import time

import numpy as np


def main():
    import jax.numpy as jnp

    from benchmarks._timing import require_tpu, step_time_s

    dev = require_tpu()

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.models import transformer

    set_flags({"amp": True})
    cfg = transformer.TransformerConfig(
        src_vocab=32000, trg_vocab=32000, max_len=512, d_model=512,
        n_heads=8, d_ff=2048, n_layers=6, dropout=0.0,
    )
    batch = 16

    def _mark(msg):
        print(f"# transformer_bench: {msg} t={time.perf_counter():.0f}",
              file=sys.stderr, flush=True)

    main_prog, startup, scope = Program(), Program(), fluid.Scope()
    main_prog.random_seed = startup.random_seed = 3
    with fluid.scope_guard(scope):
        with program_guard(main_prog, startup):
            src = layers.data(name="src", shape=[cfg.max_len], dtype="int64")
            trg = layers.data(name="trg", shape=[cfg.max_len], dtype="int64")
            lbl = layers.data(name="lbl", shape=[cfg.max_len, 1],
                              dtype="int64")
            avg_cost, _ = transformer.build_train(cfg, src, trg, lbl)
            _mark("built train graph")
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
            _mark("built optimizer")
        exe = fluid.Executor()
        exe.run(startup)
        _mark("startup ran")

        rng = np.random.RandomState(0)
        s = jnp.asarray(rng.randint(3, cfg.src_vocab,
                                    (batch, cfg.max_len)).astype(np.int64))
        t = jnp.concatenate(
            [jnp.zeros((batch, 1), s.dtype), s[:, :-1]], axis=1)
        feed = {"src": s, "trg": t, "lbl": s[:, :, None]}

        # flops of the compiled step, from XLA itself — via the executor's
        # own cache entry, so AOT inspection and the run() loop below share
        # ONE compiled executable
        _mark("lowering step")
        jfn, args = exe.lowered(main_prog, feed, [avg_cost], scope)
        _mark("lowered; compiling")
        comp = jfn.lower(*args).compile()
        _mark("compiled")
        step_flops = comp.cost_analysis().get("flops", 0.0)

        # fetch-sync slope timing (benchmarks/_timing.py)
        a_param = main_prog.global_block().all_parameters()[0].name
        last = {}

        def _dispatch(_i):
            (last["l"],) = exe.run(main_prog, feed=feed,
                                   fetch_list=[avg_cost],
                                   return_numpy=False)
            # the Adam-updated param is the end of the step's chain
            return scope.find_var(a_param)

        dt, _ev = step_time_s(_dispatch, 8, 24, warmup=4)
        l = last["l"]

        tokens_per_sec = batch * cfg.max_len / dt
        tflops = step_flops / dt / 1e12
        loss = float(np.asarray(l).reshape(-1)[0])
        if not np.isfinite(loss):
            raise SystemExit(f"transformer_bench: non-finite loss {loss}")
        print(json.dumps({
            "model": "transformer-base-6L-512d",
            "platform": dev.platform, "device_kind": dev.device_kind,
            "seq": cfg.max_len, "batch": batch,
            "step_ms": round(dt * 1e3, 2),
            "tokens_per_sec": round(tokens_per_sec),
            "xla_step_gflop": round(step_flops / 1e9, 1),
            "sustained_tflops": round(tflops, 1),
            "loss": loss,
        }))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
