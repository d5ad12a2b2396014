"""Legacy headline-benchmark parity: the reference's K40m table
(reference benchmark/README.md:33-61,113-118 / BASELINE.md) measured
ms/batch for AlexNet (bs=128/512), GoogleNet (bs=128), SmallNet-cifar
(bs=128) and a 2-layer LSTM text classifier (h=512, bs=64) on the legacy
v2 framework. This harness runs the same workloads on one TPU chip through
the Program IR -> Executor stack and prints one JSON line per workload:

  {"workload": ..., "ms_per_batch": N, "ref_k40m_ms": N, "speedup": N}

Run directly (`python benchmarks/legacy_conv_bench.py`), optionally with
WORKLOADS=alexnet,smallnet to subset. On a non-TPU backend it still runs
(smaller iteration counts) but labels the backend so numbers aren't
mistaken for the TPU result.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# reference benchmark/README.md ms/batch numbers (K40m, cuDNN v5.1)
REF_MS = {
    "alexnet_bs128": 334.0,
    "alexnet_bs512": 1629.0,
    "googlenet_bs128": 1149.0,
    "smallnet_bs128": 18.184,
    "lstm_h512_bs64": 184.0,
}


def _conv_workload(model_mod, batch, image_shape, class_dim):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard

    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            img = layers.data(name="img", shape=list(image_shape),
                              dtype="float32")
            label = layers.data(name="label", shape=[1], dtype="int64")
            avg_cost, _, _ = model_mod.build_train(
                img, label, class_dim=class_dim)
            fluid.optimizer.Momentum(learning_rate=0.01,
                                     momentum=0.9).minimize(avg_cost)
    rng = np.random.RandomState(0)
    feed = {
        "img": rng.rand(batch, *image_shape).astype(np.float32),
        "label": rng.randint(0, class_dim, size=(batch, 1)).astype(np.int64),
    }
    return main, startup, scope, feed, avg_cost


def _lstm_workload(batch=64, seq_len=100, hid=512, dict_dim=10000):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.models import stacked_lstm

    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            data = layers.data(name="words", shape=[1], dtype="int64",
                               lod_level=1)
            label = layers.data(name="label", shape=[1], dtype="int64")
            # reference legacy rnn bench is 2 stacked lstm layers, h=512
            avg_cost, _, _ = stacked_lstm.build(
                data, label, dict_dim=dict_dim, emb_dim=hid, hid_dim=hid,
                stacked_num=2)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    rng = np.random.RandomState(0)
    feed = {
        "words": rng.randint(0, dict_dim,
                             size=(batch, seq_len)).astype(np.int64),
        "words@LEN": np.full((batch,), seq_len, dtype=np.int64),
        "label": rng.randint(0, 2, size=(batch, 1)).astype(np.int64),
    }
    return main, startup, scope, feed, avg_cost


def _measure(main, startup, scope, feed, fetch, iters, warmup):
    import jax

    import paddle_tpu.fluid as fluid
    from benchmarks._timing import step_time_from_iters

    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)
        param = main.global_block().all_parameters()[0].name
        # Device-resident feed: the reference table's numbers are model
        # time; re-feeding 77 MB of AlexNet images per step would measure
        # the host->device link, not the model.
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        # fetch-sync slope timing (benchmarks/_timing.py)
        def _dispatch(_i):
            exe.run(main, feed=feed, fetch_list=[fetch], return_numpy=False)
            return scope.find_var(param)

        per_step_s, _ev = step_time_from_iters(_dispatch, iters, warmup)
        return per_step_s * 1000.0


def main():
    import jax

    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.models import alexnet, googlenet, smallnet

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    iters = int(os.environ.get("BENCH_ITERS", "20" if on_tpu else "3"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5" if on_tpu else "1"))
    set_flags({"amp": os.environ.get("BENCH_AMP", "1") == "1"})

    workloads = {
        "alexnet_bs128": lambda: _conv_workload(alexnet, 128, (3, 224, 224),
                                                1000),
        "alexnet_bs512": lambda: _conv_workload(alexnet, 512, (3, 224, 224),
                                                1000),
        "googlenet_bs128": lambda: _conv_workload(googlenet, 128,
                                                  (3, 224, 224), 1000),
        "smallnet_bs128": lambda: _conv_workload(smallnet, 128, (3, 32, 32),
                                                 10),
        "lstm_h512_bs64": lambda: _lstm_workload(),
    }
    only = os.environ.get("WORKLOADS")
    if only:
        prefixes = tuple(p for p in only.split(",") if p)
        workloads = {k: v for k, v in workloads.items()
                     if k.startswith(prefixes)}
        if not workloads:
            print(json.dumps({"error": f"WORKLOADS={only!r} matched "
                              f"nothing; keys: {sorted(REF_MS)}"}))
            return 1

    for name, build in workloads.items():
        try:
            ms = _measure(*build(), iters=iters, warmup=warmup)
            ref = REF_MS[name]
            print(json.dumps({
                "workload": name, "ms_per_batch": round(ms, 3),
                "ref_k40m_ms": ref, "speedup": round(ref / ms, 2),
                "backend": backend,
            }), flush=True)
        except Exception as e:  # keep going past one workload's OOM
            print(json.dumps({"workload": name, "error": str(e)[-300:],
                              "backend": backend}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
