"""ResNet-50 training throughput on one TPU chip: prints ONE JSON line
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Workload (BASELINE.json): the reference's benchmark/fluid resnet.py —
ResNet-50, ImageNet shapes, bs=32, Momentum — through fluid.Executor.run.
Baseline denominator: V100-class fluid-era ResNet-50 throughput (~300
imgs/s fp32, bs=32); the reference tree itself only commits CPU numbers
(ResNet-50 81.69 imgs/s on Xeon 6148, BASELINE.md).

One process, one measurement. Without a TPU it exits non-zero before
anything compiles; a device kind missing from the peak table, a
non-finite loss or an implausible MFU is an error and exits non-zero.
(ROADMAP S0 replaces this file with the cell list.)

Self-validation: the run records device_kind + device count, computes
MFU = imgs/s x FLOP/img / chip peak from BOTH the XLA cost analysis and
an analytic FLOP count, and rejects MFU > 0.85 as a measurement bug.
"""
import json
import os
import sys
import time

V100_BASELINE_IMGS_PER_SEC = 300.0

# Analytic FLOP estimate for one ResNet-50 training image at 224x224:
# forward ~4.1 GFLOP (multiply+add = 2 FLOPs), backward ~2x forward.
ANALYTIC_TRAIN_FLOP_PER_IMG = 3.0 * 4.1e9

# Peak dense bf16 FLOP/s per chip, keyed by the EXACT device_kind JAX
# reports. MFU against the bf16 peak is conservative for f32 runs.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16). A
# kind that is not here is an error, never a default.
CHIP_PEAK_BF16 = {
    "TPU v5 lite": 197e12,
}

MFU_PLAUSIBLE_MAX = 0.85

BATCH = int(os.environ.get("BENCH_BATCH", "32"))
ITERS = int(os.environ.get("BENCH_ITERS", "30"))
WARMUP = int(os.environ.get("BENCH_WARMUP", "5"))


def main():
    import numpy as np
    import jax

    if ITERS < 1 or WARMUP < 0:
        print(json.dumps({"error": "BENCH_ITERS must be >= 1"}))
        return 2

    from benchmarks._timing import device_sync, require_tpu, \
        sample_indices, step_time_from_iters, sync_roundtrip_ms

    devices = jax.devices()
    backend, device_kind = require_tpu().platform, devices[0].device_kind
    print(f"# backend={backend} kind={device_kind} n={len(devices)}",
          file=sys.stderr)
    if device_kind not in CHIP_PEAK_BF16:
        print(f"no peak recorded for device_kind {device_kind!r}; add it "
              f"to CHIP_PEAK_BF16 with its source", file=sys.stderr)
        return 1
    peak = CHIP_PEAK_BF16[device_kind]

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.models import resnet

    # bf16 matmul/conv on the MXU (f32 params/master weights), the standard
    # TPU training configuration; numerics-sensitive paths keep f32 via
    # dtypes. FLAGS['amp'] casts conv/matmul operands to bf16 (one MXU pass
    # instead of the f32 3-pass decomposition; f32 accumulate inside the
    # MXU). Override with BENCH_AMP=0 for the pure-f32 configuration.
    amp = os.environ.get("BENCH_AMP", "1") == "1"
    set_flags({"matmul_precision": "default", "amp": amp})

    # BENCH_DATA=recordio drives the in-graph async input pipeline
    # (recordio file -> batch -> double_buffer -> read op) instead of a
    # device-resident synthetic batch: uint8 images are decoded to f32 and
    # transferred by the double-buffer thread while the device computes.
    data_mode = os.environ.get("BENCH_DATA", "synthetic")
    recordio_path = None
    if data_mode == "recordio":
        import tempfile

        from paddle_tpu.fluid.recordio_writer import (
            convert_reader_to_recordio_file,
        )

        n_samples = (WARMUP + ITERS) * BATCH
        rng0 = np.random.RandomState(0)

        def _sample_gen():
            for _ in range(n_samples):
                yield (rng0.randint(0, 256, size=(3 * 224 * 224,),
                                    ).astype(np.uint8),
                       rng0.randint(0, 1000, size=(1,)).astype(np.int64))

        import atexit
        import shutil

        recordio_dir = tempfile.mkdtemp(prefix="bench_rio_")
        atexit.register(shutil.rmtree, recordio_dir, ignore_errors=True)
        recordio_path = os.path.join(recordio_dir, "imgs.recordio")
        t0 = time.perf_counter()
        convert_reader_to_recordio_file(recordio_path, _sample_gen)
        print(f"# wrote {n_samples} recordio samples in "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    main_prog, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main_prog, startup):
            if data_mode == "recordio":
                reader = layers.open_recordio_file(
                    recordio_path, shapes=[[3, 224, 224], [1]],
                    dtypes=["float32", "int64"],
                )
                reader = layers.multi_pass(reader, pass_num=4)
                reader = layers.batch(reader, batch_size=BATCH,
                                      drop_last=True)
                reader = layers.double_buffer(reader, capacity=2)
                img, label = layers.read_file(reader)
            else:
                img = layers.data(name="img", shape=[3, 224, 224],
                                  dtype="float32")
                label = layers.data(name="label", shape=[1], dtype="int64")
            avg_cost, acc, _ = resnet.build_train(
                img, label, class_dim=1000, depth=50
            )
            fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9).minimize(
                avg_cost
            )
        exe = fluid.Executor()
        t0 = time.perf_counter()
        exe.run(startup)
        print(f"# startup ran in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)

        # device-resident synthetic batch (the reference benchmark's
        # --use_fake_data mode, resnet.py:44) — measures the training step,
        # not the host->device link
        import jax.numpy as jnp

        if data_mode == "recordio":
            feed = {}
        else:
            rng = np.random.RandomState(0)
            x = jnp.asarray(rng.rand(BATCH, 3, 224, 224).astype(np.float32))
            y = jnp.asarray(
                rng.randint(0, 1000, size=(BATCH, 1)).astype(np.int64))
            jax.block_until_ready(x)
            feed = {"img": x, "label": y}
        a_param = main_prog.global_block().all_parameters()[0].name

        # steps are timed with benchmarks/_timing.py's slope method:
        # (t(n2) - t(n1)) / (n2 - n1) with one device->host fetch as the
        # barrier of each run, so the fetch's own latency cancels
        t0 = time.perf_counter()
        for i in range(WARMUP):
            exe.run(main_prog, feed=feed, fetch_list=[avg_cost],
                    return_numpy=False)
            if i == 0:
                device_sync(scope.find_var(a_param))
                print(f"# first step (trace+compile) "
                      f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
        device_sync(scope.find_var(a_param))

        # XLA's own FLOP count for the compiled step (the same executable
        # run() replays) — cross-checked against the analytic estimate.
        # In recordio mode the read-op outputs are the "feeds" of the
        # jitted step — hand lowered() dummy arrays under those names so
        # it resolves the same cache entry run() uses
        cost_feed = feed
        if data_mode == "recordio":
            cost_feed = {
                img.name: jnp.zeros((BATCH, 3, 224, 224), jnp.float32),
                label.name: jnp.zeros((BATCH, 1), jnp.int32),
            }
        jfn, args = exe.lowered(main_prog, feed=cost_feed,
                                fetch_list=[avg_cost], scope=scope)
        cost = jfn.lower(*args).compile().cost_analysis()
        flops_cost_analysis = float(cost.get("flops", 0.0)) or None

        losses = []

        def _dispatch(_i):
            out = exe.run(main_prog, feed=feed, fetch_list=[avg_cost],
                          return_numpy=False)
            losses.append(out[0])
            # the updated param depends on the WHOLE step (fwd+bwd+
            # momentum) — syncing on it is the true end-of-step barrier
            return scope.find_var(a_param)

        per_step_s, timing_ev = step_time_from_iters(_dispatch, ITERS,
                                                     warmup=0)
        timing_ev["sync_roundtrip_ms"] = round(sync_roundtrip_ms(), 1)

        # integrity evidence that real steps executed: fetched losses are
        # distinct, finite values from param-chained steps (a stalled or
        # elided execution would repeat or NaN)
        idx = sample_indices(len(losses), k=8)
        loss_vals = [float(np.asarray(losses[i]).ravel()[0]) for i in idx]
        distinct = len({round(v, 6) for v in loss_vals})
        finite = bool(np.isfinite(loss_vals).all())
        imgs_per_sec = BATCH / per_step_s

        # --- MFU self-validation -------------------------------------
        analytic_step_flops = ANALYTIC_TRAIN_FLOP_PER_IMG * BATCH
        # prefer XLA's count unless it disagrees wildly with arithmetic
        step_flops = analytic_step_flops
        flops_disagree = None
        if flops_cost_analysis:
            ratio = flops_cost_analysis / analytic_step_flops
            flops_disagree = not (0.5 <= ratio <= 2.0)
            if not flops_disagree:
                step_flops = flops_cost_analysis
        mfu = imgs_per_sec * step_flops / BATCH / peak

        error = None
        if not finite:
            error = "nonfinite_loss"
        elif distinct < min(len(idx), 3):
            error = "losses_not_distinct"
        elif mfu > MFU_PLAUSIBLE_MAX:
            # physically implausible — a measurement bug, not a result
            error = "mfu_exceeds_plausible_peak"

        result = {
            "metric": "resnet50_imagenet_train_images_per_sec_per_chip",
            "value": round(imgs_per_sec, 2),
            "unit": "images/sec",
            "vs_baseline": round(imgs_per_sec / V100_BASELINE_IMGS_PER_SEC, 3),
            "backend": backend,
            "device_kind": device_kind,
            "device_count": len(devices),
            "amp": amp,
            "data": data_mode,
            "step_ms": round(per_step_s * 1000, 3),
            "batch": BATCH,
            "iters": ITERS,          # the requested knob (slope n2)
            "steps_run": len(losses),  # actual timed steps = n1 + n2
            "timing": timing_ev,
            "flops_per_step_xla": flops_cost_analysis,
            "flops_per_step_analytic": analytic_step_flops,
            "flops_disagree": flops_disagree,
            "chip_peak_bf16_flops": peak,
            "mfu": round(mfu, 4),
            "valid": error is None,
            "loss_first": round(loss_vals[0], 4),
            "loss_last": round(loss_vals[-1], 4),
            "distinct_losses": distinct,
            "finite": finite,
        }
        if error:
            result["error"] = error
        print(json.dumps(result))
        return 0 if error is None else 1


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
