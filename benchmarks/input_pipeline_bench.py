"""Input-pipeline A/B: synthetic device-resident feed vs the in-graph
recordio + double_buffer pipeline (done-bar: recordio step time within
~10% of synthetic).

Runs the SAME model twice and prints one JSON line:
  {"synthetic_step_ms", "recordio_step_ms", "ratio", ...}

The pipeline stores uint8 images and keeps them uint8 ON THE WIRE: the
double-buffer worker thread batches + host->device-transfers raw uint8
for batch N+1 while the device runs batch N, and the uint8 -> f32 decode
+ 1/255 scale happens IN-GRAPH on the device (reference
create_double_buffer_reader_op.cc does the decode on the host; here
f32-on-the-wire would be 4x the bytes of the host->device link).

Where the link is slow the transfer, not the pipeline, bounds the step:
a 224x224x3 uint8 batch at bs=32 is 4.8 MB. The row therefore also
reports the measured h2d bandwidth, the wire bytes per batch, the
resulting transfer floor, and pipeline_efficiency = floor / achieved —
the fraction of the physically possible rate the pipeline actually
delivers (1.0 = perfect overlap).

Env knobs: PIPE_BATCH (default 32), PIPE_ITERS (20), PIPE_DEPTH (resnet
depth, 50; use PIPE_MODEL=lenet for a CPU-friendly smoke).
"""
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.fluid import layers  # noqa: E402
from paddle_tpu.fluid.framework import Program, program_guard  # noqa: E402
from paddle_tpu.fluid.recordio_writer import (  # noqa: E402
    convert_reader_to_recordio_file,
)

BATCH = int(os.environ.get("PIPE_BATCH", "32"))
ITERS = int(os.environ.get("PIPE_ITERS", "20"))
WARMUP = int(os.environ.get("PIPE_WARMUP", "3"))
MODEL = os.environ.get("PIPE_MODEL", "resnet")
DEPTH = int(os.environ.get("PIPE_DEPTH", "50"))

if MODEL == "lenet":
    IMG_SHAPE, CLASSES = [1, 28, 28], 10
else:
    IMG_SHAPE, CLASSES = [3, 224, 224], 1000
IMG_ELEMS = int(np.prod(IMG_SHAPE))


def _build_model(img, label):
    if MODEL == "lenet":
        from paddle_tpu.models import lenet

        cost, _, _ = lenet.build(img, label)
    else:
        from paddle_tpu.models import resnet

        cost, _, _ = resnet.build_train(img, label, class_dim=CLASSES,
                                        depth=DEPTH)
    fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9).minimize(cost)
    return cost


def _measure(exe, main, scope, cost, feed):
    from benchmarks._timing import step_time_from_iters

    a_param = main.global_block().all_parameters()[0].name

    def _dispatch(_i):
        exe.run(main, feed=feed, fetch_list=[cost], return_numpy=False)
        return scope.find_var(a_param)

    per_step_s, _ev = step_time_from_iters(_dispatch, ITERS, WARMUP)
    return per_step_s * 1000


def run_synthetic():
    import jax.numpy as jnp

    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            img = layers.data(name="img", shape=IMG_SHAPE, dtype="float32")
            label = layers.data(name="label", shape=[1], dtype="int64")
            cost = _build_model(img, label)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {
            "img": jnp.asarray(
                rng.rand(BATCH, *IMG_SHAPE).astype(np.float32)),
            "label": jnp.asarray(
                rng.randint(0, CLASSES, size=(BATCH, 1)).astype(np.int64)),
        }
        return _measure(exe, main, scope, cost, feed)


def run_recordio(path):
    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            # uint8 stays uint8 through batching, the double-buffer
            # thread, and the wire; the decode runs on-device in-graph
            reader = layers.open_recordio_file(
                path, shapes=[IMG_SHAPE, [1]], dtypes=["uint8", "int64"]
            )
            reader = layers.multi_pass(reader, pass_num=8)
            reader = layers.batch(reader, batch_size=BATCH, drop_last=True)
            reader = layers.double_buffer(reader, capacity=2)
            raw, label = layers.read_file(reader)
            img = layers.scale(layers.cast(raw, "float32"), 1.0 / 255.0)
            cost = _build_model(img, label)
        exe = fluid.Executor()
        exe.run(startup)
        return _measure(exe, main, scope, cost, feed={})


def _h2d_mbps(nbytes):
    """Measured host->device bandwidth for a batch-sized uint8 buffer
    (sync round trip subtracted)."""
    import jax

    from benchmarks._timing import device_sync, sync_roundtrip_ms

    buf = np.ones((nbytes,), np.uint8)
    d = jax.device_put(buf)
    device_sync(d)
    rt = sync_roundtrip_ms() / 1000.0
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        d = jax.device_put(buf)
        device_sync(d)
    per = (time.perf_counter() - t0) / reps - rt
    if per <= 0:
        return None
    return nbytes / per / 1e6


def main():
    # same precision configuration as bench.py (bf16 MXU operands) so
    # synthetic_step_ms here matches its step time
    from paddle_tpu.fluid.flags import set_flags

    set_flags({"amp": os.environ.get("PIPE_AMP", "1") == "1"})
    n_samples = (WARMUP + ITERS + 2) * BATCH
    rng = np.random.RandomState(1)

    def gen():
        for _ in range(n_samples):
            yield (rng.randint(0, 256, size=(IMG_ELEMS,)).astype(np.uint8),
                   rng.randint(0, CLASSES, size=(1,)).astype(np.int64))

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "pipe.recordio")
        t0 = time.perf_counter()
        convert_reader_to_recordio_file(path, gen)
        write_s = time.perf_counter() - t0

        # bandwidth probe FIRST: once run_recordio starts, its
        # double-buffer daemon keeps prefetching over the same link
        # and a contended link would understate h2d_MBps (and so overstate
        # transfer_floor_ms / pipeline_efficiency)
        wire_bytes = BATCH * IMG_ELEMS  # uint8 images dominate; labels ~0
        mbps = _h2d_mbps(wire_bytes)
        syn_ms = run_synthetic()
        rio_ms = run_recordio(path)

    import jax

    transfer_ms = (wire_bytes / (mbps * 1e6) * 1e3) if mbps else None
    floor_ms = max(syn_ms, transfer_ms) if transfer_ms else syn_ms
    print(json.dumps({
        "model": MODEL,
        "batch": BATCH,
        "iters": ITERS,
        "backend": jax.default_backend(),
        "synthetic_step_ms": round(syn_ms, 3),
        "recordio_step_ms": round(rio_ms, 3),
        "ratio": round(rio_ms / syn_ms, 3),
        "within_10pct": rio_ms <= syn_ms * 1.10,
        "wire_bytes_per_batch": wire_bytes,
        "h2d_MBps": round(mbps, 1) if mbps else None,
        "transfer_floor_ms": round(transfer_ms, 1) if transfer_ms else None,
        "pipeline_efficiency": round(floor_ms / rio_ms, 3),
        "within_10pct_of_floor": rio_ms <= floor_ms * 1.10,
        "recordio_write_s": round(write_s, 1),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
