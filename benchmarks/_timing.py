"""Fetch-sync slope timing.

A device->host fetch of a value that depends on a computation cannot
complete before the computation does, on any backend — so it is the
barrier these helpers time against. (Whether ``jax.block_until_ready``
alone is an equally honest barrier on the chip tool's machine is ROADMAP
S0's question; nothing here depends on the answer.)

  * device_sync(x)   — fetch a single element DERIVED FROM x (a jitted
    1-element reduce; 4-byte transfer). Completion of the fetch implies
    completion of everything x depends on. Cost: one round trip.

  * slope timing     — run the step n1 times + one sync, then n2 times
    + one sync; per-step time = (t2 - t1) / (n2 - n1). The constant
    round-trip latency and any per-run overhead cancel, leaving
    steady-state device time. Both raw totals are reported so the
    subtraction is auditable.

Used by bench.py and the benchmarks/*.py scripts. ``require_tpu`` is
their shared gate: a device benchmark that finds no TPU exits non-zero.
"""
import time

import numpy as np

import jax
import jax.numpy as jnp


def require_tpu():
    """Gate of every device benchmark: returns ``jax.devices()[0]`` when
    it is a TPU, else exits non-zero naming the platform JAX gave — a
    number from another backend is never printed under a chip metric's
    name."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"this benchmark measures the TPU; JAX gave "
                         f"platform {dev.platform!r} ({dev.device_kind})")
    return dev


def framework_metrics():
    """Compact snapshot of the paddle_tpu.observability registry (nonzero
    counters/gauges, populated histograms) for embedding in BENCH_*.json
    — the perf trajectory then carries framework-side numbers (jit
    compiles vs cache hits, step-latency percentiles, RPC bytes), not
    wall clock alone. Never raises: benches must survive a broken or
    absent registry."""
    try:
        from paddle_tpu.observability import metrics

        snap = metrics.snapshot(skip_zero=True)
        # fault-tolerance counters ride along even at zero: an artifact
        # from a distributed run must SHOW that no retransmit was
        # double-applied and no trainer was evicted, not omit the lane
        for name in ("rpc.server.dedup_hits", "pserver.evicted_trainers",
                     "elastic.resumes"):
            snap.setdefault(name, metrics.counter(name).value())
        return snap
    except Exception:  # registry unavailable: report that, don't die
        return {}


def compile_cost_report():
    """The executor's per-compiled-executable XLA cost records (ISSUE 3:
    cost_analysis flops/bytes, memory_analysis under compile_stats=
    'full') for embedding in evidence dicts — BENCH artifacts then carry
    what the COMPILER says a step costs, not wall clock alone. Empty
    when the run never went through the fluid executor (raw-jax benches)
    or compile_stats is off. Never raises."""
    try:
        from paddle_tpu.fluid.executor import compile_report

        return compile_report()
    except Exception:
        return []


def _first_leaf(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("device_sync: no array leaves in output")
    return leaves[0]


@jax.jit
def _probe(x):
    # 1-element reduce: depends on x, transfers 4-8 bytes
    return jnp.sum(jnp.ravel(x)[:1])


def device_sync(x):
    """True device barrier: fetch one element derived from `x` to host.

    Returns the fetched float (occasionally useful as integrity
    evidence). One host round trip; use once per timed run, never per
    step.
    """
    return float(np.asarray(_probe(_first_leaf(x))))


def sync_roundtrip_ms(samples: int = 3) -> float:
    """Measured cost of device_sync on an already-materialized array —
    the constant the slope method cancels; recorded in artifacts as
    evidence of the fetch's latency floor."""
    x = jnp.ones((8,), jnp.float32)
    device_sync(x)  # compile the probe
    t0 = time.perf_counter()
    for _ in range(samples):
        device_sync(x)
    return (time.perf_counter() - t0) / samples * 1000.0


def timed_run(dispatch, n):
    """Dispatch `n` steps (dispatch(i) -> device output), one sync at the
    end. Returns (seconds, last_output)."""
    out = None
    t0 = time.perf_counter()
    for i in range(n):
        out = dispatch(i)
    device_sync(out)
    return time.perf_counter() - t0, out


def step_time_s(dispatch, n1, n2, warmup=1):
    """Steady-state per-step seconds via the slope method.

    dispatch(i) must enqueue one step and return a device value that
    depends on the step's full computation (e.g. the loss, or an updated
    parameter). Runs warmup steps (synced) first, then the n1- and
    n2-step timed runs. Requires n2 > n1 >= 1.

    Returns (per_step_s, evidence_dict). A non-increasing t2<=t1 pair
    (a stall in the shorter run) yields per_step_s from the n2 run alone with
    the round trip subtracted, flagged in the evidence.
    """
    if not n2 > n1 >= 1:
        raise ValueError(f"need n2 > n1 >= 1, got {n1}, {n2}")
    for i in range(warmup):
        out = dispatch(i)
    if warmup:
        device_sync(out)
    t1, _ = timed_run(dispatch, n1)
    t2, _ = timed_run(dispatch, n2)
    evidence = {
        "method": "slope_sync",
        "n1": n1, "n2": n2,
        "t1_s": round(t1, 4), "t2_s": round(t2, 4),
        "framework_metrics": framework_metrics(),
        "compile_report": compile_cost_report(),
    }
    if t2 > t1:
        per_step = (t2 - t1) / (n2 - n1)
    else:
        rt = sync_roundtrip_ms() / 1000.0
        per_step = max(t2 - rt, 1e-9) / n2
        evidence["slope_degenerate"] = True
        evidence["roundtrip_s"] = round(rt, 4)
    evidence["per_step_ms"] = round(per_step * 1000.0, 4)
    return per_step, evidence


def step_time_from_iters(dispatch, iters, warmup):
    """The shared policy every bench uses to map a user-facing ITERS knob
    onto slope runs: n1 = iters//3 (>=1), n2 = iters (> n1). Keeping it
    here means one edit changes every harness identically. NOTE the total
    timed step count is n1 + n2 (~1.33x iters) — callers reporting
    executed-step counts should report that, not iters."""
    n1 = max(1, iters // 3)
    return step_time_s(dispatch, n1, max(iters, n1 + 1), warmup=warmup)


def sample_indices(n, k=8):
    """<= k+1 indices over range(n), always including 0 and n-1 — for
    integrity-sampling per-step losses without fetching every one. Ceil
    stride so the count actually stays <= k
    (a floor stride both overshoots the cap and can push the final index
    out of a later truncation)."""
    if n <= 0:
        return []
    stride = -(-n // k)  # ceil(n / k)
    return sorted({0, n - 1, *range(0, n, stride)})


def kernel_time_ms(dispatch, target_s=0.3, max_iters=20000, warmup=2):
    """Per-call milliseconds for a micro-kernel (µs-to-ms scale), where a
    single call is far below the sync round trip's ~±5 ms jitter.

    Calibrates: one small timed run estimates the per-call cost, then the
    iteration count is chosen so the measured window is ~`target_s` of
    real device work, and the slope method cancels the latency. dispatch
    (i) -> device output, as in step_time_s.

    Returns (ms_per_call, evidence_dict).
    """
    for i in range(warmup):
        out = dispatch(i)
    if warmup:
        device_sync(out)
    rt = sync_roundtrip_ms() / 1000.0
    n_cal = 16
    t_cal, _ = timed_run(dispatch, n_cal)
    per_rough = max((t_cal - rt) / n_cal, 1e-7)
    n2 = int(min(max(target_s / per_rough, 64), max_iters))
    n1 = max(n2 // 4, 1)
    per, ev = step_time_s(dispatch, n1, n2, warmup=0)
    ev["calibration_per_call_ms"] = round(per_rough * 1000.0, 5)
    ev["roundtrip_ms"] = round(rt * 1000.0, 1)
    return per * 1000.0, ev
