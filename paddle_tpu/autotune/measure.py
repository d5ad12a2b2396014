"""Measure-or-model candidate selection + the executor's step-timing
log (ISSUE 8).

TVM (PAPERS.md) picks schedules by measuring candidates when it can and
consulting a cost model when it can't; this is that loop at framework
granularity:

  - ``measure_or_model(tunable_id, candidates, runner=...)`` — when a
    real executable exists, each candidate is timed (median of ``k``
    runs after one warmup, so jit compiles never pollute the sample)
    and the fastest wins; the decision lands in the tuning cache under
    (device_kind, tunable_id, shape_key), so a REPEAT session returns
    it without running anything.
  - ``measure_or_model(..., cost_fn=...)`` — the zero-run fallback:
    ``cost_fn(candidate)`` returns an XLA ``cost_analysis`` dict
    (``jit_cost`` below lowers a jax callable and extracts it via
    jax_compat) and
    the candidate with the lowest ``flops + bytes_accessed`` proxy
    wins. The proxy only ORDERS structurally different candidates —
    prefer measurement whenever a runner is available.
  - ``note_step_timing(tunable_id, program, feeds, ms)`` — the
    executor hook: every steady-state (non-compile) step's wall time is
    logged under a stable program/shape fingerprint, so the cache
    accumulates per-shape step costs across sessions and
    ``cached_step_ms`` can answer "have we measured this before?"
    without running it again.
"""
from __future__ import annotations

import hashlib
import json
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..observability import metrics as _metrics, tracing as _tracing
from .cache import TuningCache, get_cache, _median

__all__ = ["measure_or_model", "jit_cost", "model_score",
           "step_shape_key", "note_step_timing", "cached_step_ms"]

# one inc per TIMED candidate run — a bench re-run with a warm cache
# proves the skip by this counter's delta staying 0
_m_measurements = _metrics.counter("autotune.measurements")
_m_modeled = _metrics.counter("autotune.modeled")


def _canon(v: Any) -> Any:
    """JSON-round-trip normalization (tuples -> lists, int keys ->
    str): cached decisions are compared in the form they persist in."""
    try:
        return json.loads(json.dumps(v))
    except (TypeError, ValueError):
        return v


def model_score(cost: Dict[str, Any]) -> float:
    """Unitless cost-model proxy over an XLA cost_analysis dict:
    ``flops + bytes_accessed``. Good enough to order candidates that
    differ structurally (a fused vs unfused graph, a kernel vs a
    gather-then-dense reference); NOT a latency estimate — measured
    runs always override it in the cache (source 'measured' vs
    'model')."""
    flops = float(cost.get("flops") or 0.0)
    bytes_acc = float(cost.get("bytes accessed")
                      or cost.get("bytes_accessed") or 0.0)
    return flops + bytes_acc


def jit_cost(fn: Callable, *args, **kw) -> Dict[str, Any]:
    """Zero-run cost extraction: trace/lower ``fn`` at the given
    arguments (pure tracing — no XLA compile) and return its
    cost_analysis dict via jax_compat."""
    import jax

    from .. import jax_compat as _jc

    return _jc.cost_analysis_dict(jax.jit(fn).lower(*args, **kw))


def measure_or_model(tunable_id: str, candidates: Sequence[Any], *,
                     runner: Optional[Callable[[Any], Any]] = None,
                     cost_fn: Optional[Callable[[Any], Dict[str, Any]]]
                     = None,
                     k: int = 5, shape_key: str = "",
                     cache: Optional[TuningCache] = None,
                     device: Optional[str] = None
                     ) -> Tuple[Any, Dict[str, Any]]:
    """Pick the best candidate and persist the decision.

    Returns ``(best, evidence)`` where evidence carries the per-
    candidate scores and the source ('cache' when a previous session
    already decided — nothing is run in that case)."""
    cands = list(candidates)
    if not cands:
        raise ValueError("measure_or_model needs at least one candidate")
    c = cache or get_cache()
    prior = c.lookup(tunable_id, shape_key=shape_key, device=device)
    if prior is not None:
        # match through JSON canonicalization: a persisted tuple comes
        # back as a list, and the repeat-session skip must still fire —
        # the caller gets ITS candidate object back, not the JSON form
        pc = _canon(prior)
        for cand in cands:
            if _canon(cand) == pc:
                return cand, {"source": "cache", "value": cand}
    scores: List[float] = []
    if runner is not None:
        with _tracing.span("autotune.measure", tunable=str(tunable_id),
                           candidates=len(cands)):
            for cand in cands:
                runner(cand)  # warmup: the jit compile never counts
                times = []
                for _ in range(max(1, int(k))):
                    t0 = time.perf_counter()
                    runner(cand)
                    times.append((time.perf_counter() - t0) * 1e3)
                    _m_measurements.inc()
                scores.append(round(_median(times), 4))
        source = "measured"
    elif cost_fn is not None:
        for cand in cands:
            scores.append(float(model_score(cost_fn(cand))))
            _m_modeled.inc()
        source = "model"
    else:
        raise ValueError("need a runner (measure) or a cost_fn (model)")
    # ties break to the FIRST candidate — callers order by preference
    best_i = min(range(len(cands)), key=lambda i: (scores[i], i))
    best = cands[best_i]
    evidence = {"source": source,
                "scores": {str(cand): s for cand, s in zip(cands, scores)},
                "value": best}
    c.put(tunable_id, best, shape_key=shape_key, source=source,
          device=device,
          extra={"scores": evidence["scores"]})
    return best, evidence


# -- the executor's per-shape step log -----------------------------------

def _program_fingerprint(program) -> str:
    """Hash of the op-type sequence AND the declared var shapes —
    op types alone would pool two same-stack models of different
    widths (an fc size=64 vs size=4096 has identical op types and feed
    shapes; only the weight vars differ) into one timing record.
    Memoized on the Program per version: the per-step path must not
    rehash a multi-thousand-op program."""
    cached = getattr(program, "_autotune_fingerprint", None)
    if cached is not None and cached[0] == program._version:
        return cached[1]
    block = program.global_block()
    ops = ",".join(op.desc.type for op in block.ops)
    shapes = ",".join(f"{n}:{tuple(v.shape) if v.shape else ()}"
                      for n, v in sorted(block.vars.items()))
    h = hashlib.md5(f"{ops}|{shapes}".encode()).hexdigest()[:8]
    program._autotune_fingerprint = (program._version, h)
    return h


def _dtype_name(v) -> str:
    # no np.asarray: materializing a jax feed just to name its dtype
    # would be a device->host transfer on the per-step path
    dt = getattr(v, "dtype", None)
    return str(dt) if dt is not None else str(np.asarray(v).dtype)


def step_shape_key(program, feeds: Dict[str, Any]) -> str:
    """Stable fingerprint of (program structure, feed shapes/dtypes) —
    deliberately NOT ``program._version`` (a per-process counter that
    would never match across sessions): the op-type sequence hash plus
    the sorted feed signature."""
    sig = ";".join(
        f"{name}:{_dtype_name(v)}{tuple(np.shape(v))}"
        for name, v in sorted(feeds.items()))
    return f"{_program_fingerprint(program)}|{sig}"


def note_step_timing(tunable_id: str, program, feeds: Dict[str, Any],
                     ms: float):
    """Log one steady-state step time under the program/shape key (the
    ``FLAGS['autotune']`` executor hook — compile runs are excluded by
    the caller)."""
    get_cache().note_timing(tunable_id, step_shape_key(program, feeds),
                            float(ms))


def cached_step_ms(tunable_id: str, program,
                   feeds: Dict[str, Any]) -> Optional[float]:
    """Median step ms a previous session recorded for this exact
    program/shape, or None — the repeat-session measurement skip."""
    rec = get_cache().timing(tunable_id, step_shape_key(program, feeds))
    return float(rec["median_ms"]) if rec else None
