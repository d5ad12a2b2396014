"""Cost-model-driven autotuning (ISSUE 8): tuning cache, ladder
derivation, routing read-through, auto serving ladders, step-timing
log.

Coverage map:
  - derive_ladder is a PURE function — property-style tests: P99
    coverage, expected waste monotone non-increasing in the bucket
    budget, deterministic given the histogram, strictly beats the
    static 1/2/4/8/16 default on skewed traffic (the acceptance
    claim), expected_padding_waste agrees with bucket_for by hand;
  - TuningCache: round-trip through a real directory, corrupt file
    degrades to defaults (and stays writable), atomic tmp+rename with
    a chaos crash at the `autotune.save` site leaving the previous
    file intact;
  - routing reads THROUGH the cache: autotune.cache.hits/misses
    counter asserts on effective_flag, per-device-kind override (a
    foreign kind's record must NOT apply), the paged-attention
    kernel-vs-reference crossover re-routes via attention.route.*
    counters, trace_flags carries the effective values so the jit key
    tracks cache updates;
  - buckets="auto" / slots="auto": resolve from a recorded histogram
    at load, ladder fixed after warm — jit-compile counters pin the
    bucket bound and zero post-warm compiles (no wall-clock asserts,
    per tier-1 timing margin);
  - executor step-timing log: steady-state (non-compile) steps land in
    the cache under a stable shape key; compile runs are excluded.

Slow lane: the autotune CLI selftest and benchmarks/autotune_bench.py
--smoke as subprocesses.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu import autotune
from paddle_tpu.fluid.flags import effective_flag, get_flag, set_flags
from paddle_tpu.observability import metrics

STATIC = [1, 2, 4, 8, 16]


def _skewed_hist(seed):
    rng = np.random.RandomState(seed)
    hist = {}
    for _ in range(200):
        r = rng.rand()
        if r < 0.5:
            s = 1
        elif r < 0.75:
            s = int(rng.randint(2, 8))
        else:
            s = int(rng.randint(8, 24))
        hist[s] = hist.get(s, 0) + 1
    return hist


# --- ladder math (pure) --------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_derive_ladder_properties(seed):
    hist = _skewed_hist(seed)
    lad = autotune.derive_ladder(hist, max_buckets=5)
    assert lad == sorted(set(lad)) and lad[0] >= 1
    # the documented bound holds at every budget, tail or not
    for k in range(1, 7):
        assert len(autotune.derive_ladder(hist, max_buckets=k)) <= k
    # P99 coverage — and nothing admissible becomes inadmissible
    assert lad[-1] >= autotune.percentile_size(hist, 0.99)
    assert lad[-1] >= max(hist)
    # deterministic: two replicas derive the same ladder
    assert autotune.derive_ladder(hist, max_buckets=5) == lad
    # waste monotone non-increasing in the bucket budget
    wastes = [autotune.expected_padding_waste(
        hist, autotune.derive_ladder(hist, max_buckets=k))
        for k in range(1, 8)]
    for a, b in zip(wastes, wastes[1:]):
        assert b <= a + 1e-12, wastes


def test_derived_ladder_strictly_beats_static_on_skewed_traffic():
    """The acceptance shape: lumpy traffic (heavy 5/6-row mode padding
    to 8 under the geometric default) — the derived ladder must
    strictly reduce expected padding waste vs 1/2/4/8/16."""
    hist = {1: 50, 3: 30, 5: 60, 6: 40, 16: 2}
    derived = autotune.derive_ladder(hist, max_buckets=5)
    w_static = autotune.expected_padding_waste(hist, STATIC)
    w_derived = autotune.expected_padding_waste(hist, derived)
    assert w_derived < w_static, (derived, w_derived, w_static)


def test_expected_padding_waste_by_hand():
    # sizes 1 (exact), 3 (pads to 4: waste 1/4), 5 (pads to 8: 3/8)
    hist = {1: 2, 3: 1, 5: 1}
    w = autotune.expected_padding_waste(hist, STATIC)
    assert abs(w - (0 + 0 + 0.25 + 0.375) / 4) < 1e-12
    with pytest.raises(ValueError):
        autotune.expected_padding_waste(hist, [])
    with pytest.raises(ValueError):
        autotune.derive_ladder({}, max_buckets=3)


def test_derive_ladder_tail_rides_top_bucket():
    """A single giant outlier must not spend an optimization bucket:
    with coverage below its mass it rides the appended top, and the
    body's buckets still fit the body."""
    hist = {1: 500, 2: 300, 3: 100, 64: 1}
    lad = autotune.derive_ladder(hist, max_buckets=4, coverage=0.99)
    assert lad[-1] == 64
    assert set(lad[:-1]).issubset({1, 2, 3})
    # the budget-of-one-with-a-tail edge: [max] is the only legal
    # answer, never max_buckets + 1 entries
    assert autotune.derive_ladder(hist, max_buckets=1) == [64]


# --- the cache -----------------------------------------------------------

def test_cache_roundtrip_and_timing_log(tmp_path):
    c = autotune.TuningCache(str(tmp_path))
    c.put("flash_min_seq", 2048, source="measured")
    c.put("serving_buckets", [1, 3, 6], shape_key="ladder",
          source="derived")
    c.note_timing("executor.step", "k1", 1.0)
    c.note_timing("executor.step", "k1", 3.0)
    assert c.flush() == os.path.join(str(tmp_path),
                                     autotune.CACHE_FILENAME)
    c2 = autotune.TuningCache(str(tmp_path))
    assert c2.lookup("flash_min_seq", default=-1) == 2048
    assert c2.lookup("serving_buckets", shape_key="ladder") == [1, 3, 6]
    rec = c2.timing("executor.step", "k1")
    assert rec["n"] == 2 and abs(rec["median_ms"] - 2.0) < 1e-9
    assert rec["best_ms"] == 1.0
    # nothing dirty: flush is a no-op
    assert c2.flush() is None


def test_cache_corrupt_file_degrades_to_defaults(tmp_path):
    path = os.path.join(str(tmp_path), autotune.CACHE_FILENAME)
    with open(path, "w") as f:
        f.write("{not json")
    base = metrics.counter("autotune.cache.corrupt").value()
    c = autotune.TuningCache(str(tmp_path))  # must not raise
    assert metrics.counter("autotune.cache.corrupt").value() == base + 1
    assert c.lookup("flash_min_seq", default=3072) == 3072
    c.put("flash_min_seq", 99)
    assert c.flush()
    assert autotune.TuningCache(str(tmp_path)).lookup("flash_min_seq") == 99
    # wrong schema counts as corrupt too
    with open(path, "w") as f:
        json.dump({"schema": 999, "entries": {}}, f)
    assert autotune.TuningCache(str(tmp_path)).lookup(
        "flash_min_seq", default=-1) == -1


def test_cache_crash_between_tmp_write_and_rename_keeps_old(tmp_path):
    """The master.snapshot discipline at the `autotune.save` fault
    site: a crash mid-save leaves the previous file intact AND the
    cache dirty, so a retry persists everything."""
    from paddle_tpu.distributed import faults
    from paddle_tpu.distributed.faults import InjectedFault

    c = autotune.TuningCache(str(tmp_path))
    c.put("flash_min_seq", 1111)
    assert c.flush()
    c.put("flash_min_seq", 2222)
    with faults.scoped("crash@autotune.save:0"):
        with pytest.raises(InjectedFault):
            c.flush()
    # the torn write never replaced the consistent previous snapshot
    assert autotune.TuningCache(str(tmp_path)).lookup(
        "flash_min_seq") == 1111
    # still dirty: the retry writes the new value
    assert c.flush()
    assert autotune.TuningCache(str(tmp_path)).lookup(
        "flash_min_seq") == 2222


def test_measure_repeat_skip_survives_json_roundtrip(tmp_path):
    """Tuple candidates persist as JSON lists; the repeat-session skip
    must still fire — and hand back the caller's own candidate object,
    not the JSON form."""
    runs = [0]

    def runner(cand):
        runs[0] += 1

    c = autotune.TuningCache(str(tmp_path))
    best, ev = autotune.measure_or_model(
        "shape_knob", [(8, 128), (16, 64)], runner=runner, k=2, cache=c)
    assert ev["source"] == "measured" and runs[0] > 0
    c.flush()
    first_runs = runs[0]
    c2 = autotune.TuningCache(str(tmp_path))  # the "repeat session"
    best2, ev2 = autotune.measure_or_model(
        "shape_knob", [(8, 128), (16, 64)], runner=runner, k=2, cache=c2)
    assert ev2["source"] == "cache", ev2
    assert isinstance(best2, tuple) and best2 == best
    assert runs[0] == first_runs, "repeat session must not re-measure"


# --- routing reads through the cache ------------------------------------

def test_routing_consults_cache_with_counters():
    hits = metrics.counter("autotune.cache.hits")
    misses = metrics.counter("autotune.cache.misses")
    with autotune.scoped(enable=True) as cache:
        m0 = misses.value()
        assert effective_flag("flash_min_seq") == get_flag("flash_min_seq")
        assert misses.value() == m0 + 1, \
            "cold routing must be a counted cache miss"
        cache.put("flash_min_seq", 640, source="override")
        h0 = hits.value()
        assert effective_flag("flash_min_seq") == 640
        assert hits.value() == h0 + 1, \
            "tuned routing must be a counted cache hit"
    # autotune off: the constant, no cache traffic
    m1 = misses.value()
    assert effective_flag("flash_min_seq") == get_flag("flash_min_seq")
    assert misses.value() == m1


def test_per_device_kind_override():
    """The cache is keyed by device kind: a foreign chip's measured
    crossover must never route THIS chip."""
    with autotune.scoped(enable=True) as cache:
        cache.put("flash_min_seq", 4096, device="some_other_chip",
                  source="measured")
        assert effective_flag("flash_min_seq") == get_flag("flash_min_seq")
        cache.put("flash_min_seq", 256, device=autotune.device_kind(),
                  source="measured")
        assert effective_flag("flash_min_seq") == 256
        # trace_flags carries the EFFECTIVE value: a cache update means
        # a new jit key, never a stale-routed executable replay
        from paddle_tpu.fluid.flags import trace_flags

        assert 256 in trace_flags()


def test_paged_attention_crossover_reads_cache():
    """paged_min_slots demotes the always-kernel answer to a cold-cache
    default: with a tuned threshold above the batch, routing falls to
    the reference even with kernels forced on — counter-asserted and
    numerically identical."""
    from paddle_tpu.fluid.ops.pallas_kernels.paged_attention import (
        paged_attention, paged_attention_reference)

    rng = np.random.RandomState(0)
    q = rng.randn(2, 2, 4).astype(np.float32)
    kp = rng.randn(5, 4, 1, 4).astype(np.float32)
    vp = rng.randn(5, 4, 1, 4).astype(np.float32)
    tables = np.array([[1, 2], [3, 0]], np.int32)
    lens = np.array([6, 3], np.int32)
    k_ctr = metrics.counter("attention.route.paged_kernel")
    r_ctr = metrics.counter("attention.route.paged_reference")
    prev = get_flag("use_pallas_kernels")
    set_flags({"use_pallas_kernels": True})
    try:
        with autotune.scoped(enable=True) as cache:
            cache.put("paged_min_slots", 8, source="measured")  # 2 < 8
            r0, k0 = r_ctr.value(), k_ctr.value()
            out = paged_attention(q, kp, vp, tables, lens)
            assert r_ctr.value() == r0 + 1 and k_ctr.value() == k0
            ref = paged_attention_reference(q, kp, vp, tables, lens)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)
            # at/above the threshold the kernel routes back in
            cache.put("paged_min_slots", 2, source="measured")
            k1 = k_ctr.value()
            paged_attention(q, kp, vp, tables, lens, interpret=True)
            assert k_ctr.value() == k1 + 1
    finally:
        set_flags({"use_pallas_kernels": prev})


# --- auto ladders in the serving engines --------------------------------

def _model_dir(tmp_path):
    from paddle_tpu.serving.__main__ import make_model_dir

    d, probe, ref = make_model_dir(os.path.join(str(tmp_path), "m"))
    return d


def test_engine_auto_buckets_resolve_from_histogram(tmp_path):
    """buckets='auto' resolves ONCE at load from the observed request
    histogram; the ladder is fixed after warm — the jit cache stays
    bounded at len(buckets) and mixed traffic compiles nothing new
    (counter asserts, no wall clocks)."""
    from paddle_tpu.serving import InferenceEngine

    d = _model_dir(tmp_path)
    with autotune.scoped(enable=True) as cache:
        autotune.reset_histograms()
        hist = {1: 40, 3: 25, 6: 20}
        for s, c in hist.items():
            for _ in range(c):
                autotune.observe("serving_buckets", s)
        eng = InferenceEngine.from_inference_dir(d, name="auto_m",
                                                 buckets="auto")
        try:
            assert eng.buckets == autotune.derive_ladder(hist,
                                                         max_buckets=5)
            assert eng.buckets[-1] == 6
            # the derivation persisted: source 'derived' in the cache
            assert cache.lookup("serving_buckets", shape_key="ladder",
                                count=False) == eng.buckets
            compiles = metrics.counter("executor.jit_compiles")
            c_warm = compiles.value()
            pool = np.random.RandomState(1).rand(6, 8).astype(np.float32)
            for rows in (1, 2, 3, 4, 6, 5, 1):
                outs, _v = eng.infer({"x": pool[:rows]})
                assert outs[0].shape[0] == rows
            assert compiles.value() == c_warm, \
                "auto ladder must keep the zero-post-warm-compiles bound"
        finally:
            eng.stop()
        autotune.reset_histograms()


def test_decode_auto_slots_zero_post_warm_compiles():
    """slots='auto' on a recorded demand histogram: the derived slot
    ladder pre-compiles at warm and churn mints nothing —
    serving.decode.compiles stays at its post-warm value (the ISSUE 8
    acceptance counter)."""
    from paddle_tpu.serving import DecodeEngine, DecoderSpec

    spec = DecoderSpec(vocab=32, d_model=16, n_layers=2, n_heads=2,
                       n_kv_heads=1, seed=7)
    with autotune.scoped(enable=True):
        autotune.reset_histograms()
        for demand, count in {1: 30, 2: 20, 3: 14}.items():
            for _ in range(count):
                autotune.observe("decode_slots", demand)
        eng = DecodeEngine(spec, name="auto_d", slots="auto", page_size=4,
                           num_pages=24, max_seq_len=12, max_queue=16)
        try:
            assert eng.slot_ladder == [1, 2, 3]
            compiles = metrics.counter("serving.decode.compiles")
            c_warm = compiles.value()
            # the step ladder, and the host route's row fetch: one
            # program a slot count (ISSUE 29)
            assert c_warm == len(eng.slot_ladder) * (
                len(eng.table_width_ladder) * len(eng.chunk_ladder) + 1)
            rng = np.random.RandomState(3)
            reqs = [eng.submit(rng.randint(0, 32,
                                           size=1 + int(rng.randint(4))),
                               max_new_tokens=1 + int(rng.randint(5)))
                    for _ in range(7)]
            for r in reqs:
                assert r.ev.wait(120) and r.error is None, r.error
            assert compiles.value() == c_warm, \
                "churn on an auto-derived ladder must compile nothing"
        finally:
            eng.stop()
        autotune.reset_histograms()


def test_resolve_ladder_prefers_cache_then_histogram_then_default():
    with autotune.scoped(enable=True) as cache:
        autotune.reset_histograms()
        default = [1, 2, 4]
        # nothing observed, nothing cached: the static default
        assert autotune.resolve_ladder("t_ladder", default) == default
        # enough observations: derived + persisted
        for _ in range(40):
            autotune.observe("t_ladder", 3)
        lad = autotune.resolve_ladder("t_ladder", default)
        assert lad == [3]
        # cached now: an empty histogram still answers the derivation
        autotune.reset_histograms()
        assert autotune.resolve_ladder("t_ladder", default) == [3]
        # an operator pin in the cache beats everything
        cache.put("t_ladder", [2, 4], shape_key="ladder",
                  source="override")
        assert autotune.resolve_ladder("t_ladder", default) == [2, 4]
        autotune.reset_histograms()


def test_merge_observed_replays_a_saved_histogram():
    """A bench artifact's shape_histogram (JSON string keys) replays
    into the live recorder and drives resolution without the bench
    session's cache."""
    with autotune.scoped(enable=True):
        autotune.reset_histograms()
        autotune.merge_observed("m_ladder", {"1": 30, "4": 20})
        autotune.merge_observed("m_ladder", {"4": 5})
        assert autotune.histogram("m_ladder") == {1: 30, 4: 25}
        assert autotune.resolve_ladder("m_ladder", [1, 2, 4, 8],
                                       min_observations=32) == [1, 4]
        autotune.reset_histograms()


# --- executor step-timing log -------------------------------------------

def test_executor_records_steady_state_step_timings():
    """With autotune on, cache-hit executor steps land in the tuning
    cache under a stable (program fingerprint, feed signature) key;
    the compile run is excluded."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, unique_name
    from paddle_tpu.fluid.framework import program_guard

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup), unique_name.guard():
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.fc(input=x, size=3)
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        feed = {"x": np.ones((2, 4), np.float32)}
        with autotune.scoped(enable=True) as cache:
            key = autotune.step_shape_key(main, feed)
            exe.run(main, feed=feed, fetch_list=[y], scope=scope)  # compile
            assert cache.timing("executor.step", key) is None, \
                "the compile run must not pollute the timing log"
            exe.run(main, feed=feed, fetch_list=[y], scope=scope)
            exe.run(main, feed=feed, fetch_list=[y], scope=scope)
            rec = cache.timing("executor.step", key)
            assert rec is not None and rec["n"] == 2, rec
            assert rec["median_ms"] > 0
            # the repeat-session query answers the same record
            assert autotune.cached_step_ms("executor.step", main, feed) \
                == rec["median_ms"]
            # the key is shape-sensitive: a new batch size is a new key
            assert cache.timing(
                "executor.step",
                autotune.step_shape_key(
                    main, {"x": np.ones((3, 4), np.float32)})) is None


# --- slow lane: CLI selftest + bench smoke ------------------------------

@pytest.mark.slow
def test_autotune_selftest_cli():
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.autotune", "--selftest"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "all ok" in proc.stdout


@pytest.mark.slow
def test_autotune_bench_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "benchmarks", "autotune_bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    ev = json.loads(proc.stdout.strip().splitlines()[-1])
    lad = ev["ladder"]
    assert lad["realized"]["derived"]["padding_waste_mean"] < \
        lad["realized"]["static"]["padding_waste_mean"]
    assert ev["measure"]["repeat_session_timed_runs"] == 0
    assert ev["decode"]["post_warm_compiles"] == 0
