"""benchmarks/_timing.py — the fetch-sync slope measurement layer the
benchmark scripts time through. CPU tests: arithmetic + contract, not
wall-clock.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import _timing


def test_sample_indices_includes_first_and_last():
    for n in (1, 2, 3, 7, 8, 9, 13, 16, 100):
        idx = _timing.sample_indices(n, k=8)
        assert idx[0] == 0
        assert idx[-1] == n - 1, (n, idx)
        assert len(idx) <= 9  # k + the explicit last
        assert idx == sorted(set(idx))
    assert _timing.sample_indices(0) == []


def test_sample_indices_13_includes_final_step():
    # the exact regression: 13 losses (n1=3 + n2=10), floor stride dropped
    # index 12 after truncation so loss_last wasn't the last loss
    idx = _timing.sample_indices(13, k=8)
    assert 12 in idx


def test_device_sync_returns_scalar_and_waits():
    import jax.numpy as jnp

    x = jnp.arange(8.0)
    v = _timing.device_sync(x)
    assert v == 0.0  # sum of first element
    # pytrees: syncs on the first leaf
    assert _timing.device_sync({"a": x + 1, "b": x}) == 1.0
    with pytest.raises(ValueError):
        _timing.device_sync([])


def test_step_time_s_slope_arithmetic(monkeypatch):
    # t(n) = latency + n * per_step must recover per_step exactly
    per, lat = 0.007, 0.075
    monkeypatch.setattr(_timing, "timed_run",
                        lambda dispatch, n: (lat + n * per, object()))
    monkeypatch.setattr(_timing, "device_sync", lambda x: 0.0)
    got, ev = _timing.step_time_s(lambda i: object(), 5, 20, warmup=1)
    assert got == pytest.approx(per, rel=1e-9)
    assert ev["method"] == "slope_sync"
    assert "slope_degenerate" not in ev


def test_step_time_s_degenerate_slope_falls_back(monkeypatch):
    # a stall in the shorter run: t2 <= t1 — must not return
    # negative/zero time
    times = {5: 0.5, 20: 0.4}
    monkeypatch.setattr(_timing, "timed_run",
                        lambda dispatch, n: (times[n], object()))
    monkeypatch.setattr(_timing, "device_sync", lambda x: 0.0)
    monkeypatch.setattr(_timing, "sync_roundtrip_ms", lambda samples=3: 75.0)
    got, ev = _timing.step_time_s(lambda i: object(), 5, 20, warmup=0)
    assert got > 0
    assert ev["slope_degenerate"] is True
    assert got == pytest.approx((0.4 - 0.075) / 20, rel=1e-9)


def test_step_time_s_rejects_bad_iter_counts():
    with pytest.raises(ValueError):
        _timing.step_time_s(lambda i: None, 5, 5)
    with pytest.raises(ValueError):
        _timing.step_time_s(lambda i: None, 0, 5)


def test_kernel_time_ms_accepts_warmup_zero(monkeypatch):
    # warmup=0 is valid for an already-warm kernel; used to NameError
    times = iter([0.08, 0.1, 0.3])  # cal, n1, n2

    def fake_timed_run(dispatch, n):
        return next(times), object()

    monkeypatch.setattr(_timing, "timed_run", fake_timed_run)
    monkeypatch.setattr(_timing, "device_sync", lambda x: 0.0)
    monkeypatch.setattr(_timing, "sync_roundtrip_ms", lambda samples=3: 75.0)
    ms, ev = _timing.kernel_time_ms(lambda i: object(), warmup=0)
    assert ms > 0
    assert ev["roundtrip_ms"] == 75.0
