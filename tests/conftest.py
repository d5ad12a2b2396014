"""Test harness config: run everything on a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware (the chip
itself is checked by chip_smoke.py through the chip tool). The platform
and device count are pinned via jax.config before any backend
initialization.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# the suite compiles the same toy decode ladders over and over, each in
# well under JAX's default one-second floor for the persistent cache
# (paddle_tpu.place_compile_cache): without the floor the repeats within
# ONE cold run become cache reads (test_spec_decode.py: 135 s -> 111 s).
# The compile-count assertions count traces, not XLA compiles.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
assert len(jax.devices()) == 8, jax.devices()


import numpy as np
import pytest

# CI runs with strict shape inference: an emitter whose abstract eval
# fails unexpectedly is a hard build-time error here, not a warning
# (reference shape_inference.h enforce semantics).
# compile_stats is OFF for the suite: the default 'auto' re-lowers every
# program once per jit-cache miss for cost_analysis — ~19% wall on
# compile-heavy test files, which matters against tier-1's hard timeout.
# The tests that assert cost accounting enable it explicitly.
from paddle_tpu.fluid.flags import set_flags

# verify_programs runs the static IR verifier (paddle_tpu.analysis) on
# every program the executor compiles — structural checks per jit-cache
# miss, so malformed graphs fail with op-indexed diagnostics instead of
# deep JAX trace errors. On suite-wide here (off by default for users).
set_flags({"strict_shape_inference": True, "compile_stats": False,
           "verify_programs": True})


@pytest.fixture(autouse=True)
def _seed_numpy():
    """Deterministic test data: OpTest subclasses draw inputs from the global
    numpy RNG with tight float32 gradient tolerances — unseeded draws made
    e.g. TestLayerNorm flaky (~1 in 6)."""
    np.random.seed(90210)
    yield


@pytest.fixture(autouse=True)
def _observability_isolation():
    """Zero the process-wide metrics registry (and the trace ring) before
    every test (ISSUE 3 satellite): the registry is module-global by
    design, so without this a test asserting absolute counter values
    only passed in orderings where no earlier test touched the same
    counter. Registrations survive — module-level handles keep working —
    only the VALUES reset."""
    from paddle_tpu.observability import metrics

    metrics.reset_all()
    yield


class _ProfilerSession:
    """A ``jax.profiler`` session the way a traced benchmark run starts
    one (``perf/lib/trace.py``: the Python tracer off), and its host
    events read back from the ``.xplane.pb``."""

    def __init__(self, trace_dir):
        self.dir = str(trace_dir)
        self._open = False

    def start(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._open = True
        return self

    def stop(self, prefix=""):
        """End the session; the host planes' events whose name starts
        with ``prefix`` as dicts (``line`` is the thread's), by start."""
        import glob

        from jax.profiler import ProfileData

        if self._open:
            self._open = False
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        events = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                events += [
                    {"line": line.name, "name": ev.name,
                     "start": int(ev.start_ns),
                     "end": int(ev.start_ns + ev.duration_ns),
                     "args": {k: v for k, v in ev.stats}}
                    for ev in line.events if ev.name.startswith(prefix)]
        return sorted(events, key=lambda e: (e["start"], -e["end"]))


@pytest.fixture
def profiler_session(tmp_path):
    """An unstarted session writing under the test's tmp_path; a test
    that fails between start() and stop() still leaves none collecting."""
    session = _ProfilerSession(tmp_path / "profile")
    yield session
    if session._open:
        jax.profiler.stop_trace()
