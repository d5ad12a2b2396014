"""The benchmark's per-layer metric that reads where tokens are chosen
(ISSUE 29): `sched_device_choice_pct`, data only under `perf/`, read from
the program's histogram `serving.decode.device_choice_pct`.

The benchmark's own tests live in `perf/tests` and are not collected by
the tier-1 command; this case is, so that a tree whose BENCHMARK.json no
longer loads with the metric fails here.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.lib.loader import Benchmark  # noqa: E402

NAME, CELL = "sched_device_choice_pct", "xglm17b_chat"
HISTOGRAM = "serving.decode.device_choice_pct"


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


def test_device_choice_metric_loads_and_reads_the_histograms_avg(bench):
    bench.check_files()
    entry = bench.doc["per_layer"][-1]          # appended, not inserted
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert entry["moves"] in bench.end_to_end(CELL)
    (found, desc), = [(m, d) for m, d in bench.per_layer(CELL)
                      if m["name"] == NAME]
    assert found is entry
    assert (desc["reader"], desc["histogram"], desc["stat"]) == (
        "histogram", HISTOGRAM, "avg")
    assert (desc["name"], desc["unit"], desc["layer"], desc["moves"]) == (
        NAME, "%", "scheduler", "serve_tokens_per_s")
    # data only: no reader of its own, and the training cell is not asked
    assert not os.path.exists(bench.path("layer_metrics", NAME + ".py"))
    assert NAME not in [m["name"] for m, _d in
                        bench.per_layer("resnet50_train")]
    facts = {"histograms": {HISTOGRAM: {
        "count": 424, "sum": 42188.0, "avg": 99.5, "min": 93.75,
        "max": 100.0, "p50": 100.0}}}
    assert bench.read_layer_metric(entry, desc, facts) == 99.5
    # the parent's case: a program without the histogram, or one whose
    # window chose no token, gives nothing to read and does not raise
    assert bench.read_layer_metric(entry, desc, {"histograms": {}}) is None
    assert bench.read_layer_metric(
        entry, desc, {"histograms": {HISTOGRAM: {"count": 0}}}) is None


def test_the_program_registers_what_the_metric_reads():
    """The histogram and the two counters exist under the names the
    benchmark's data file and the run's `# counters:` line use."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import decode  # noqa: F401  (registers them)

    snap = metrics.snapshot("serving.decode.")
    assert isinstance(snap[HISTOGRAM], dict)
    for name in ("device_choices", "host_choices"):
        assert snap["serving.decode." + name] == 0
