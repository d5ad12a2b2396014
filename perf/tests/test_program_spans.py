"""The per-layer metrics that read the program's own spans and histograms
(PR 27): `sched_sample_ms`, `sched_host_ms`, `exec_run_ms.train`.

    JAX_PLATFORMS=cpu python3 -m pytest perf/tests/test_program_spans.py -q

CPU only, at the toy sizes of test_perfbench.py's rehearsals (its helpers
are used as they are); no number from here is a device metric.
"""
import glob
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import test_perfbench as base  # noqa: E402
from perf.lib.loader import Benchmark  # noqa: E402

NEW = {"sched_sample_ms": "xglm17b_chat", "sched_host_ms": "xglm17b_chat",
       "exec_run_ms.train": "resnet50_train"}
CHAT, TRAIN = "xglm17b_chat", "resnet50_train"


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


def _files(root):
    return {os.path.relpath(os.path.join(dp, f), root)
            for dp, _d, fs in os.walk(root) for f in fs
            if "__pycache__" not in dp}


def test_the_three_metrics_load_and_every_named_file_exists(bench):
    bench.check_files()
    entries = {m["name"]: m for m in bench.doc["per_layer"]}
    assert list(entries)[-3:] == list(NEW)      # appended, in this order
    for name, cell in NEW.items():
        e = entries[name]
        assert (e["unit"], e["better"], e["source"]) == (
            "ms", "lower", "program_span")
        assert e["workloads"] == [cell]
        (entry, desc), = [(m, d) for m, d in bench.per_layer(cell)
                          if m["name"] == name]
        assert desc["name"] == name and desc["moves"] == e["moves"]
        assert e["moves"] in bench.end_to_end(cell)
        # a cell that does not list the metric is not asked for it
        other = TRAIN if cell == CHAT else CHAT
        assert name not in [m["name"] for m, _d in bench.per_layer(other)]


def test_adding_them_changed_no_file_that_was_there(tmp_path, bench):
    """A checkout without the three, then the three added as PR 27 added
    them: new files and appended entries, every other file as it was."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    metrics_dir = root / "perf" / "layer_metrics"
    mine = [p for name in NEW for p in glob.glob(
        str(metrics_dir / (name + ".*")))]
    assert sorted(os.path.basename(p) for p in mine) == [
        "exec_run_ms.train.json", "exec_run_ms.train.py",
        "sched_host_ms.json", "sched_sample_ms.json"]
    held = {p: open(p, "rb").read() for p in mine}
    for p in mine:
        os.remove(p)
    doc = json.loads(json.dumps(bench.doc))
    added = doc["per_layer"][-3:]
    del doc["per_layer"][-3:]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    before = {p: open(root / p, "rb").read() for p in _files(root)}
    without = Benchmark(str(root))
    without.check_files()
    assert not set(NEW) & {m["name"] for cell in (CHAT, TRAIN)
                           for m, _d in without.per_layer(cell)}
    # now add them: files, and entries at the end of per_layer
    for p, data in held.items():
        with open(p, "wb") as f:
            f.write(data)
    doc["per_layer"] += added
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    with_them = Benchmark(str(root))
    with_them.check_files()
    assert [m["name"] for m, _d in with_them.per_layer(CHAT)][-2:] == [
        "sched_sample_ms", "sched_host_ms"]
    after = _files(root)
    assert after - set(before) == {os.path.relpath(p, root) for p in mine}
    for p, data in before.items():
        if p != "BENCHMARK.json":
            assert open(root / p, "rb").read() == data, p


def _host_events(trace_dir):
    """(name, line, start, end) of the host planes' events of a trace."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, line.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
    return out


def test_serving_rehearsal_reads_both_and_the_trace_holds_both_families(
        bench, tmp_path):
    """A traced rehearsal of the serving runner: the two histograms reach
    the line through facts["histograms"] with no edit to the runner, and
    the wrappers' perf.* annotations sit beside the program's own spans
    in one trace, each wrapper inside the span of the same role."""
    facts = base._toy_serving(bench, CHAT, trace=True,
                              trace_dir=str(tmp_path))
    _err, line = base._report(bench, facts, True)
    assert line["correct"] is True, base._bad(line)
    got = line["metrics"]
    assert got["sched_sample_ms"]["unit"] == "ms"
    # three of four requests are sampled at T=1: a step samples
    assert got["sched_sample_ms"]["value"] > 0.0
    assert got["sched_host_ms"]["value"] > 0.0
    steps = facts["counters"]["serving.decode.steps"]
    for name in ("serving.decode.sample_ms", "serving.decode.sched_ms"):
        # one observation a step, at the step's end: the window's reset
        # and its snapshot may each fall inside a step
        assert abs(facts["histograms"][name]["count"] - steps) <= 1
    # the histograms are read untraced too (the driver's runs are)
    for entry, desc in bench.per_layer(CHAT):
        if entry["name"] in NEW:
            assert bench.read_layer_metric(
                entry, desc, dict(facts, trace=None)) is not None
            assert bench.read_layer_metric(
                entry, desc, dict(facts, histograms={})) is None
    events = _host_events(str(tmp_path))
    names = {n for n, _l, _s, _e in events}
    assert {"perf.engine.scheduler_step", "perf.engine.device_call",
            "perf.engine.sample_token"} <= names
    assert {"serving.decode." + n for n in (
        "admit", "prepare", "step", "build", "device_call", "answer",
        "sample")} <= names

    def inside(inner, outer):
        spans = [(s, e) for n, _l, s, e in events if n == outer]
        # an outer span the session's start or stop cut is not recorded:
        # judge the inner ones between the first and the last that were
        first, last = min(s for s, _e in spans), max(e for _s, e in spans)
        found = [(s, e) for n, _l, s, e in events if n == inner
                 and first <= s and e <= last]
        assert len(found) > 10
        return all(any(os_ <= s and e <= oe for os_, oe in spans)
                   for s, e in found)

    assert inside("perf.engine.device_call", "serving.decode.device_call")
    assert inside("perf.engine.sample_token", "serving.decode.sample")
    assert inside("serving.decode.step", "perf.engine.scheduler_step")
    line_of = {n: l for n, l, _s, _e in events}
    assert line_of["serving.decode.step"] == \
        line_of["perf.engine.scheduler_step"]       # the scheduler's thread


def test_training_rehearsal_reads_the_programs_own_histogram(bench):
    from paddle_tpu.observability import metrics

    (entry, desc), = [(m, d) for m, d in bench.per_layer(TRAIN)
                      if m["name"] == "exec_run_ms.train"]
    metrics.reset_metrics("executor.")
    assert bench.read_layer_metric(entry, desc, {}) is None
    facts = base._toy_training(bench)
    assert facts["histograms"] == {}        # the runner hands over none
    import jax

    from perf.run import result_line

    line = result_line(bench, TRAIN, facts, jax.devices()[:1], True)
    assert line["correct"] is True, base._bad(line)
    got = line["metrics"]
    assert got["exec_run_ms.train"]["unit"] == "ms"
    ours, theirs = (got["exec_run_ms.train"]["value"],
                    got["dispatch_ms.train"]["value"])
    # the program's span lies inside the benchmark's own around the same
    # call: every step of the window was observed, and set-up's few
    h = metrics.snapshot("executor.step_ms")["executor.step_ms"]
    assert h["count"] >= facts["attempted"]
    assert 0.0 < ours and h["min"] <= ours <= h["max"]
    assert theirs > 0.0
