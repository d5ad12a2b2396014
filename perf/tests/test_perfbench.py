"""CPU-only checks of the benchmark's own code, and a rehearsal of both
runners at a toy size.

    JAX_PLATFORMS=cpu python3 -m pytest perf/tests -q

Nothing here touches a TPU or describes a topology; no number from here is a
device metric. The controls and the planted faults have to come out as not
correct; the chip's readings of them are in PERF.md.
"""
import copy
import json
import os
import re
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from perf.lib import flops, stats, traffic  # noqa: E402
from perf.lib import trace as tracelib  # noqa: E402
from perf.lib.loader import Benchmark, BenchmarkError  # noqa: E402


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


# --- window statistics -----------------------------------------------------

def _requests(stall_at=None, stall=0.0):
    """Sixteen clients, each a request every 2 s: first token after 0.5 s,
    then four more 0.4 s apart. A stall holds back every token due after
    ``stall_at``, as a stuck scheduler does to all its slots at once."""
    out = []
    for client in range(16):
        for k in range(3):
            submit = client * 0.1 + 2.0 * k
            times = [submit + 0.5 + 0.4 * i for i in range(5)]
            if stall_at is not None:
                times = [t + stall if t >= stall_at else t for t in times]
            out.append({"submit": submit, "token_times": times,
                        "failed": False})
    return out


def test_a_stall_inside_the_window_moves_every_serving_metric():
    calm = stats.serving_window(_requests(), 0.0, 8.0, 8.0)
    stalled = stats.serving_window(_requests(3.05, 1.5), 0.0, 8.0, 8.0)
    assert calm["ttft_p95_ms"] == pytest.approx(500.0)
    assert calm["token_gap_p95_ms"] == pytest.approx(400.0)
    assert stalled["ttft_p95_ms"] > calm["ttft_p95_ms"] + 1000
    assert stalled["token_gap_p95_ms"] > calm["token_gap_p95_ms"] + 1000
    assert stalled["serve_tokens_per_s"] < calm["serve_tokens_per_s"]


def test_a_request_without_a_first_token_counts_as_the_worst():
    reqs = _requests()
    for _ in range(2):
        reqs.append({"submit": 9.0, "token_times": [], "failed": False})
        reqs.append({"submit": 9.5, "token_times": [9.6], "failed": True})
    got = stats.serving_window(reqs, 0.0, 10.0, 70.0)
    assert got["ttft_p95_ms"] == pytest.approx(60500.0)
    assert got["requests_started"] == len(reqs)


def test_a_stall_moves_train_step_ms():
    calm = stats.training_window(0.0, 10.0, 200)
    stalled = stats.training_window(0.0, 10.0, 140)
    assert stalled["train_step_ms"] > calm["train_step_ms"] * 1.4
    with pytest.raises(ValueError):
        stats.training_window(0.0, 1.0, 0)


def test_percentile_is_nearest_rank_upward():
    assert stats.percentile(range(1, 61), 95) == 57
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None


def test_the_schedule_lays_requests_on_the_step_clock():
    """Two clients whose tokens come in the same bursts, 100 ms apart."""
    reqs = [{"submit": 9.95, "token_times": [10.3, 10.4, 10.5], "done": 10.5,
             "failed": False, "client": 1,
             "spec": {"prompt": [0] * 7, "max_new": 3, "temperature": 1.0},
             "result": {"steps_to_first_token": 2}},
            {"submit": 9.90, "token_times": [10.101, 10.202, 10.301],
             "done": None, "failed": False, "client": 0,
             "spec": {"prompt": [0] * 5, "max_new": 9, "temperature": 0.0},
             "result": None}]
    got = stats.serving_schedule(reqs, 10.0)
    assert got["step_clock_ms"] == [101.0, 202.0, 300.0, 400.0, 500.0]
    rows = [dict(zip(got["columns"], r)) for r in got["rows"]]
    assert [r["client"] for r in rows] == [0, 1]       # by submission
    assert rows[0] == {
        "client": 0, "prompt": 5, "answer": 9, "greedy": 1,
        "submit_ms": -100.0, "first_ms": 101.0, "done_ms": -1,
        "submit_step": -1, "first_step": 0, "done_step": -1, "tokens": 3,
        "steps_to_first_token": -1}
    assert (rows[1]["first_step"], rows[1]["done_step"],
            rows[1]["done_ms"], rows[1]["steps_to_first_token"]) == (
        2, 4, 500.0, 2)


# --- traffic ---------------------------------------------------------------

# the mix of the deferred cell xglm17b_docqa (PERF.md, Open questions): the
# generator and the runner serve it from data alone, so it stays tested
DOCQA_MIX = {
    "kind": "closed_loop_sessions", "clients": 8, "sessions": 64,
    "requests_per_session": 3,
    "prefix_len": {"lo": 1024, "hi": 1536, "scale": "linear"},
    "suffix_len": {"lo": 16, "hi": 48, "scale": "linear"},
    "answer_len": 16, "temperature": 0.0, "ramp_tokens": 96,
    "ramp_max_s": 90.0, "first_token_wait_s": 60.0,
    "greedy_topk_first": 4096}


def _mix(bench, name):
    return (copy.deepcopy(DOCQA_MIX) if name == "docqa"
            else bench.cell(name)["traffic"])


def _lengths(clients):
    """Each client's sizes in the order it sends them."""
    return [[(len(r["prompt"]), r["max_new"], r["temperature"])
             for s in c for r in s] for c in clients]


@pytest.mark.parametrize("cell_name", ["xglm17b_chat", "docqa"])
def test_seed_never_changes_the_sizes_or_their_order(bench, cell_name):
    mix = _mix(bench, cell_name)
    a = traffic.closed_loop_sessions(mix, 256008, 7)
    b = traffic.closed_loop_sessions(mix, 256008, 7)
    c = traffic.closed_loop_sessions(mix, 256008, 2 ** 31 + 11)
    assert len(a) == mix["clients"]
    for x, y in zip(a, b):
        for sx, sy in zip(x, y):
            for rx, ry in zip(sx, sy):
                assert np.array_equal(rx["prompt"], ry["prompt"])
                assert rx["seed"] == ry["seed"]
    assert _lengths(a) == _lengths(c)
    # nor the pause before a request: a turnaround plus a step per client
    for i, (x, y) in enumerate(zip(a, c)):
        want = (mix.get("think_ms", 0.0)
                + i * mix.get("think_stagger_ms", 0.0)) / 1e3
        assert {r["think_s"] for cl in (x, y) for s_ in cl
                for r in s_} == {want}
    assert (a[3][0][0]["think_s"] > 0) == (cell_name == "xglm17b_chat")
    assert any(not np.array_equal(x[0][0]["prompt"][:8], y[0][0]["prompt"][:8])
               for x, y in zip(a, c))
    # every client's first few requests span the range
    firsts = [len(c_[0][0]["prompt"]) for c_ in a]
    assert max(firsts) > 2 * min(firsts) or mix.get("prefix_len")


def test_chat_mix_spans_its_ranges_and_mixes_greedy_requests(bench):
    mix = bench.cell("xglm17b_chat")["traffic"]
    reqs = [r for c in traffic.closed_loop_sessions(mix, 256008, 3)
            for s in c for r in s]
    prompts = [len(r["prompt"]) for r in reqs]
    answers = [r["max_new"] for r in reqs]
    assert (min(prompts), max(prompts)) == (32, 512)
    assert (min(answers), max(answers)) == (16, 256)
    greedy = [r for r in reqs if r["temperature"] == 0.0]
    assert len(greedy) * 4 == len(reqs)
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 2048


def test_docqa_sessions_share_exactly_the_document(bench):
    mix = _mix(bench, "docqa")
    for client in traffic.closed_loop_sessions(mix, 256008, 5):
        for session in client:
            assert len(session) == 3
            n = session[0]["prefix_len"]
            assert 1024 <= n <= 1536
            doc = session[0]["prompt"][:n]
            for r in session:
                assert np.array_equal(r["prompt"][:n], doc)
                assert 16 <= len(r["prompt"]) - n <= 48
                assert r["max_new"] == 16 and r["temperature"] == 0.0
            assert not np.array_equal(session[0]["prompt"][n:n + 16],
                                      session[1]["prompt"][n:n + 16])


# --- operations and bytes --------------------------------------------------

def test_resnet50_operations_from_layer_shapes(bench):
    cfg = bench.config("resnet50")
    fwd = flops.resnet_forward_flops(cfg)
    # 3.86 G multiply-adds with the stride on each block's first 1x1 (the
    # source's layout); the usual "4.1 G" puts it on the 3x3
    assert 2 * 3.8e9 < fwd < 2 * 4.1e9
    train = flops.resnet_train_flops(cfg)
    assert 2.9 * fwd < train < 3 * fwd
    # XLA's own count of the compiled bs128 step on a v5e: 2.877e12
    assert train * 128 == pytest.approx(2.877e12, rel=0.03)
    assert len(flops.resnet_layers(cfg)) == 54


def test_xglm_operations_from_its_shapes(bench):
    cfg = bench.config("xglm-1.7b")
    n = flops.decoder_param_count(cfg)
    assert n == cfg["sizes"]["parameters"] == 1732464640
    assert flops.kv_bytes_per_token(cfg) == cfg["sizes"]["kv_bytes_per_token"]
    body = n - cfg["vocab_size"] * cfg["d_model"]
    one = flops.decoder_token_flops(cfg, 1, False)
    assert one == pytest.approx(2 * body, rel=1e-3)
    with_head = flops.decoder_token_flops(cfg, 1, True)
    assert with_head - one == 2 * cfg["d_model"] * cfg["vocab_size"]
    span = flops.decoder_span_flops(cfg, 10, 14, 1)
    assert span == sum(flops.decoder_token_flops(cfg, p + 1, False)
                       for p in range(10, 14)) + with_head - one


def test_paged_attention_cost_counts_live_slots_only():
    ops, nbytes = flops.paged_attention_call_cost(
        [1, 0, 16], [100, 0, 48], heads=16, kv_heads=16, head_dim=128)
    keys = 100 + sum(range(33, 49))
    assert ops == 4 * keys * 16 * 128
    assert nbytes == 4 * (2 * (100 + 48) * 2048 + 2 * 17 * 2048)


# --- the loader ------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")
ONE_LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_benchmark_json_keeps_to_the_contract(bench):
    doc = bench.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    cells = {w["name"] for w in doc["workloads"]}
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in doc["per_layer"]:
        assert ONE_LINE.match(m["layer"]), m
        # whichever cell reports the metric reports what it moves
        reporting = [w for w in cells if m["name"] in
                     [e["name"] for e, _d in bench.per_layer(w)]]
        assert reporting, m
        for w in reporting:
            assert m["moves"] in bench.end_to_end(w), (m["name"], w)
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert NAME.match(w["config"])
        assert w["chips"] in (1, 4) and ONE_LINE.match(w["why"])
        assert len(bench.end_to_end(w["name"])) >= 2
        assert len(bench.per_layer(w["name"])) >= 1
    assert len({(w["config"], w["traffic"]) for w in doc["workloads"]}) == \
        len(doc["workloads"])
    used = {w["config"] for w in doc["workloads"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("perf/") and PATH.match(c["file"])
        assert ONE_LINE.match(c["why"]) and ONE_LINE.match(c["source"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    assert 1 <= len(doc["command"]) <= 32
    for word in doc["command"]:
        assert ONE_LINE.match(word) and not word.startswith("/")
        assert ".." not in word.split("/")
    for path in doc["paths"]:
        assert PATH.match(path) and len(path) <= 200
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(doc)) < 64 * 1024


def test_every_file_the_benchmark_names_exists(bench):
    bench.check_files()
    for w in bench.doc["workloads"]:
        cell = bench.cell(w["name"])
        assert cell["why"] == w["why"]
        assert bench.config(cell["config"])["runner"]


def test_unknown_names_are_errors(bench):
    with pytest.raises(BenchmarkError, match="unknown workload"):
        bench.cell("no_such_cell")
    with pytest.raises(BenchmarkError, match="unknown config"):
        bench.config("no_such_config")
    with pytest.raises(BenchmarkError, match="no peaks recorded"):
        bench.peaks("TPU v9 imaginary")
    with pytest.raises(BenchmarkError, match="unknown reader"):
        bench.read_layer_metric({"name": "x"}, {"reader": "guess"}, {})
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    """Only new files and new entries: nothing that is there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = {p: open(p, "rb").read() for p in
              (os.path.join(dp, f) for dp, _d, fs in os.walk(root)
               for f in fs)}
    perf = root / "perf"
    cfg = json.load(open(perf / "configs" / "xglm-1.7b.json"))
    cfg.update(name="toy-decoder", runner="toy_runner")
    (perf / "configs" / "toy-decoder.json").write_text(json.dumps(cfg))
    (perf / "references" / "toy-decoder.py").write_text("ANSWER = 42\n")
    (perf / "runners" / "toy_runner.py").write_text(
        "def run(ctx):\n    return {'seed': ctx['seed']}\n")
    cell = json.load(open(perf / "workloads" / "xglm17b_chat.json"))
    cell.update(name="toy_cell", config="toy-decoder")
    (perf / "workloads" / "toy_cell.json").write_text(json.dumps(cell))
    (perf / "layer_metrics" / "toy_metric.json").write_text(json.dumps(
        {"name": "toy_metric", "reader": "python"}))
    (perf / "layer_metrics" / "toy_metric.py").write_text(
        "def read(facts):\n    return facts.get('toy')\n")
    doc["configs"].append({"name": "toy-decoder", "source": "test",
                           "file": "perf/configs/toy-decoder.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "toy_cell", "config": "toy-decoder",
                             "traffic": "toy", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "toy_metric", "unit": "%",
                             "better": "higher", "source": "host_clock",
                             "layer": "toy", "moves": "setup_s",
                             "workloads": ["toy_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    b = Benchmark(str(root))
    b.check_files()
    got = b.cell("toy_cell")
    assert b.runner(b.config(got["config"])["runner"]).run(
        {"seed": 5}) == {"seed": 5}
    assert b.reference("toy-decoder").ANSWER == 42
    (entry, desc), = [(e, d) for e, d in b.per_layer("toy_cell")
                      if e["name"] == "toy_metric"]
    assert b.read_layer_metric(entry, desc, {"toy": 12.5}) == 12.5
    assert b.read_layer_metric(entry, desc, {}) is None
    assert "toy_metric" not in [e["name"] for e, _d in
                                b.per_layer("xglm17b_chat")]
    for p, data in before.items():
        assert open(p, "rb").read() == data


def test_run_py_refuses_to_run_without_a_tpu():
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         "resnet50_train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""
    assert "measures the TPU" in out.stderr


# --- a rehearsal of both runners on the CPU, at a toy size ------------------
# The runners are importable functions; perf/run.py's own main keeps
# refusing to run without a TPU. The toy limits below were read here, on the
# CPU, where the program computes in float32: they are no device numbers.

TOY_GAP_LIMIT = 1e-3        # the program reads 0.0 here, an altered token 1+
TOY_RANK_LIMIT = 1e-6       # mean square: the program reads under 1e-10,
#                             bfloat16 over 1e-5


def _toy_serving(bench, mix_name, seed=2 ** 31 + 5, runner=None, **ctx_more):
    import jax  # noqa: F401
    import paddle_tpu  # noqa: F401

    # wide enough that bfloat16 puts another token first now and then
    cfg = dict(bench.config("xglm-1.7b"), vocab_size=16384, d_model=128,
               num_layers=4, attention_heads=4, ffn_dim=512)
    # the CPU's float32 matmuls are exact: the toy states `highest`
    cfg["precision"] = dict(cfg["precision"], reference_matmul="highest")
    cell = bench.cell("xglm17b_chat")
    cell["traffic"] = _mix(bench, mix_name)
    cell["engine"] = {"slots": [2], "page_size": 4, "num_pages": 64,
                      "max_seq_len": 64}
    cell["check_requests"] = 200
    cell["expect_route"] = ["paged_reference"]
    mix = cell["traffic"]
    mix.update(clients=2, sessions=6000, ramp_tokens=8, ramp_max_s=30.0)
    if mix.get("think_ms"):
        # a toy step takes a millisecond or two, not a hundred
        mix.update(think_ms=1.0, think_stagger_ms=0.5)
    if mix.get("prefix_len"):
        mix.update(prefix_len={"lo": 16, "hi": 24, "scale": "linear"},
                   suffix_len={"lo": 2, "hi": 6, "scale": "linear"},
                   answer_len=4, sessions=2000)
        cell["limits"]["min_cached_requests_compared"] = 1
    else:
        mix.update(suffix_len={"lo": 4, "hi": 20, "scale": "log"},
                   answer_len={"lo": 2, "hi": 12, "scale": "log"})
    cell["limits"]["served_logit_gap"] = TOY_GAP_LIMIT
    cell["limits"]["first_rank_gap_mean_sq"] = TOY_RANK_LIMIT
    mix["greedy_topk_first"] = 512
    ctx = {"bench": bench, "cell": cell, "config": cfg, "seed": seed,
           "seconds": 1.0, "trace": False, "trace_dir": None,
           "t_start": time.perf_counter(), "devices": None, "peaks": None,
           "reference": bench.reference("xglm-1.7b")}
    ctx.update(ctx_more)
    return (runner or bench.runner("serve_decoder")).run(ctx)


def _bad(line):
    return {n: c for n, c in line["checks"].items() if not c["ok"]}


def _line(bench, cell_name, facts):
    import jax

    from perf.run import result_line

    return result_line(bench, cell_name, facts, jax.devices()[:1], False)


CHAT = "xglm17b_chat"


@pytest.mark.parametrize("mix_name", ["xglm17b_chat", "docqa"])
def test_serving_rehearsal_is_correct_and_its_control_is_not(bench,
                                                             mix_name):
    facts = _toy_serving(bench, mix_name, control="bfloat16")
    line = _line(bench, CHAT, facts)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, _bad(line)
    assert line["failed"] == 0 and line["attempted"] > 10
    assert set(line["metrics"]) == set(bench.end_to_end(CHAT))
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["platform"] == "cpu"
    assert line["checks"]["served_logit_gap"]["value"] <= TOY_GAP_LIMIT
    assert line["checks"]["tokens_compared"]["value"] >= 100
    # the control: the same tokens through the reference in bfloat16
    got = facts["readings"]
    # the control fails by the rank gaps; a greedy token it flips only
    # now and then, here as on the chip (PERF.md)
    assert got["first_rank_gap_mean_sq"] < TOY_RANK_LIMIT / 10, got
    assert got["control_first_rank_gap_mean_sq"] > 3 * TOY_RANK_LIMIT, got
    if mix_name == "docqa":
        assert line["checks"]["cached_requests_compared"]["ok"]
        assert facts["counters"]["serving.prefix.cached_tokens"] > 0
    json.dumps(line)


def test_a_token_altered_where_it_is_produced_is_not_correct(bench,
                                                             monkeypatch):
    from paddle_tpu.serving.decode import DecodeEngine

    from perf.limits import plant_altered_token

    # registers the real method for monkeypatch to put back
    monkeypatch.setattr(DecodeEngine, "_run_step_arrays",
                        DecodeEngine._run_step_arrays)
    plant_altered_token()
    line = _line(bench, "xglm17b_chat", _toy_serving(bench, "xglm17b_chat"))
    assert line["correct"] is False
    assert not line["checks"]["served_logit_gap"]["ok"]
    assert line["checks"]["requests_failed"]["ok"]


CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "breakdown", "checks"]


def _report(bench, facts, trace):
    """A run's printing, as perf/run.py's main does it: (standard error's
    lines, the result's line as standard output's last one gives it)."""
    import io

    import jax

    from perf.run import report

    out, err = io.StringIO(), io.StringIO()
    report(bench, CHAT, facts, jax.devices()[:1], trace, out, err)
    return (err.getvalue().splitlines(),
            json.loads(out.getvalue().splitlines()[-1]))


@pytest.mark.parametrize("gone", [None, "ENGINE_DEVICE_CALL", "ENGINE_STEP",
                                  "SAMPLER"])
def test_a_traced_run_goes_on_without_a_name_inside_the_program(
        bench, tmp_path, monkeypatch, gone):
    """A traced run wraps three names inside the program from outside. A
    PR that renames one must not make the run raise: the span is skipped
    and named on an earlier line, and a metric whose source went with it
    is left out of the line by its reader."""
    runner = bench.runner("serve_decoder")
    if gone:
        monkeypatch.setattr(runner, gone, "renamed_by_a_later_pr")
    facts = _toy_serving(bench, CHAT, runner=runner, trace=True,
                         trace_dir=str(tmp_path))
    err, line = _report(bench, facts, True)
    assert line["correct"] is True, _bad(line)
    assert [k for k in CONTRACT_KEYS if k in line] == list(line)
    not_placed = [t for t in err if t.startswith("# span not placed: ")]
    assert not_placed == (["# span not placed: renamed_by_a_later_pr"]
                          if gone else [])
    first_check = min(i for i, t in enumerate(err) if t.startswith("CHECK "))
    assert all(err.index(t) < first_check for t in not_placed)
    # the program's own histograms are read whatever was wrapped
    assert {"serve_step_ms", "sched_occupancy_pct",
            "sched_queue_wait_ms"} <= set(line["metrics"])
    # no chip here, so no device trace and no roofline in the line; hand
    # the kernel's reader the recorded v5e trace beside this run's log of
    # device calls, which is what the wrapper around the device call makes
    (entry, desc), = [(e, d) for e, d in bench.per_layer(CHAT)
                      if e["name"] == "paged_attn_roofline"]
    assert "paged_attn_roofline" not in line["metrics"]
    with_trace = dict(
        facts, peaks=bench.peaks("TPU v5 lite"),
        trace=tracelib.reduce_events(tracelib.load_recorded(RECORDED)))
    value = bench.read_layer_metric(entry, desc, with_trace)
    if gone == "ENGINE_DEVICE_CALL":
        assert facts["traced_calls"] == [] and value is None
    else:
        assert len(facts["traced_calls"]) > 0 and value > 0
    # what was wrapped is put back
    from paddle_tpu.serving import decode as decode_mod
    assert decode_mod.sample_token.__name__ == "sample_token"


def test_the_last_line_holds_the_contracts_keys_and_nothing_else(bench):
    facts = _toy_serving(bench, CHAT)
    # the training runner's reading of the allocator, which a CPU has not
    facts["memory"] = {"allocator_peak_bytes": 1, "step_temporaries_bytes": 2}
    err, line = _report(bench, facts, False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit", "ok"}
    heads = [t.split(":")[0] for t in err]
    first_check = min(i for i, h in enumerate(heads)
                      if h.startswith("CHECK "))
    for name in ("# window", "# reference_s", "# memory", "# schedule",
                 "# set-up, seconds by phase"):
        assert name in heads[:first_check], heads
    # the last lines of standard error: each number compared, its limit
    assert all(h.startswith("CHECK ") for h in heads[first_check:])
    assert len(heads) - first_check == len(line["checks"])
    window = json.loads(err[heads.index("# window")].split(": ", 1)[1])
    assert window["requests_started"] == line["attempted"]
    assert not set(window) & set(line["metrics"])
    sched = json.loads(err[heads.index("# schedule")].split(": ", 1)[1])
    assert len(sched["rows"]) >= line["attempted"]
    assert all(len(r) == len(sched["columns"]) for r in sched["rows"])


def _toy_training(bench, **ctx_more):
    import paddle_tpu  # noqa: F401

    cfg = dict(bench.config("resnet50"), image=[3, 32, 32], class_dim=10)
    cfg["model"] = dict(cfg["model"], kwargs={"class_dim": 10, "depth": 50})
    # float32 here: the CPU's bfloat16 is no reading of the chip's amp
    cfg["flags"] = {"amp": False, "matmul_precision": "highest"}
    cell = bench.cell("resnet50_train")
    cell["traffic"]["batch"] = 8
    cell["limits"] = dict(TOY_TRAIN_LIMITS)
    ctx = {"bench": bench, "cell": cell, "config": cfg, "seed": 2 ** 31 + 5,
           "seconds": 1.0, "trace": False, "trace_dir": None,
           "t_start": time.perf_counter(), "devices": None, "peaks": None,
           "reference": bench.reference("resnet50")}
    ctx.update(ctx_more)
    return bench.runner("train_fluid").run(ctx)


# float32 against float32 on the CPU, batch 8 at 32x32: the first step agrees
# to rounding, the third to a tenth (eight rows through batch norm at 1x1 is
# chaotic); the limits of the toy sit between those and what the faults read
TOY_TRAIN_LIMITS = {"loss_gap": 0.2, "grad_norm_gap": 0.01,
                    "param_change_gap": 0.2}


def test_training_rehearsal_is_correct_and_its_control_is_not(bench):
    facts = _toy_training(bench, control=3)
    line = _line(bench, "resnet50_train", facts)
    assert line["correct"] is True, _bad(line)
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["checks"]["grad_norm_gap"]["value"] < 1e-4
    control = facts["readings"]["control"]
    assert control["grad_norm_gap"] > TOY_TRAIN_LIMITS["grad_norm_gap"]
    half = facts["readings"]["fault_half_batch"]
    assert half["grad_norm_gap"] > TOY_TRAIN_LIMITS["grad_norm_gap"]


def _unchanged_state(real_step):
    """A step that returns its state unchanged: it runs, then every
    variable of the scope is put back."""
    import jax.numpy as jnp

    def step(obj):
        scope = obj["scope"]
        saved = {n: jnp.array(scope.find_var(n), copy=True)
                 for n in obj["names"] + obj["velocity"]}
        out = real_step(obj)
        for n, v in saved.items():
            scope.set_var(n, v)
        return out

    return step


def _half_batch(real_step):
    """Half of the batch left out, the mean taken over the rest."""
    def step(obj):
        half = obj["feed"]["img"].shape[0] // 2
        whole = obj["feed"]
        obj["feed"] = {k: v[:half] for k, v in whole.items()}
        try:
            return real_step(obj)
        finally:
            obj["feed"] = whole

    return step


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_a_broken_training_step_is_not_correct(bench, fault):
    runner = bench.runner("train_fluid")
    facts = _toy_training(bench, step=fault(runner.step))
    line = _line(bench, "resnet50_train", facts)
    assert line["correct"] is False, line["checks"]
    bad = [n for n, c in line["checks"].items() if not c["ok"]]
    if fault is _unchanged_state:
        # nothing moved: the change reads 1 against the reference's
        assert line["checks"]["param_change_gap"]["value"] == pytest.approx(
            1.0, abs=1e-3)
        assert "param_change_gap" in bad
    else:
        assert "grad_norm_gap" in bad


# --- the trace reduction -----------------------------------------------------

RECORDED = os.path.join(ROOT, "perf", "lib", "data", "small_trace.json")


def test_trace_reduction_on_the_recorded_trace():
    """150 ms of xglm17b_chat's first traced run on the v5e (PR 25): one
    scheduler step and the host-side sampling that follows it."""
    got = tracelib.reduce_events(tracelib.load_recorded(RECORDED))
    assert got["chips"] == 1
    assert got["busy_s"] == pytest.approx(0.075513928, rel=1e-6)
    assert got["window_s"] == pytest.approx(0.150106056, rel=1e-6)
    top = dict(got["device_ops"])
    assert got["device_ops"][0][0] == "custom-call tpu_custom_call"
    assert top["custom-call tpu_custom_call"] == pytest.approx(0.041192903,
                                                               rel=1e-6)
    assert (tracelib.kernel_seconds(got, "tpu_custom_call")
            == top["custom-call tpu_custom_call"])
    assert tracelib.kernel_seconds(got, "no_such_kernel") is None
    # the idle time is the host sampling tokens (ROADMAP S1)
    assert got["idle_gaps"][0][0] == "perf.engine.sample_token"
    idle = got["window_s"] - got["busy_s"]
    assert sum(t for _n, t in got["idle_gaps"]) == pytest.approx(idle,
                                                                 rel=1e-6)
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_trace_reduction_unions_overlaps_and_averages_chips():
    dev0, dev1, line = "/device:TPU:0", "/device:TPU:1", "XLA Ops"
    events = [
        (dev0, line, "a", 0, 100_000), (dev0, line, "b", 50_000, 100_000),
        (dev0, line, "a", 400_000, 100_000),
        (dev1, line, "a", 0, 500_000),
        ("/host:CPU", "python3", "perf.host.wait", 140_000, 200_000),
        ("/host:CPU", "python3", "perf.host.inner", 200_000, 50_000),
        ("/host:CPU", "python3", "not_ours", 0, 500_000),
    ]
    got = tracelib.reduce_events(events)
    assert got["chips"] == 2 and got["window_s"] == pytest.approx(500e-6)
    # chip 0 is busy 150 + 100 us, chip 1 all 500 us
    assert got["busy_s"] == pytest.approx((250e-6 + 500e-6) / 2)
    assert dict(got["device_ops"])["a"] == pytest.approx(700e-6 / 2)
    gaps = dict(got["idle_gaps"])
    # chip 0's gap 150..400 us: the inner span claims its 50 us first
    assert gaps["perf.host.inner"] == pytest.approx(50e-6 / 2)
    assert gaps["perf.host.wait"] == pytest.approx(140e-6 / 2)
    assert gaps["unattributed"] == pytest.approx(60e-6 / 2)
    assert tracelib.reduce_events([events[4]]) is None
    clipped = tracelib.reduce_events(events, 100_000, 450_000)
    assert clipped["window_s"] == pytest.approx(350e-6)
    assert clipped["busy_s"] == pytest.approx((100e-6 + 350e-6) / 2)


def test_device_operations_get_short_names():
    kernel = ('%_step.43 = f32[16,16,16,128]{3,2,1,0:T(8,128)S(1)} '
              'custom-call(s32[16,32]{1,0:T(8,128)S(1)} %copy-done.2), '
              'custom_call_target="tpu_custom_call", operand_layout')
    assert tracelib.short_name(kernel) == "custom-call tpu_custom_call"
    fusion = ('%fusion.1641 = f32[16,256008]{1,0:T(8,128)} fusion('
              'f32[256008,2048]{1,0:T(8,128)} %params__tok_emb__.1), '
              'kind=kOutput, calls=%fused_computation.1289')
    assert tracelib.short_name(fusion) == "fusion kOutput f32[16,256008]"
    pair = ('%fusion.7 = (f32[256]{0:T(256)}, f32[256]{0:T(256)}) fusion('
            'bf16[128,256,56,56]{3,2,1,0} %x), kind=kInput, calls=%fc.7')
    assert tracelib.short_name(pair) == "fusion kInput (f32[256],..)"
    assert tracelib.short_name("jit__step(115)") == "jit__step(115)"
