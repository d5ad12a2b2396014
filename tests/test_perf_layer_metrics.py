"""The benchmark's per-layer metrics that later PRs added as files of
their own under `perf/layer_metrics/`: `sched_device_choice_pct` (ISSUE 29:
where tokens are chosen, data only, read from the program's histogram
`serving.decode.device_choice_pct`) and the five of the `sdar30b_blockgen`
cell (ISSUE 30): `block_tokens_per_pass`, `moe_load_max_over_mean`,
`moe_experts_roofline`, `moe_route_share_pct`, `paged_attn_block_roofline`;
and `paged_attn_grid_live_pct` (ISSUE 31: data only, the program's histogram
`serving.decode.attn_grid_live_pct`) and `paged_attn_dot_fold_pct` (ISSUE 35:
data only, `serving.decode.attn_dot_fold_pct`).

The benchmark's own tests live in `perf/tests` and are not collected by
the tier-1 command; this case is, so that a tree whose BENCHMARK.json no
longer loads with the metric fails here. ISSUE 34's two cells
(`xglm17b_docqa`, `trinity_mini_longmix`), its configuration, its four
metrics and its operations file have a section; the last holds the three
that read the device call's host stretches in every serving cell.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.lib.loader import Benchmark  # noqa: E402

NAME, CELL = "sched_device_choice_pct", "xglm17b_chat"
# the cell of the kimi-linear configuration, appended to the serving lists
# whose readers hold for it
KIMI = "kimi_linear_longgen"
HISTOGRAM = "serving.decode.device_choice_pct"
# the data-only metrics read from a histogram's mean in both serving cells:
# name -> (histogram, layer)
HISTOGRAM_AVG = {
    NAME: (HISTOGRAM, "scheduler"),
    "paged_attn_grid_live_pct": ("serving.decode.attn_grid_live_pct",
                                 "kernels"),
    "paged_attn_dot_fold_pct": ("serving.decode.attn_dot_fold_pct",
                                "kernels")}


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


@pytest.mark.parametrize("name", sorted(HISTOGRAM_AVG))
def test_a_histogram_metric_loads_and_reads_the_histograms_avg(bench, name):
    histogram, layer = HISTOGRAM_AVG[name]
    bench.check_files()
    entry, = [m for m in bench.doc["per_layer"] if m["name"] == name]
    # ISSUE 34 appended its two cells to the list, and its four metrics
    # behind the grid's live share; the dot fold's share behind those, and
    # the three metrics of the device call's stretches behind it. The
    # kimi cell is appended where the program's counter holds for its
    # latent kernel (not the dot fold's: that kernel folds every lane by
    # products, whatever the counter's predicate says)
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": layer,
        "moves": "serve_tokens_per_s",
        "workloads": [CELL, "sdar30b_blockgen", "xglm17b_docqa",
                      "trinity_mini_longmix"]
        + ([] if name == "paged_attn_dot_fold_pct" else [KIMI])}
    assert [m["name"] for m in bench.doc["per_layer"]].index(
        "paged_attn_grid_live_pct") == 18
    assert [m["name"] for m in bench.doc["per_layer"]][19:24] == [
        "kv_prefix_hit_pct", "paged_attn_window_roofline",
        "attn_window_skip_pct", "kv_window_held_pct",
        "paged_attn_dot_fold_pct"]
    # data only: no reader of its own, and the training cell is not asked
    assert not os.path.exists(bench.path("layer_metrics", name + ".py"))
    assert name not in [m["name"] for m, _d in
                        bench.per_layer("resnet50_train")]
    for cell in entry["workloads"]:
        assert entry["moves"] in bench.end_to_end(cell)
        (found, desc), = [(m, d) for m, d in bench.per_layer(cell)
                          if m["name"] == name]
        assert found is entry
        assert (desc["reader"], desc["histogram"], desc["stat"]) == (
            "histogram", histogram, "avg")
        assert (desc["name"], desc["unit"], desc["layer"], desc["source"],
                desc["moves"]) == (name, "%", layer, "program_counter",
                                   "serve_tokens_per_s")
    facts = {"histograms": {histogram: {
        "count": 424, "sum": 42188.0, "avg": 99.5, "min": 93.75,
        "max": 100.0, "p50": 100.0}}}
    assert bench.read_layer_metric(entry, desc, facts) == 99.5
    # the parent's case: a program without the histogram, or one whose
    # window observed nothing, gives nothing to read and does not raise
    assert bench.read_layer_metric(entry, desc, {"histograms": {}}) is None
    assert bench.read_layer_metric(
        entry, desc, {"histograms": {histogram: {"count": 0}}}) is None


def test_the_program_registers_what_the_metric_reads():
    """The histogram and the two counters exist under the names the
    benchmark's data file and the run's `# counters:` line use."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import decode  # noqa: F401  (registers them)

    snap = metrics.snapshot("serving.decode.")
    for histogram, _layer in HISTOGRAM_AVG.values():
        assert isinstance(snap[histogram], dict)
    for name in ("device_choices", "host_choices"):
        assert snap["serving.decode." + name] == 0


# --- the sdar30b_blockgen cell's metrics (ISSUE 30) ------------------------

NEW_CELL = "sdar30b_blockgen"
NEW = {"block_tokens_per_pass": ("tokens", "higher", "program_counter",
                                 "scheduler", "serve_tokens_per_s"),
       "moe_load_max_over_mean": ("ratio", "lower", "program_counter",
                                  "model step", "token_gap_p95_ms"),
       "moe_experts_roofline": ("%", "higher", "device_trace", "kernels",
                                "serve_tokens_per_s"),
       "moe_route_share_pct": ("%", "lower", "device_trace", "model step",
                               "token_gap_p95_ms"),
       "paged_attn_block_roofline": ("%", "higher", "device_trace", "kernels",
                                     "serve_tokens_per_s")}


def test_the_new_cell_and_configuration_are_appended_and_their_files_exist(
        bench):
    bench.check_files()
    assert [w["name"] for w in bench.doc["workloads"]][:2] == [
        "xglm17b_chat", "resnet50_train"]
    assert bench.doc["workloads"][2] == {
        "name": NEW_CELL, "config": "sdar-30b-a3b-chat",
        "traffic": "blockgen", "chips": 1,
        "why": bench.cell(NEW_CELL)["why"]}
    assert all(w["chips"] == 1 for w in bench.doc["workloads"])
    entry = bench.doc["configs"][2]
    assert (entry["name"], entry["reduced"]) == ("sdar-30b-a3b-chat",
                                                 ["num_hidden_layers"])
    cfg = bench.config("sdar-30b-a3b-chat")
    # every published width unchanged; depth the one cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"]) == (2048, 32, 4, 128, 768, 128, 8, 151936,
                                     1000000, 1e-6)
    assert (cfg["num_hidden_layers"],
            cfg["num_hidden_layers_published"]) == (6, 48)
    assert cfg["assumed"]["block_length"] == 4
    assert 0 <= cfg["assumed"]["mask_token_id"] < cfg["vocab_size"]
    # ttft_p95_ms is not resolved at the issue's traffic (PERF.md section
    # 6): the cell is not held to it, nor to the metric that moves it
    assert set(bench.end_to_end(NEW_CELL)) == {
        "serve_tokens_per_s", "token_gap_p95_ms", "setup_s"}
    names = [m["name"] for m, _d in bench.per_layer(NEW_CELL)]
    assert "paged_attn_roofline" not in names       # PERF.md section 7
    assert "sched_queue_wait_ms" not in names
    assert (bench.cell(NEW_CELL)["traffic"]["think_ms"],
            bench.cell(NEW_CELL)["traffic"]["think_stagger_ms"]) == (10, 1)
    assert set(NEW) <= set(names) and "serve_mfu_pct" in names
    # nothing of the new cell is asked of the cells that were there
    for cell in ("xglm17b_chat", "resnet50_train"):
        assert not set(NEW) & {m["name"] for m, _d in bench.per_layer(cell)}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_loads_and_reads_what_its_file_says(bench, name):
    unit, better, source, layer, moves = NEW[name]
    entry, = [m for m in bench.doc["per_layer"] if m["name"] == name]
    # ISSUE 34's cell has experts too and is appended to their lists; the
    # block cell's own two stay its own
    cells = ([NEW_CELL] if name in ("block_tokens_per_pass",
                                    "paged_attn_block_roofline")
             else [NEW_CELL, "trinity_mini_longmix"])
    # and the kimi cell to the two that read nothing of the experts' sizes
    if name in ("moe_load_max_over_mean", "moe_route_share_pct"):
        cells.append(KIMI)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": cells}
    assert bench.doc["per_layer"].index(entry) >= 13    # appended
    (_m, desc), = [(m, d) for m, d in bench.per_layer(NEW_CELL)
                   if m["name"] == name]
    assert (desc["name"], desc["unit"], desc["layer"], desc["moves"]) == (
        name, unit, layer, moves)
    cfg = bench.config("sdar-30b-a3b-chat")
    peaks = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    # a program without the histogram, the scopes or the spans' args (the
    # parent): nothing to read, nothing raised
    assert bench.read_layer_metric(
        entry, desc, {"histograms": {}, "config": cfg, "peaks": peaks,
                      "trace": None}) is None
    if desc["reader"] == "histogram":
        assert desc["histogram"] in (
            "serving.decode.block.tokens_per_pass",
            "serving.decode.moe.load_max_over_mean")
        facts = {"histograms": {desc["histogram"]: {
            "count": 10, "avg": 1.25, "p50": 1.5, "sum": 12.5}}}
        assert bench.read_layer_metric(entry, desc, facts) == (
            1.25 if desc["stat"] == "avg" else 1.5)
        return
    # one traced step of 64 lanes: 64 x 8 x 6 assignments on 700 of the
    # 768 expert matrices, 12 ms in the grouped products, 3 ms routing
    # and 14 ms in the paged kernel: 16 passes of 4 lanes at 400 keys each
    found = {"experts_s": 0.012, "route_s": 0.003, "attn_s": 0.014,
             "device_s": 0.030,
             "calls": [{"moe_assignments": 3072, "moe_experts_touched": 700,
                        "q_tokens": 64, "kv_tokens": 6400,
                        "attn_pairs": 25600},
                       {"moe_assignments": 1024,
                        "moe_experts_touched": None, "q_tokens": None,
                        "kv_tokens": None, "attn_pairs": None}]}
    value = bench.read_layer_metric(entry, desc, {
        "moe_trace": found, "config": cfg, "peaks": peaks})
    if name == "moe_route_share_pct":
        assert value == pytest.approx(10.0)
    elif name == "paged_attn_block_roofline":
        # bytes bound: the bfloat16 K and V of 6400 keys on 4 heads of 128
        # and q and out of 64 lanes on 32, six layers, over the 14 ms
        nbytes = (2 * 4 * 128 * 6400 + 2 * 32 * 128 * 64) * 2
        assert value == pytest.approx(100 * 6 * nbytes / 8.19e11 / 0.014)
        assert 0 < value < 100
    else:
        # bytes bound: 700 x 3 x 2048 x 768 x 2 B of weights + tokens in
        # and out, at 819 GB/s, over the 12 ms the products took
        nbytes = (700 * 3 * 2048 * 768 + 3072 * 2 * 2048) * 2
        assert value == pytest.approx(100 * nbytes / 8.19e11 / 0.012)
        assert 0 < value < 100


def test_operations_and_bytes_of_the_expert_model():
    from perf.lib import flops_moe

    cfg = Benchmark(ROOT).config("sdar-30b-a3b-chat")
    assert flops_moe.param_count(cfg) == cfg["sizes"]["parameters"]
    assert flops_moe.kv_bytes_per_token(cfg) == 12288
    m = flops_moe.dims(cfg)
    # active parameters only: 8 experts a token, the head only if asked
    lane = flops_moe.lane_flops(cfg, 0, False)
    assert lane == 6 * 2 * (2048 * 4096 * 2 + 2048 * 512 * 2 + 2048 * 128
                            + 8 * 3 * 2048 * 768)
    assert flops_moe.lane_flops(cfg, 0, True) - lane == 2 * 2048 * 151936
    assert flops_moe.lane_flops(cfg, 10, False) - lane == (
        6 * 4 * 10 * m["heads"] * m["head_dim"])
    assert flops_moe.block_flops(cfg, 8, 4, 2) == 3 * 4 * (
        flops_moe.lane_flops(cfg, 12, True))
    assert flops_moe.prefill_flops(cfg, 0, 8, 4) == 4 * (
        flops_moe.lane_flops(cfg, 4, False)
        + flops_moe.lane_flops(cfg, 8, False))
    ops, nbytes = flops_moe.experts_call_cost(cfg, 512, 126)
    assert ops == 512 * 2 * 3 * 2048 * 768
    assert nbytes == (126 * 3 * 2048 * 768 + 512 * 2 * 2048) * 2
    # one layer's attention: the pool's and the activations' stated widths
    ops, nbytes = flops_moe.attention_call_cost(cfg, 64, 6400, 25600)
    assert ops == 4 * 32 * 128 * 25600
    assert nbytes == (2 * 4 * 128 * 6400 + 2 * 32 * 128 * 64) * 2


@pytest.mark.parametrize("block,q,kv", [(1, 5, 9), (4, 4, 12), (4, 16, 48)])
def test_a_device_calls_pairs_follow_the_models_mask(block, q, kv):
    """`attn_pairs` of `serving.decode.device_call` counted lane by lane: a
    lane at position i sees the keys under its block's end."""
    import numpy as np

    from paddle_tpu.serving.decode import _call_work

    work = _call_work(2, q, 8, np.array([q, 0]), np.array([kv, 0]),
                      block=block)
    lanes = range(kv - q, kv)
    assert work["attn_pairs"] == sum(
        min(kv, (i // block + 1) * block) for i in lanes)
    assert (work["q_tokens"], work["kv_tokens"]) == (q, kv)
    # the compiled buckets beside the sums, and nothing else: what the
    # grid walks empty is the histogram serving.decode.attn_grid_live_pct's
    assert work == {"slots": 2, "chunk": q, "width": 8, "q_tokens": q,
                    "kv_tokens": kv, "attn_pairs": work["attn_pairs"]}


def test_the_trace_reduction_tells_the_paged_kernel_from_the_grouped_products(
        bench, tmp_path, monkeypatch):
    """Both are Mosaic calls with the target tpu_custom_call: the paged
    kernel is the instruction the program named."""
    from perf.lib import trace as tracelib
    from perf.lib import xplane
    from perf.lib.loader import load_module

    runner = load_module(os.path.join(ROOT, "perf", "runners",
                                      "serve_model.py"), "serve_model")
    call = ('custom-call(%x), custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(_block_step)/decoder.')

    def event(name, us, **stats):
        return {"name": name, "start_ns": 0, "dur_ns": us * 1000,
                "stats": stats}

    planes = [
        {"name": tracelib.DEVICE_PLANE_PREFIX + "0", "lines": [
            {"name": tracelib.OP_LINE, "events": [
                event("%paged_attention.7 = bf16[16,4,32,128]{3,2,1,0} "
                      + call + 'attn/paged_attention"}', 29,
                      tf_op="jit(_block_step)/decoder.attn/paged_attention"),
                event("%ragged-dot-none.3 = bf16[2048,768]{1,0} " + call
                      + 'moe.experts/ragged_dot"}', 11),
                event("%fusion.9 = f32[64,128]{1,0} fusion(%a), kind=kLoop",
                      2, tf_op="jit(_block_step)/decoder.moe.route/top_k"),
                event("%fusion.4 = bf16[1024,16,4,128]{3,2,1,0} fusion(%p), "
                      "kind=kLoop", 5,
                      tf_op="jit(_block_step)/decoder.attn/squeeze")]},
            {"name": "Steps", "events": [event("step", 99)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            event("serving.decode.device_call", 60, slots=16, q_tokens=64,
                  kv_tokens=6400, attn_pairs=25600, moe_assignments=3072),
            event("serving.decode.answer", 1)]}]}]
    monkeypatch.setattr(xplane, "newest", lambda d: d)
    monkeypatch.setattr(xplane, "read", lambda path: planes)
    found = runner.moe_trace(str(tmp_path), bench)
    assert found["attn_s"] == pytest.approx(29e-6)
    assert found["experts_s"] == pytest.approx(11e-6)
    assert found["route_s"] == pytest.approx(2e-6)
    assert found["device_s"] == pytest.approx(47e-6)
    assert found["custom_calls_s"] == {
        "%paged_attention": pytest.approx(29e-6),
        "%ragged-dot-none": pytest.approx(11e-6)}
    assert found["calls"] == [dict.fromkeys(runner.CALL_ARGS) | {
        "slots": 16, "q_tokens": 64, "kv_tokens": 6400, "attn_pairs": 25600,
        "moe_assignments": 3072}]


def test_the_program_registers_what_the_new_metrics_read():
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import decode  # noqa: F401  (registers them)

    snap = metrics.snapshot("serving.decode.")
    for name in ("serving.decode.block.tokens_per_pass",
                 "serving.decode.moe.load_max_over_mean"):
        assert isinstance(snap[name], dict)
    for name in ("serving.decode.block.passes",
                 "serving.decode.block.tokens_committed",
                 "serving.decode.block.tokens_dropped",
                 "serving.decode.moe.assignments"):
        assert name in snap


def test_the_xplane_reader_keeps_an_annotations_args(tmp_path):
    """perf/lib/xplane.py on a trace made here: the spans' keyword
    arguments come back as the event's stats."""
    import jax
    import jax.numpy as jnp

    from perf.lib import xplane

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("serving.decode.device_call", slots=16,
                                      moe_assignments=3072, kind="pass"):
        jax.jit(lambda a: a @ a)(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    events = [e for p in xplane.read(xplane.newest(str(tmp_path)))
              for l in p["lines"] for e in l["events"]
              if e["name"] == "serving.decode.device_call"]
    assert len(events) == 1 and events[0]["dur_ns"] > 0
    assert events[0]["stats"] == {"slots": 16, "moe_assignments": 3072,
                                  "kind": "pass"}


# --- ISSUE 34: xglm17b_docqa, trinity-mini / trinity_mini_longmix ----------

LONGMIX, DOCQA = "trinity_mini_longmix", "xglm17b_docqa"
PR34 = {"kv_prefix_hit_pct": ("higher", "program_counter", "KV manager",
                              DOCQA),
        "paged_attn_window_roofline": ("higher", "device_trace", "kernels",
                                       LONGMIX),
        "attn_window_skip_pct": ("higher", "program_counter", "kernels",
                                 LONGMIX),
        "kv_window_held_pct": ("lower", "program_counter", "KV manager",
                               LONGMIX)}
PEAKS = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def test_the_two_cells_and_the_configuration_are_appended(bench):
    bench.check_files()
    assert [w["name"] for w in bench.doc["workloads"]] == [
        "xglm17b_chat", "resnet50_train", "sdar30b_blockgen", DOCQA,
        LONGMIX, KIMI]
    assert all(w["chips"] == 1 for w in bench.doc["workloads"])
    entry = bench.doc["configs"][3]
    assert (entry["name"], entry["reduced"]) == (
        "trinity-mini", ["num_hidden_layers", "num_dense_layers",
                         "layer_types"])
    cfg = bench.config("trinity-mini")
    # every published width unchanged; depth the one cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["vocab_size"],
            cfg["sliding_window"], cfg["route_scale"]) == (
        2048, 32, 4, 128, 6144, 1024, 128, 8, 1, 200192, 2048, 2.826)
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"],
            cfg["num_dense_layers"], cfg["num_dense_layers_published"]) == (
        5, 32, 1, 2)
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    assert set(bench.end_to_end(LONGMIX)) == {
        "serve_tokens_per_s", "token_gap_p95_ms", "setup_s"}
    cell = bench.cell(LONGMIX)
    assert cell["engine"] == {
        "slots": [16], "page_size": 16, "num_pages": 9472,
        "num_window_pages": 2304, "max_seq_len": 9216, "prefill_chunk": 64}
    names = [m["name"] for m, _d in bench.per_layer(LONGMIX)]
    assert {"serve_mfu_pct", "moe_experts_roofline", "moe_route_share_pct",
            "moe_load_max_over_mean", "paged_attn_grid_live_pct",
            "device_idle_pct.serve"} <= set(names)
    assert "paged_attn_block_roofline" not in names
    assert "paged_attn_roofline" not in names
    docqa = bench.cell(DOCQA)
    assert docqa["engine"] == {"slots": [2], "page_size": 16,
                               "num_pages": 1024, "max_seq_len": 2048}
    assert (docqa["traffic"]["clients"],
            docqa["traffic"]["requests_per_session"]) == (2, 3)
    assert "kv_prefix_hit_pct" in [m["name"] for m, _d in
                                   bench.per_layer(DOCQA)]
    # nothing of the new cells is asked of the cells that were there
    for old in ("xglm17b_chat", "resnet50_train", "sdar30b_blockgen"):
        assert not set(PR34) & {m["name"] for m, _d in bench.per_layer(old)}


@pytest.mark.parametrize("name", sorted(PR34))
def test_a_metric_of_issue_34_loads_and_reads_what_its_file_says(bench,
                                                                 name):
    better, source, layer, cell = PR34[name]
    entry, = [m for m in bench.doc["per_layer"] if m["name"] == name]
    assert {k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "workloads")} == {
        "unit": "%", "better": better, "source": source, "layer": layer,
        "workloads": [cell]}
    assert bench.doc["per_layer"].index(entry) >= 19        # appended
    assert entry["moves"] in bench.end_to_end(cell)
    (_m, desc), = [(m, d) for m, d in bench.per_layer(cell)
                   if m["name"] == name]
    assert (desc["name"], desc["unit"], desc["layer"], desc["moves"]) == (
        name, "%", layer, entry["moves"])
    cfg = bench.config("trinity-mini")
    # the parent's case: no histogram, counter, scope or span argument
    assert bench.read_layer_metric(entry, desc, {
        "histograms": {}, "counters": {}, "config": cfg, "peaks": PEAKS,
        "trace": None, "moe_trace": {"attn_s": 0.01, "calls": [
            {"q_tokens": 16, "kv_tokens": 100, "attn_pairs": 100}]}}) is None
    if desc["reader"] == "histogram":
        assert desc["histogram"] in ("serving.decode.attn_window_skip_pct",
                                     "serving.kv.window.held_pct")
        facts = {"histograms": {desc["histogram"]: {
            "count": 10, "avg": 41.5, "p50": 40.0, "sum": 415.0}}}
        assert bench.read_layer_metric(entry, desc, facts) == 41.5
    elif name == "kv_prefix_hit_pct":
        facts = {"counters": {"serving.prefix.cached_tokens": 30000},
                 "prompt_tokens_submitted": 48000}
        assert bench.read_layer_metric(entry, desc, facts) == 62.5
    else:
        # a made-up trace whose time IS the roofline's own: one decode
        # step of 16 slots at 4000 keys each, bytes bound in both kinds
        from perf.lib import flops_afmoe

        call = {"q_tokens": 16, "kv_tokens_full": 64000,
                "attn_pairs_full": 64000, "kv_tokens_window": 32768,
                "attn_pairs_window": 32768}
        full = (2 * 4 * 128 * 64000 + 2 * 32 * 128 * 16) * 2 / 8.19e11
        window = (2 * 4 * 128 * 32768 + 2 * 32 * 128 * 16) * 2 / 8.19e11
        least = flops_afmoe.attention_step_least_s(cfg, call, PEAKS)
        assert least == pytest.approx(full + 4 * window)
        read = lambda seconds: bench.read_layer_metric(entry, desc, {
            "moe_trace": {"attn_s": seconds, "calls": [call, {}]},
            "config": cfg, "peaks": PEAKS})
        assert read(least) == pytest.approx(100.0)
        assert 0 < read(3 * least) < 100
        # one layer's sums times num_hidden_layers would read 5x the full
        # kind's: over 100 at the roofline's own time
        assert 5 * full > least


def test_operations_and_bytes_of_the_window_model():
    from paddle_tpu.models.afmoe import TINY_CONFIG, AfmoeSpec
    from perf.lib import flops_afmoe as fa

    cfg = Benchmark(ROOT).config("trinity-mini")
    assert fa.param_count(cfg) == cfg["sizes"]["parameters"] == sum(
        int(__import__("numpy").prod(s))
        for s in AfmoeSpec.from_config(cfg).tensors().values())
    assert fa.kv_bytes_per_token(cfg) == (2048, 4 * 2048)
    # the tiny preset by hand: d 64, 4/2 heads of 16, dense 96, experts 32,
    # 8 experts top-2 + 1 shared, 1 dense + 4 expert layers, window 8
    tiny = dict(TINY_CONFIG, precision=cfg["precision"])
    spec = AfmoeSpec.from_config(TINY_CONFIG)
    assert fa.param_count(tiny) == sum(
        int(__import__("numpy").prod(s)) for s in spec.tensors().values())
    proj = 2 * (64 * 64 * 3 + 64 * 32 * 2)
    lane = 5 * proj + 2 * 3 * 64 * 96 + 4 * (
        2 * 64 * 8 + (2 + 1) * 2 * 3 * 64 * 32)
    assert fa.lane_matmul_flops(tiny) == lane
    # positions 6..11: the full layer sees 7..12 keys, a window layer
    # 7, 8, 8, 8, 8, 8; one lane unembedded
    keys_full, keys_window = sum(range(7, 13)), 7 + 5 * 8
    assert fa.span_flops(tiny, 6, 12, 1) == (
        6 * lane + 4 * 4 * 16 * (keys_full + 4 * keys_window)
        + 2 * 64 * 128)
    assert fa.span_flops(tiny, 0, 3, 0) == 3 * lane + 4 * 4 * 16 * 5 * 6
    ops, nbytes = fa.attention_call_cost(cfg, 16, 32768, 32768)
    assert ops == 4 * 32 * 128 * 32768
    assert nbytes == (2 * 4 * 128 * 32768 + 2 * 32 * 128 * 16) * 2
    # the accepted experts' reader takes its cost from this file's keys
    from perf.lib import flops_moe

    ops, nbytes = flops_moe.experts_call_cost(cfg, 512, 300)
    assert ops == 512 * 2 * 3 * 2048 * 1024
    assert nbytes == (300 * 3 * 2048 * 1024 + 512 * 2 * 2048) * 2


def test_the_program_registers_what_issue_34s_metrics_read():
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import decode  # noqa: F401  (registers them)

    snap = metrics.snapshot("serving.")
    for name in ("serving.kv.window.held_pct",
                 "serving.decode.attn_window_skip_pct"):
        assert isinstance(snap[name], dict)
    for name in ("serving.kv.window.pages_released",
                 "serving.prefix.cached_tokens"):
        assert name in snap


# --- the device call's host stretches, in every serving cell --------------

SERVING = ["xglm17b_chat", "sdar30b_blockgen", DOCQA, LONGMIX, KIMI]
# name -> (unit, layer, histogram, statistic or None for a python reader)
PR39 = {"serve_dispatch_ms": ("ms", "model step",
                              "serving.decode.dispatch_ms", "p50"),
        "sched_release_ms": ("ms", "scheduler",
                             "serving.decode.release_ms", "p50"),
        "sched_host_gap_pct": ("%", "scheduler",
                               "serving.decode.host_gap_ms", None)}


@pytest.mark.parametrize("name", sorted(PR39))
def test_a_stretch_metric_loads_and_reads_what_its_file_says(bench, name):
    unit, layer, histogram, stat = PR39[name]
    bench.check_files()
    entry, = [m for m in bench.doc["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "serve_tokens_per_s", "workloads": SERVING}
    # appended, in this order, behind every metric that was there before
    # them (what later PRs append follows them)
    names = [m["name"] for m in bench.doc["per_layer"]]
    at = names.index("serve_dispatch_ms")
    assert names[at:at + 3] == [
        "serve_dispatch_ms", "sched_release_ms", "sched_host_gap_pct"]
    assert name not in [m["name"] for m, _d in
                        bench.per_layer("resnet50_train")]
    for cell in SERVING:
        assert entry["moves"] in bench.end_to_end(cell)
        (found, desc), = [(m, d) for m, d in bench.per_layer(cell)
                          if m["name"] == name]
        assert found is entry
        assert (desc["name"], desc["unit"], desc["layer"], desc["source"],
                desc["moves"]) == (name, unit, layer, "program_span",
                                   "serve_tokens_per_s")
    h = {"count": 171, "sum": 855.0, "avg": 5.0, "min": 0.0, "max": 9.0,
         "p50": 4.75}
    facts = {"histograms": {histogram: h}, "window_s": 51.0,
             "trace": None}
    if stat is None:
        assert desc["reader"] == "python"
        # 855 ms of host gap in a window of 51 s
        assert bench.read_layer_metric(entry, desc, facts) == \
            pytest.approx(100.0 * 855.0 / 51000.0)
    else:
        assert (desc["reader"], desc["histogram"], desc["stat"]) == (
            "histogram", histogram, stat)
        assert not os.path.exists(bench.path("layer_metrics", name + ".py"))
        assert bench.read_layer_metric(entry, desc, facts) == 4.75
    # the parent's case: a program without the histogram, or a window
    # that observed nothing, gives nothing to read and does not raise
    assert bench.read_layer_metric(entry, desc, {
        "histograms": {}, "window_s": 51.0}) is None
    assert bench.read_layer_metric(entry, desc, {
        "histograms": {histogram: dict(h, count=0)},
        "window_s": 51.0}) is None


def test_the_program_registers_what_the_stretch_metrics_read():
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import decode  # noqa: F401  (registers them)

    snap = metrics.snapshot("serving.decode.")
    for _unit, _layer, histogram, _stat in PR39.values():
        assert isinstance(snap[histogram], dict)


# --- the round that goes one step ahead, in every serving cell ------------

def test_the_ahead_share_loads_and_reads_the_programs_histogram(bench):
    """sched_ahead_pct: the mean of serving.decode.ahead_pct (100 a
    target dispatch that went ahead of the step before it, else 0), in
    the four serving cells, appended behind every metric that was there;
    a program without the histogram gives nothing to read."""
    bench.check_files()
    names = [m["name"] for m in bench.doc["per_layer"]]
    assert names.index("sched_ahead_pct") > names.index("sched_host_gap_pct")
    entry, = [m for m in bench.doc["per_layer"]
              if m["name"] == "sched_ahead_pct"]
    assert entry == {"name": "sched_ahead_pct", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "scheduler", "moves": "serve_tokens_per_s",
                     "workloads": SERVING}
    assert "sched_ahead_pct" not in [m["name"] for m, _d in
                                     bench.per_layer("resnet50_train")]
    for cell in SERVING:
        (found, desc), = [(m, d) for m, d in bench.per_layer(cell)
                          if m["name"] == "sched_ahead_pct"]
        assert found is entry
    assert (desc["reader"], desc["histogram"], desc["stat"]) == (
        "histogram", "serving.decode.ahead_pct", "avg")
    h = {"count": 200, "sum": 19600.0, "avg": 98.0, "min": 0.0,
         "max": 100.0, "p50": 100.0}
    assert bench.read_layer_metric(entry, desc, {
        "histograms": {"serving.decode.ahead_pct": h}}) == 98.0
    assert bench.read_layer_metric(entry, desc, {"histograms": {}}) is None
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import decode  # noqa: F401  (registers it)

    assert isinstance(metrics.snapshot("serving.")[
        "serving.decode.ahead_pct"], dict)
