"""Generation by diffusion over blocks through the decode engine (ISSUE 30).

The tiny ``sdar_moe`` preset served through ``DecodeEngine.submit`` /
``stream_tokens`` on the CPU, in float32 so that tokens can be compared one
for one with the plain reference's loop (``perf/references/
sdar-30b-a3b-chat.py``, the one reference the benchmark also uses); and the
benchmark's own comparison (``perf/runners/serve_model.py``) rehearsed on the
same model: correct as served, not correct one precision down, not correct
with one lane unmasked out of order.
"""
import os
import sys
import time

import jax
import numpy as np
import pytest

from paddle_tpu.models.decoders import DecoderSpec
from paddle_tpu.observability import metrics
from paddle_tpu.serving.decode import DecodeEngine
from paddle_tpu.serving.errors import ServingError
from test_moe_decoder import CFG, MASK_ID, ROOT, load_reference, tiny_spec

sys.path.insert(0, ROOT)

from perf.lib.loader import Benchmark, load_module  # noqa: E402


@pytest.fixture(scope="module")
def ref():
    return load_reference()


@pytest.fixture(scope="module")
def served():
    spec = tiny_spec(dtype="float32")
    params = jax.device_put(spec.seeded_arrays())
    eng = DecodeEngine(spec, name="blocks", slots=[4], page_size=4,
                       num_pages=128, max_seq_len=48, prefill_chunk=8,
                       params=params)
    yield eng, params
    eng.stop(drain=False)


def _prompt(n, seed):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 120, size=n)]


def test_the_engine_reads_the_block_length_off_the_model(served):
    eng, _ = served
    st = eng.stats()
    assert st["prefix_cache"] is False          # off for a block model
    assert st["chunk_ladder"] == [4, 8] and st["prefill_chunk"] == 8
    assert st["spec"]["family"] == "sdar_moe"
    assert st["spec"]["block_length"] == 4


@pytest.mark.parametrize("p_mod_4", [0, 1, 3])
@pytest.mark.parametrize("denoise_steps", [1, 2, 4])
def test_greedy_generation_equals_the_references_loop(served, ref, p_mod_4,
                                                      denoise_steps):
    eng, params = served
    prompt = _prompt(8 + p_mod_4, 10 * p_mod_4 + denoise_steps)
    got = eng.generate(prompt, max_new_tokens=10,
                       denoise_steps=denoise_steps, topk_first=6)
    want, passes = ref.generate(params, CFG, prompt, 10, denoise_steps)
    assert got["tokens"] == want and len(want) == 10
    # pass for pass what the reference's loop did: inputs, masks, choices
    assert len(got["passes"]) == len(passes)
    for mine, theirs in zip(got["passes"], passes):
        for key in ("pos", "input", "masked", "ids", "unmasked"):
            assert mine[key] == theirs[key], key
        np.testing.assert_allclose(mine["confidence"], theirs["confidence"],
                                   rtol=2e-4)
    # a block costs denoise_steps passes (+ the commit pass, which chooses
    # nothing and is not recorded); a first block with tokens left over
    # from the prompt needs fewer
    assert len(passes) <= 4 * denoise_steps
    first = passes[0]
    lane = first["masked"].index(True)
    assert lane == p_mod_4
    row = np.asarray(ref.logits_at(
        params, CFG, prompt[:8] + first["input"], range(8, 12)))[lane]
    assert got["first_topk"] == [int(t) for t in
                                 np.argsort(-row, kind="stable")[:6]]


def test_a_prompt_that_holds_the_mask_id_is_harmless(served, ref):
    eng, params = served
    prompt = [5, MASK_ID, 9, MASK_ID, MASK_ID, 2, 7, 1, MASK_ID]
    got = eng.generate(prompt, max_new_tokens=8, denoise_steps=2)
    assert got["tokens"] == ref.generate(params, CFG, prompt, 8, 2)[0]


def test_slots_in_different_phases_in_one_step_equal_requests_served_alone(
        served):
    """Four requests of different prompt lengths, denoise steps and
    sampling in flight at once: in any one step some slots prefill, some
    denoise and some commit. Each answers what it answers alone."""
    eng, _ = served
    cases = [(_prompt(23, 1), 12, 1, 0.0, 0), (_prompt(5, 2), 9, 2, 0.0, 0),
             (_prompt(14, 3), 16, 4, 0.8, 77), (_prompt(3, 4), 7, 2, 1.0, 5)]

    def submit(c):
        return eng.submit(c[0], max_new_tokens=c[1], denoise_steps=c[2],
                          temperature=c[3], seed=c[4])

    alone = []
    for c in cases:
        r = submit(c)
        assert r.ev.wait(120)
        alone.append(r.result["tokens"])
    steps0 = eng.stats()["steps"]
    reqs = [submit(c) for c in cases]
    for r in reqs:
        assert r.ev.wait(120) and r.error is None
    assert [r.result["tokens"] for r in reqs] == alone
    assert [len(t) for t in alone] == [12, 9, 16, 7]
    # they did share steps: fewer than the four runs would take in a row
    together = eng.stats()["steps"] - steps0
    assert together < sum(r.result["steps_to_first_token"] for r in reqs) + 40
    # a drawn request repeats for its seed and moves with it
    again = eng.generate(cases[2][0], 16, denoise_steps=4, temperature=0.8,
                         seed=77)["tokens"]
    other = eng.generate(cases[2][0], 16, denoise_steps=4, temperature=0.8,
                         seed=78)["tokens"]
    assert again == alone[2] and other != alone[2]


def test_blocks_stream_in_order_and_stop_at_max_new(served):
    eng, _ = served
    prompt = _prompt(9, 5)
    whole = eng.generate(prompt, max_new_tokens=14, denoise_steps=2)["tokens"]
    dropped0 = metrics.counter(
        "serving.decode.block.tokens_dropped").value()
    req = eng.submit(prompt, max_new_tokens=14, denoise_steps=2)
    offset, chunks = 0, []
    while True:
        out = eng.stream_tokens(req, offset, timeout=60.0)
        if out["tokens"]:
            chunks.append(out["tokens"])
        offset = out["next_offset"]
        if out["done"]:
            break
    assert [t for c in chunks for t in c] == whole == out["result"]["tokens"]
    assert len(whole) == 14
    # P mod 4 = 1: the first block answers 3 tokens, then 4 at a time,
    # and the last block's tokens past max_new are dropped; a reader may
    # find several blocks waiting, never a torn one
    sizes = np.cumsum([len(c) for c in chunks])
    assert set(sizes) <= {3, 7, 11, 14}
    assert metrics.counter(
        "serving.decode.block.tokens_dropped").value() - dropped0 == 1
    # stream_tokens is a pure function of (request, offset)
    assert eng.stream_tokens(req, 3)["tokens"] == whole[3:]


def test_generation_stops_at_eos_and_drops_the_rest_of_the_block(served):
    eng, _ = served
    prompt = _prompt(8, 6)
    whole = eng.generate(prompt, max_new_tokens=16, denoise_steps=4)["tokens"]
    eos = whole[5]
    try:
        eng.spec.eos_id = eos       # read at every commit
        got = eng.generate(prompt, max_new_tokens=16,
                           denoise_steps=4)["tokens"]
    finally:
        eng.spec.eos_id = None
    assert got == whole[:whole.index(eos) + 1]


@pytest.mark.parametrize("field,kw", [
    ("draft_spec", {"draft_spec": DecoderSpec(vocab=128), "spec_k": 2}),
    ("spec_k", {"spec_k": 2}),
    ("embeddings", {"embeddings": True}),
    ("prefix_cache", {"prefix_cache": True}),
])
def test_what_assumes_one_token_a_pass_refuses_a_block_model_by_name(field,
                                                                     kw):
    with pytest.raises(ValueError, match=f"'{field}' is for causal"):
        DecodeEngine(tiny_spec(), name="no", slots=[1], page_size=4,
                     num_pages=8, max_seq_len=16, warm=False, **kw)


def test_requests_a_block_model_cannot_serve_are_refused_by_field(served):
    eng, _ = served
    from paddle_tpu.serving.workloads import beam_search
    from paddle_tpu.serving.workloads.masks import TokenMaskSpec

    with pytest.raises(ValueError, match="'top_k'"):
        eng.submit([1, 2, 3], temperature=1.0, top_k=5)
    with pytest.raises(ValueError, match="'mask'"):
        eng.submit([1, 2, 3], mask=TokenMaskSpec.one_of([[1, 2]]))
    with pytest.raises(ValueError, match="denoise_steps must divide"):
        eng.submit([1, 2, 3], denoise_steps=3)
    with pytest.raises(ServingError, match="embeddings"):
        eng.submit_embed([1, 2, 3])
    with pytest.raises(ServingError, match="'block_length' 4"):
        beam_search(eng, [1, 2, 3], k=2)
    # whole blocks are reserved: 13 + 32 rounds up to 48 = max_seq_len
    assert eng.generate(_prompt(13, 0), 32, denoise_steps=1)["tokens"]


def test_counters_and_histograms_of_block_passes_and_experts(served):
    eng, _ = served
    metrics.reset_metrics("serving.decode.")
    out = eng.generate(_prompt(9, 9), max_new_tokens=11, denoise_steps=2)
    assert len(out["tokens"]) == 11
    snap = metrics.snapshot("serving.decode.")
    # blocks at 8 (1 token known), 12, 16: 3 blocks x (2 denoise + 1
    # commit); the last block answers 4 of its... 3 + 4 + 4 = 11, none lost
    assert snap["serving.decode.block.passes"] == 9
    assert snap["serving.decode.block.tokens_committed"] == 11
    assert snap["serving.decode.block.tokens_dropped"] == 0
    assert snap["serving.decode.tokens"] == 11
    assert snap["serving.decode.device_choices"] == 11
    # 8 prompt tokens prefilled + 9 passes of 4 lanes, 2 experts a token in
    # each of 2 layers
    assert snap["serving.decode.moe.assignments"] == (8 + 36) * 4
    per_pass = snap["serving.decode.block.tokens_per_pass"]
    assert per_pass["count"] == 9 and per_pass["sum"] == pytest.approx(11.0)
    load = snap["serving.decode.moe.load_max_over_mean"]
    assert load["count"] == 9 and 1.0 <= load["min"] <= load["max"] <= 8.0


# --- the benchmark's comparison, rehearsed on the tiny model -----------------

TINY_FILE = dict(
    CFG, name="sdar-tiny", runner="serve_model", head_dim=16,
    model={"module": "paddle_tpu.models.sdar_moe", "spec": "SdarMoeSpec"},
    precision={"weights": "float32"})
TINY_CELL = {
    "name": "tiny_blockgen", "config": "sdar-tiny", "chips": 1,
    "engine": {"slots": [4], "page_size": 4, "num_pages": 128,
               "max_seq_len": 48},
    "expect_route": ["paged_reference"],
    "traffic": {"kind": "closed_loop_sessions", "clients": 4,
                "sessions": 6000, "requests_per_session": 1,
                "prefix_len": None, "suffix_len": {"lo": 3, "hi": 14},
                "answer_len": {"lo": 6, "hi": 16}, "block_length": 4,
                "denoise_steps": 2, "temperature": 1.0, "greedy_every": 2,
                "greedy_topk_first": 16, "think_ms": 1.0,
                "think_stagger_ms": 0.5, "ramp_tokens": 40,
                "ramp_max_s": 60.0, "first_token_wait_s": 30.0},
    "trace_seconds": 0.5, "check_requests": 6,
    "check_passes_per_request": 4,
    # float32 served against float32 at highest: summation order only
    "limits": {"served_logit_gap": 1e-3, "unmask_confidence_gap": -0.01,
               "first_rank_gap_mean_sq": 1e-6, "min_tokens_compared": 20},
}


@pytest.fixture(scope="module")
def runner():
    return load_module(os.path.join(ROOT, "perf", "runners",
                                    "serve_model.py"), "serve_model")


def _rehearse(runner, ref, seed, control=None, trace=False, tmp=None):
    ctx = {"bench": Benchmark(ROOT), "cell": TINY_CELL, "config": TINY_FILE,
           "devices": [], "peaks": {"bf16_flops_per_s": 1.97e14,
                                    "hbm_bytes_per_s": 8.19e11},
           "reference": ref, "seed": seed, "seconds": 2.5, "trace": trace,
           "trace_dir": tmp, "t_start": time.perf_counter(),
           "control": control}
    return runner.run(ctx)


def test_runner_rehearsal_is_correct_and_the_control_is_not(runner, ref,
                                                            tmp_path):
    facts = _rehearse(runner, ref, 2 ** 31 + 12345, runner.CONTROLS,
                      trace=True, tmp=str(tmp_path))
    bad = [(n, v, l) for n, v, l, ok in facts["checks"] if not ok]
    assert not bad, bad
    got = facts["readings"]
    assert got["tokens_compared"] >= 20 and got["orders_compared"] > 0
    # in order on every compared pass: the mean gap lies well under 0
    assert got["unmask_confidence_gap_max"] <= 1e-3
    assert got["unmask_confidence_gap"] < -0.01
    assert facts["end_to_end"]["serve_tokens_per_s"] > 0
    assert facts["processed_flops"] > 0
    assert facts["histograms"]["serving.decode.block.tokens_per_pass"][
        "count"] > 0
    # a traced rehearsal reads the device calls' args off the trace (no
    # device plane on the CPU: nothing to time, nothing raised)
    calls = facts["moe_trace"]["calls"]
    assert calls and all(c["moe_assignments"] > 0 for c in calls)
    assert any(c["moe_experts_touched"] for c in calls)
    bench = Benchmark(ROOT)
    for entry, desc in bench.per_layer("sdar30b_blockgen"):
        if entry["name"].startswith("moe_") and desc["reader"] == "python":
            assert bench.read_layer_metric(entry, desc, dict(
                facts, config=bench.config("sdar-30b-a3b-chat"))) is None
    # the control: the same passes chosen by the reference one precision
    # down (float8_e4m3 weights and stored activations) and judged in the
    # reference's logits by the SAME checks has to fail, by one of the
    # cell's limits; the second control (weights alone) is read beside it
    control = got["controls"][runner.CONTROL]
    assert not control["correct"] and control["failed_by"]
    assert set(control["failed_by"]) <= {
        "served_logit_gap", "first_rank_gap_mean_sq",
        "unmask_confidence_gap"}
    assert control["tokens_off_the_best"] > 0
    second = got["controls"]["float8_e4m3_weights"]
    assert second["tokens_compared"] == got["tokens_compared"]
    assert second["first_rank_gap_mean_sq"] > got["first_rank_gap_mean_sq"]


def test_one_lane_unmasked_out_of_order_is_not_correct(runner, ref):
    undo = runner.plant_unmask_order()
    try:
        facts = _rehearse(runner, ref, 987654321)
    finally:
        undo()
    by_name = {n: (v, l, ok) for n, v, l, ok in facts["checks"]}
    value, limit, ok = by_name["unmask_confidence_gap"]
    assert not ok and value > limit + 0.02
    assert facts["readings"]["orders_out_of_order"] > 0


def test_a_token_that_is_not_the_programs_choice_is_not_correct(runner, ref):
    """The upper reading of served_logit_gap: a wrong token lies units
    under the reference's best, where rounding moves a logit by
    hundredths."""
    undo = runner.plant_wrong_token(every=3)
    try:
        facts = _rehearse(runner, ref, 24681357)
    finally:
        undo()
    by_name = {n: (v, l, ok) for n, v, l, ok in facts["checks"]}
    value, limit, ok = by_name["served_logit_gap"]
    assert not ok and value > 100 * limit
    assert facts["readings"]["tokens_off_the_best"] > 0
