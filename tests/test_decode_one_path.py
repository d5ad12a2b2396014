"""The served step's one path (ISSUE 32): one array builder, one run
function over one program table, one round.

  - ``_build_arrays``: one case a kind of feed the engine makes (plain
    prefill chunk, 1-token decode, a block model's denoise and commit
    passes, the embed lane's chunk, the draft's catch-up chunk and its
    singles, the verify chunk) — positions are ``start + arange``,
    ``q_lens``/``lens`` as fed, dead rows all zero, padded table columns
    on the garbage page, a feed past its slot's reservation refused;
  - ``_run``: on an engine with a draft AND the embed lane, every device
    call of a mixed churn goes through it (and the target's through
    ``_run_step_arrays``, looked up on the instance at every call, as
    the benchmark's wrapper needs), nothing compiles after ``warm()``,
    and the compiled-shape keys are the tagged tuples they always were;
    a block model's engine keeps its bare triples and hands ``masked``
    by keyword.
"""
import collections

import jax
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.serving.decode import (DecodeEngine, DecoderSpec,
                                       _DecodeRequest, _EmbedRequest,
                                       _EmbedSlot, _Slot)
from paddle_tpu.serving.errors import ServingError
from paddle_tpu.serving.kv_cache import GARBAGE_PAGE
from test_moe_decoder import tiny_spec

S_BUCKET, W_BUCKET = 4, 8


def _causal_spec(**kw):
    kw = dict(dict(vocab=32, d_model=16, n_layers=2, n_heads=2,
                   n_kv_heads=1, seed=7), **kw)
    return DecoderSpec(**kw)


def _draft_spec():
    return _causal_spec(d_model=8, n_layers=1, n_heads=1, seed=3)


@pytest.fixture(scope="module")
def causal():
    """Never warmed, never fed: the builder needs the pool's allocator
    and the ladders, not a compiled program."""
    eng = DecodeEngine(_causal_spec(), name="build", slots=[1, 2, 4],
                       page_size=4, num_pages=64, max_seq_len=32,
                       prefill_chunk=8, warm=False)
    yield eng
    eng.stop(drain=False)


@pytest.fixture(scope="module")
def blocks():
    spec = tiny_spec(dtype="float32")
    eng = DecodeEngine(spec, name="buildb", slots=[1, 2, 4], page_size=4,
                       num_pages=64, max_seq_len=32, prefill_chunk=8,
                       warm=False)
    yield eng
    eng.stop(drain=False)


_seq = iter(range(1000, 2000))


def _slot(eng, prompt, produced=(), pos=0, reserve=None, embed=False):
    """A slot as admission would have made it: its reservation taken
    from the engine's allocator, its sequence as far as ``pos``."""
    seq_id = next(_seq)
    prompt = np.asarray(prompt, np.int32)
    total = len(prompt) + len(produced) + 8 if reserve is None else reserve
    eng.cache.allocator.alloc(seq_id, total)
    held = eng.cache.allocator.held_pages(seq_id)
    if embed:
        s = _EmbedSlot(_EmbedRequest(prompt, None, seq_id,
                                     eng.spec.d_model), held)
    else:
        req = _DecodeRequest(prompt, 16, None, seq_id)
        req.produced = list(produced)
        s = _Slot(req, held)
    s.pos = pos
    return s


def _plain_chunk(eng):
    # two prompts mid-prefill, chunks of 8 and 3
    a, b = _slot(eng, range(1, 21), pos=8), _slot(eng, range(5, 12), pos=4)
    return [a, b], [(8, a.tokens_at(8, 8)), (4, b.tokens_at(4, 3))], 8


def _decode_token(eng):
    # past their prompts: each feeds its last generated token
    a = _slot(eng, [3, 4, 5], produced=[9, 8], pos=4)
    b = _slot(eng, [6], produced=[2], pos=1)
    feeds = [(s.pos, s.tokens_at(s.pos, 1)) for s in (a, b)]
    assert [list(f[1]) for f in feeds] == [[8], [2]]
    return [a, b], feeds, 1


def _block_pass(eng, masked_lanes):
    def opened(prompt, pos):
        s = _slot(eng, prompt, pos=pos)
        eng._open_block(s)
        if not masked_lanes:            # every lane filled: the commit
            s.block = [7 + j for j in range(4)]
            s.masked = [False] * 4
        return s
    # 9 = 2 blocks + 1 token left over, which opens the block unmasked
    a, b = opened(range(1, 10), 8), opened(range(1, 5), 4)
    if masked_lanes:
        mask_id = eng.spec.mask_token_id
        assert a.block == [9, mask_id, mask_id, mask_id]
        assert a.masked == [False, True, True, True]
    return [a, b], [(s.pos, s.block) for s in (a, b)], 4


def _embed_chunk(eng):
    a = _slot(eng, range(2, 14), pos=4, embed=True)
    b = _slot(eng, range(1, 4), pos=0, embed=True)
    return [a, b], [(4, a.req.prompt[4:12]), (0, b.req.prompt[0:3])], 8


def _draft_catch_up(eng):
    # a: fully accepted round, the draft one token behind (2 lanes);
    # b: in step (1 lane); c: bonus-only this round, a dead row
    a = _slot(eng, [3, 4], produced=[5, 6, 7], pos=4)
    b = _slot(eng, [8], produced=[9, 1], pos=2)
    c = _slot(eng, [2], produced=[4], pos=1)
    a.dpos, b.dpos = 3, 2
    feeds = [(s.dpos, s.tokens_at(s.dpos, s.pos - s.dpos + 1))
             for s in (a, b)] + [None]
    assert [list(f[1]) for f in feeds[:2]] == [[6, 7], [1]]
    return [a, b, c], feeds, 2


def _draft_single(eng):
    # proposal d_1 fed at pos + 1 to propose d_2
    a = _slot(eng, [3, 4], produced=[5], pos=2)
    b = _slot(eng, [8], produced=[9, 1], pos=2)
    proposals = [[11, 12], [13]]
    return [a, b], [(s.pos + 1, p[0:1])
                    for s, p in zip((a, b), proposals)], 1


def _verify_chunk(eng):
    # [pending, d_1 .. d_k]: k_eff 2 and 0 at the fixed spec_k + 1 lanes
    a = _slot(eng, [3, 4], produced=[5], pos=2)
    b = _slot(eng, [8], produced=[9, 1], pos=2)
    proposals = [[11, 12], []]
    return [a, b], [(s.pos, [s.token_at(s.pos)] + p)
                    for s, p in zip((a, b), proposals)], 3


FEEDS = {
    "plain_chunk": ("causal", _plain_chunk),
    "decode_token": ("causal", _decode_token),
    "block_denoise": ("blocks", lambda e: _block_pass(e, True)),
    "block_commit": ("blocks", lambda e: _block_pass(e, False)),
    "embed_chunk": ("causal", _embed_chunk),
    "draft_catch_up": ("causal", _draft_catch_up),
    "draft_single": ("causal", _draft_single),
    "verify_chunk": ("causal", _verify_chunk),
}


@pytest.mark.parametrize("kind", sorted(FEEDS))
def test_build_arrays_pads_each_kind_of_feed_the_same_way(kind, request):
    which, make = FEEDS[kind]
    eng = request.getfixturevalue(which)
    slots, feeds, c_bucket = make(eng)
    tokens, positions, q_lens, tables, lens = eng._build_arrays(
        slots, feeds, S_BUCKET, c_bucket, W_BUCKET)
    assert tokens.shape == positions.shape == (S_BUCKET, c_bucket)
    assert tables.shape == (S_BUCKET, W_BUCKET)
    for a in (tokens, positions, q_lens, tables, lens):
        assert a.dtype == np.int32
    for i in range(S_BUCKET):
        feed = feeds[i] if i < len(feeds) else None
        if feed is None:
            # a dead row: nothing fed, nothing written
            assert not tokens[i].any() and not positions[i].any()
            assert q_lens[i] == 0 and lens[i] == 0
        else:
            start, fed = feed
            n = len(fed)
            assert n >= 1
            assert tokens[i, :n].tolist() == [int(t) for t in fed]
            assert positions[i, :n].tolist() == list(range(start,
                                                           start + n))
            assert not tokens[i, n:].any() and not positions[i, n:].any()
            assert q_lens[i] == n and lens[i] == start + n
        if i < len(slots):
            pages = eng.cache.allocator.pages_of(slots[i].req.seq_id)
            assert 1 <= len(pages) <= W_BUCKET
            assert tables[i, :len(pages)].tolist() == list(pages)
            assert (tables[i, len(pages):] == GARBAGE_PAGE).all()
        else:
            assert (tables[i] == GARBAGE_PAGE).all()
    # a later call of the round over the same slots takes the tables
    again = eng._build_arrays(slots, feeds, S_BUCKET, c_bucket, W_BUCKET,
                              tables)
    assert again[3] is tables
    for a, b in zip(again, (tokens, positions, q_lens, tables, lens)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("embed", [False, True])
def test_a_feed_past_its_reservation_is_refused(causal, embed):
    # 5 tokens reserved = 2 pages of 4: position 8 is past them
    s = _slot(causal, range(1, 13), pos=4, reserve=5, embed=embed)
    assert s.pages_held == 2
    fits = (4, s.req.prompt[4:8])
    causal._build_arrays([s], [fits], 1, 8, 2)
    with pytest.raises(ServingError, match="escaped seq .* reservation"):
        causal._build_arrays([s], [(4, s.req.prompt[4:9])], 1, 8, 2)
    # a canceled slot's pages are gone and its table row is garbage:
    # what it writes lands nowhere, so it is exempt
    s.req.fail(ServingError("canceled"))
    causal.cache.allocator.free(s.req.seq_id)
    out = causal._build_arrays([s], [(4, s.req.prompt[4:9])], 1, 8, 2)
    assert (out[3] == GARBAGE_PAGE).all()


def _watch(eng):
    """Count what runs: every ``_run`` by tag, every jitted program of
    the table by tag, and the target's entry point the way the benchmark
    wraps it — by name, on the instance."""
    seen = {"run": collections.Counter(), "fn": collections.Counter(),
            "entry": []}
    real_run, real_entry = eng._run, eng._run_step_arrays

    def run(tag, *args, **kw):
        seen["run"][tag] += 1
        return real_run(tag, *args, **kw)

    def entry(*args, **kw):
        assert len(args) == 5           # the call's shapes, by position
        seen["entry"].append(kw)
        return real_entry(*args, **kw)

    def counted(tag, fn):
        def call(*args):
            seen["fn"][tag] += 1
            return fn(*args)
        return call

    with eng._step_mu:
        for tag, prog in list(eng._programs.items()):
            eng._programs[tag] = prog._replace(fn=counted(tag, prog.fn))
    eng._run, eng._run_step_arrays = run, entry
    return seen


def _ctr(name):
    return metrics.counter(name).value()


def test_every_call_of_a_mixed_churn_goes_through_the_one_run_function():
    from paddle_tpu.serving.workloads import TokenMaskSpec

    eng = DecodeEngine(_causal_spec(), name="onepath", slots=[2],
                       page_size=4, num_pages=32, max_seq_len=8,
                       prefill_chunk=4, draft_spec=_draft_spec(),
                       spec_k=2, embeddings=True)
    try:
        # slots [2] x widths [1, 2] x the chunks of each family: the
        # target's and the embed lane's {1, 4}, the verify's spec_k + 1,
        # the draft's {1, 2, 4} — tagged, as at the parent
        want = {
            ("target", 2, 1, 1), ("target", 2, 1, 4), ("target", 2, 2, 1),
            ("target", 2, 2, 4), ("verify", 2, 1, 3), ("verify", 2, 2, 3),
            ("draft", 2, 1, 1), ("draft", 2, 1, 2), ("draft", 2, 1, 4),
            ("draft", 2, 2, 1), ("draft", 2, 2, 2), ("draft", 2, 2, 4),
            ("embed", 2, 1, 1), ("embed", 2, 1, 4), ("embed", 2, 2, 1),
            ("embed", 2, 2, 4)}
        assert set(eng.stats()["compiled_shapes"]) == want
        # the step shapes, and the row fetch of [2, V] and [2, 3, V]
        assert _ctr("serving.decode.compiles") >= len(want) + 2
        seen = _watch(eng)
        base = {n: _ctr(n) for n in (
            "serving.decode.compiles", "serving.decode.target_steps",
            "serving.decode.spec.draft_steps",
            "serving.decode.embed.steps")}
        with eng._cond:                 # one round admits them all
            reqs = [
                eng.submit([1, 2, 3, 4, 5], max_new_tokens=3),
                eng.submit([6, 7], max_new_tokens=4, temperature=0.9,
                           seed=5),
                eng.submit([8], max_new_tokens=4, temperature=1.1,
                           top_k=4, seed=9),
                eng.submit([9, 10, 11], max_new_tokens=3,
                           mask=TokenMaskSpec.one_of([[3, 4, 5], [3, 9]])),
                eng.submit_embed([4, 5, 6, 7, 8, 9]),
            ]
        for r in reqs:
            assert r.ev.wait(120) and r.error is None
        assert [len(r.result["tokens"]) for r in reqs[:3]] == [3, 4, 4]
        assert len(reqs[4].result["logprobs"]) == 5
        moved = {n: _ctr(n) - v for n, v in base.items()}
        assert moved["serving.decode.compiles"] == 0
        assert set(eng.stats()["compiled_shapes"]) == want
        # every family ran, each program exactly as often as _run was
        # asked for it, and the counters moved by as much
        assert set(seen["run"]) == {"target", "verify", "draft", "embed"}
        assert seen["fn"] == seen["run"]
        assert len(seen["entry"]) == seen["run"]["target"]
        assert moved["serving.decode.target_steps"] == (
            seen["run"]["target"] + seen["run"]["verify"])
        assert moved["serving.decode.spec.draft_steps"] == \
            seen["run"]["draft"]
        assert moved["serving.decode.embed.steps"] == seen["run"]["embed"]
        # the scheduler hands the target its sampling arrays by keyword
        assert all(set(kw) == {"temperature", "seed"}
                   for kw in seen["entry"])
    finally:
        eng.stop(drain=False)


def test_a_block_models_calls_keep_bare_triples_and_hand_masked_by_keyword():
    spec = tiny_spec(dtype="float32")
    eng = DecodeEngine(spec, name="onepathb", slots=[2], page_size=4,
                       num_pages=32, max_seq_len=16, prefill_chunk=8,
                       params=jax.device_put(spec.seeded_arrays()))
    try:
        # one program in the table: slots x widths x {block, chunk}
        want = {(2, w, c) for w in (1, 2, 4) for c in (4, 8)}
        assert set(eng.stats()["compiled_shapes"]) == want
        seen = _watch(eng)
        base = _ctr("serving.decode.compiles")
        steps = _ctr("serving.decode.target_steps")
        with eng._cond:
            reqs = [eng.submit(list(range(1, 10)), max_new_tokens=5),
                    eng.submit([3, 4, 5], max_new_tokens=6,
                               temperature=1.0, seed=3, denoise_steps=2)]
        for r in reqs:
            assert r.ev.wait(120) and r.error is None
        assert [len(r.result["tokens"]) for r in reqs] == [5, 6]
        assert _ctr("serving.decode.compiles") == base
        assert set(eng.stats()["compiled_shapes"]) == want
        assert set(seen["run"]) == {"target"} and seen["fn"] == seen["run"]
        assert len(seen["entry"]) == seen["run"]["target"]
        assert _ctr("serving.decode.target_steps") - steps == \
            seen["run"]["target"]
        # what the benchmark's planted faults read: the lanes still
        # masked, a row a slot of the bucket, by keyword
        for kw in seen["entry"]:
            assert set(kw) == {"temperature", "seed", "masked", "n_unmask"}
            assert kw["masked"].shape == (2, 4)
            assert kw["masked"].dtype == bool
            assert kw["n_unmask"].shape == (2,)
        assert any(kw["masked"].any() for kw in seen["entry"])
    finally:
        eng.stop(drain=False)
