"""Decode serving (ISSUE 6): paged KV cache, ragged paged attention,
continuous batching.

Coverage map:
  - PageAllocator: alloc/free/reuse determinism, occupancy bound,
    exhaustion refusal (structured, side-effect-free), page-table
    padding stability, fragmentation accounting;
  - paged_attention: reference path vs a dense numpy oracle, vs the
    flash kernel's dense path, vs the Pallas paged kernel in interpret
    mode — identical numerics across all four; the MULTI-TOKEN chunked
    form (ISSUE 10): GQA, dead slots (q_len 0) exact-zero, a chunk
    crossing a page boundary, causal masking within the chunk, and
    flash-causal agreement on a pure-prefill chunk;
  - DecodeEngine: warm pre-compiles exactly the (slots x widths x
    chunks) ladder and sequence CHURN AT RAGGED LENGTHS performs ZERO
    new compiles (the tier-1 acceptance guard — counter-asserted, and
    the fluid executor's jit counter stays untouched), KV footprint
    fixed, greedy decode deterministic;
  - chunked prefill (ISSUE 10): a P-token prompt prefills in
    ceil(P/chunk) scheduler steps (counter-pinned), greedy tokens
    identical with chunking on vs off, in-flight decodes never stall
    behind a prefilling prompt, reserve-at-admission holds exactly
    under multi-token appends, prefill_* metrics populated;
  - sampling (ISSUE 8 satellite): temperature/top-k/seed per request,
    deterministic given seed and independent of batch composition,
    temperature 0 / top_k 1 bitwise-greedy, typed validation, RPC
    pass-through;
  - continuous batching beats drain-per-batch by EXACT step counts
    (the scheduler-shape claim, proven with counters, not clocks);
  - admission: queue overload, page-pool exhaustion, RequestTooLarge,
    deadline misses — all typed and counted;
  - registry hot-swap of decoders: drain + release;
  - chaos: a generate reply killed mid-frame is answered from the
    idempotency dedup cache on retransmit — zero re-decoding, exact
    counters;
  - rpc zero-copy satellite: from_wire(copy=False) returns READ-ONLY
    buffer-backed views (mutation raises), get_param rides it, wire
    byte counters identical to the copying path.

All timing-sensitive claims are COUNTER asserts (tier-1 wall time
swings 604-836s on this host — see memory/tier1-timing-margin).
"""
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.serving import (
    DecodeEngine, DecoderSpec, ModelRegistry, PageAllocator,
    RequestTooLarge, ServerOverloaded, ServingClient, ServingServer,
)
from paddle_tpu.serving.errors import (DeadlineExceeded, EngineRetired,
                                       ServingError)
from paddle_tpu.serving.kv_cache import GARBAGE_PAGE


def _spec():
    """Smallest decoder that still exercises GQA (2 q heads per kv
    head) and multi-layer pool indexing."""
    return DecoderSpec(vocab=32, d_model=16, n_layers=2, n_heads=2,
                      n_kv_heads=1, seed=7)


def _engine(**kw):
    """Tiny ladders so warm compiles 8 shapes: slots [1,2] x widths
    [1,2] x chunks [1,4] (max_seq_len 8 / page_size 4)."""
    kw.setdefault("slots", [1, 2])
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 10)
    kw.setdefault("max_seq_len", 8)
    kw.setdefault("max_queue", 16)
    kw.setdefault("prefill_chunk", 4)
    return DecodeEngine(_spec(), name=kw.pop("name", "toy"), **kw)


# --- page allocator ------------------------------------------------------

def test_page_allocator_determinism_and_reuse():
    """Fresh pages come out in ascending order; freed pages are reused
    LIFO — the same admit/complete history always yields the same page
    tables (replayable decode)."""
    a = PageAllocator(num_pages=8, page_size=4)
    assert a.alloc(1, 8) == [1, 2]     # ceil(8/4) = 2 pages
    assert a.alloc(2, 1) == [3]
    assert a.alloc(3, 5) == [4, 5]
    a.free(2)
    a.free(1)
    # LIFO: seq 1's pages (freed last) come back first, in held order
    assert a.alloc(4, 9) == [1, 2, 3]
    assert metrics.counter("serving.kv.page_allocs").value() == 8
    assert metrics.counter("serving.kv.page_frees").value() == 3
    # double free is a no-op, not corruption
    assert a.free(1) == 0


def test_page_allocator_exhaustion_is_clean():
    """Refusal is typed, counted, and side-effect-free: the failed
    alloc leaves the free list exactly as it was."""
    a = PageAllocator(num_pages=4, page_size=2)   # 3 usable pages
    a.alloc(1, 4)                                  # takes 2
    free_before = a.pages_free
    with pytest.raises(ServerOverloaded, match="page pool exhausted"):
        a.alloc(2, 4)                              # needs 2, only 1 left
    assert a.pages_free == free_before
    assert metrics.counter("serving.kv.exhaustions").value() == 1
    a.free(1)
    assert a.pages_used == 0
    assert a.alloc(3, 4) == [1, 2]                 # pool fully recovered


def test_page_table_padding_and_fragmentation():
    a = PageAllocator(num_pages=8, page_size=4)
    a.alloc(1, 6)  # 2 pages for 6 tokens
    row = a.table_row(1, 4)
    assert row.dtype == np.int32 and row.shape == (4,)
    assert list(row) == [1, 2, GARBAGE_PAGE, GARBAGE_PAGE]
    with pytest.raises(ValueError, match="too narrow"):
        a.table_row(1, 1)
    a.note_tokens(1, 3)  # 3 of 8 reserved token slots written
    st = a.stats()
    assert st["pages_used"] == 2 and st["tokens"] == 3
    assert st["fragmentation"] == pytest.approx(1.0 - 3 / 8)
    assert metrics.gauge("serving.kv.pages_total").value() == 8


# --- paged attention numerics -------------------------------------------

def test_paged_attention_matches_dense_and_flash():
    """The A/B the tentpole demands: the paged reference path, the
    Pallas paged kernel (interpret), the flash kernel's dense path, and
    a plain numpy softmax oracle all agree on the same ragged batch."""
    import jax.numpy as jnp

    from paddle_tpu.fluid.ops.pallas_kernels.flash_attention import \
        flash_attention
    from paddle_tpu.fluid.ops.pallas_kernels.paged_attention import (
        _paged_attention_pallas, paged_attention_reference)

    rng = np.random.RandomState(0)
    B, Hq, Hkv, D, ps = 3, 4, 2, 8, 8
    P, W = 10, 3
    lens = np.array([20, 5, 0], np.int32)          # ragged + a dead slot
    tables = np.array([[1, 2, 3], [4, 0, 0], [0, 0, 0]], np.int32)
    q = rng.randn(B, Hq, D).astype(np.float32)
    kp = rng.randn(P, ps, Hkv, D).astype(np.float32)
    vp = rng.randn(P, ps, Hkv, D).astype(np.float32)

    ref = np.asarray(paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lens)))

    # oracle: dense softmax per sequence over the gathered pages
    for b in range(B):
        L = int(lens[b])
        if L == 0:
            np.testing.assert_array_equal(ref[b], 0.0)
            continue
        k = kp[tables[b]].reshape(-1, Hkv, D)[:L].repeat(Hq // Hkv, 1)
        v = vp[tables[b]].reshape(-1, Hkv, D)[:L].repeat(Hq // Hkv, 1)
        s = np.einsum("hd,thd->ht", q[b] * D ** -0.5, k)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(ref[b], np.einsum("ht,thd->hd", p, v),
                                   rtol=2e-5, atol=2e-6)
        # flash kernel's dense path on the same contiguous K/V
        fl = np.asarray(flash_attention(
            jnp.asarray(q[b][None, None]),          # [1, Sq=1, H, D]
            jnp.asarray(k.transpose(1, 0, 2)[None].transpose(0, 2, 1, 3)),
            jnp.asarray(v.transpose(1, 0, 2)[None].transpose(0, 2, 1, 3)),
            causal=False, block_q=8, block_k=8, interpret=True))
        np.testing.assert_allclose(ref[b], fl[0, 0], rtol=2e-4, atol=2e-5)

    # the Pallas paged kernel (scalar-prefetch page walk), interpret mode
    pal = np.asarray(_paged_attention_pallas(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True))
    np.testing.assert_allclose(pal, ref, rtol=2e-5, atol=2e-6)


def test_paged_attention_chunked_matches_reference_and_flash():
    """The ISSUE 10 kernel A/B: the MULTI-TOKEN form (q [B, C, Hq, D] +
    q_lens) against a per-query numpy oracle, against the Pallas kernel
    in interpret mode, and against the flash kernel's CAUSAL dense path
    on a pure-prefill chunk. Covers GQA (2 q heads per kv head), a dead
    slot (q_len 0 -> exact zero), dead lanes of a live slot, a chunk
    whose tokens cross a page boundary, and causal masking within the
    chunk."""
    import jax.numpy as jnp

    from paddle_tpu.fluid.ops.pallas_kernels.flash_attention import \
        flash_attention
    from paddle_tpu.fluid.ops.pallas_kernels.paged_attention import (
        _paged_attention_pallas, paged_attention_reference)

    rng = np.random.RandomState(1)
    B, C, Hq, Hkv, D, ps = 3, 6, 4, 2, 8, 8
    P, W = 10, 3
    # slot 0: 20 keys, 6-query chunk ending at key 20 — the chunk spans
    # absolute positions 14..19, CROSSING the page boundary at 16;
    # slot 1: pure-prefill chunk (kv_len == q_len: the whole sequence
    # IS the chunk) -> plain causal attention;
    # slot 2: dead (q_len 0, garbage table)
    kv_lens = np.array([20, 5, 0], np.int32)
    q_lens = np.array([6, 5, 0], np.int32)
    tables = np.array([[1, 2, 3], [4, 0, 0], [0, 0, 0]], np.int32)
    q = rng.randn(B, C, Hq, D).astype(np.float32)
    kp = rng.randn(P, ps, Hkv, D).astype(np.float32)
    vp = rng.randn(P, ps, Hkv, D).astype(np.float32)

    ref = np.asarray(paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(kv_lens),
        q_lens=jnp.asarray(q_lens)))

    # numpy oracle: query j of slot b sees keys <= kv_len - q_len + j
    for b in range(B):
        k = kp[tables[b]].reshape(-1, Hkv, D).repeat(Hq // Hkv, 1)
        v = vp[tables[b]].reshape(-1, Hkv, D).repeat(Hq // Hkv, 1)
        for j in range(C):
            if j >= q_lens[b]:
                np.testing.assert_array_equal(ref[b, j], 0.0)
                continue
            L = int(kv_lens[b]) - int(q_lens[b]) + j + 1
            s = np.einsum("hd,thd->ht", q[b, j] * D ** -0.5, k[:L])
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            np.testing.assert_allclose(
                ref[b, j], np.einsum("ht,thd->hd", p, v[:L]),
                rtol=2e-5, atol=2e-6)

    # the Pallas kernel (page tables + both length vectors in
    # scalar-prefetch), interpret mode
    pal = np.asarray(_paged_attention_pallas(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(kv_lens),
        q_lens=jnp.asarray(q_lens), interpret=True))
    np.testing.assert_allclose(pal, ref, rtol=2e-5, atol=2e-6)

    # slot 1 is a pure-prefill chunk: chunk-causal == flash causal
    k1 = kp[tables[1]].reshape(-1, Hkv, D)[:5]
    v1 = vp[tables[1]].reshape(-1, Hkv, D)[:5]
    fl = np.asarray(flash_attention(
        jnp.asarray(q[1, :5][None]),                  # [1, 5, Hq, D]
        jnp.asarray(k1.repeat(Hq // Hkv, 1)[None]),
        jnp.asarray(v1.repeat(Hq // Hkv, 1)[None]),
        causal=True, block_q=8, block_k=8, interpret=True))
    np.testing.assert_allclose(ref[1, :5], fl[0], rtol=2e-4, atol=2e-5)


# --- the engine: compile guard, determinism, footprint -------------------

def test_decode_churn_zero_new_compiles():
    """THE acceptance guard: after warm, a churn of admits and
    completions at ragged prompt/generation lengths performs ZERO new
    decode-step compiles (and never touches the fluid executor's jit
    cache), and the KV pool never grows."""
    # pool sized for the whole submitted queue: pages are reserved at
    # ADMISSION (kv_cache.py), so 10 queued 1-2 page sequences need
    # up to 20 usable pages
    eng = _engine(num_pages=24)
    try:
        # warm compiled exactly the ladder product (via stats(), which
        # snapshots the shape set under ITS lock — this file also runs
        # under the guard sanitizer, where a bare _compiled_shapes poke
        # is a violation)
        assert eng.slot_ladder == [1, 2]
        assert eng.table_width_ladder == [1, 2]
        assert eng.chunk_ladder == [1, 4]
        assert eng.stats()["compiled_shapes"] == [
            (1, 1, 1), (1, 1, 4), (1, 2, 1), (1, 2, 4),
            (2, 1, 1), (2, 1, 4), (2, 2, 1), (2, 2, 4)]
        pool_shape = tuple(eng.cache.k.shape)
        base_decode = metrics.counter("serving.decode.compiles").value()
        base_exec = metrics.counter("executor.jit_compiles").value()

        rng = np.random.RandomState(1)
        reqs = []
        for _ in range(10):
            prompt = rng.randint(0, 32, size=1 + int(rng.randint(4)))
            max_new = 1 + int(rng.randint(8 - len(prompt)))
            reqs.append(eng.submit(prompt, max_new_tokens=max_new))
        for r in reqs:
            assert r.ev.wait(120), "decode timed out"
            assert r.error is None, r.error
            assert 1 <= len(r.result["tokens"]) <= 8

        assert metrics.counter("serving.decode.compiles").value() \
            == base_decode, "sequence churn escaped the warmed ladder"
        assert metrics.counter("executor.jit_compiles").value() \
            == base_exec, "decode path leaked into the executor jit cache"
        assert (len(eng.stats()["compiled_shapes"]) ==
                len(eng.slot_ladder) * len(eng.table_width_ladder)
                * len(eng.chunk_ladder))
        # footprint: the pool is the SAME preallocated arrays' shape,
        # and every page went back to the free list
        assert tuple(eng.cache.k.shape) == pool_shape
        st = eng.cache.allocator.stats()
        assert st["pages_total"] == 24 and st["pages_used"] == 0
        assert metrics.counter("serving.decode.completions").value() == 10
    finally:
        eng.stop()


def test_decode_greedy_is_deterministic():
    eng = _engine()
    try:
        a = eng.generate([3, 1, 4], max_new_tokens=5)
        b = eng.generate([3, 1, 4], max_new_tokens=5)
        assert a["tokens"] == b["tokens"]
        assert a["prompt_len"] == 3 and len(a["tokens"]) == 5
        # a fresh engine with the same seeded spec replays bitwise
        eng2 = _engine(name="toy2")
        try:
            c = eng2.generate([3, 1, 4], max_new_tokens=5)
            assert c["tokens"] == a["tokens"]
        finally:
            eng2.stop()
    finally:
        eng.stop()


@pytest.mark.parametrize("top_k", [8, 0])
def test_sampling_deterministic_given_seed_and_batch_independent(top_k):
    """temperature/top-k sampling (ISSUE 8 satellite, the ROADMAP
    beyond-greedy residual): the rng derives only from (request seed,
    token position), so a request's sampled output is identical across
    engines, slot ladders, and co-riding traffic — continuous batching
    cannot perturb it. On both routes (ISSUE 29): ``top_k`` 8 is drawn
    on the host by ``sample_token``, the full vocabulary by
    ``choose_tokens`` inside the step's program."""
    from paddle_tpu.serving.decode import sample_token

    eng = _engine()
    try:
        a = eng.generate([3, 1, 4], max_new_tokens=5, temperature=0.9,
                         top_k=top_k, seed=1234)
        b = eng.generate([3, 1, 4], max_new_tokens=5, temperature=0.9,
                         top_k=top_k, seed=1234)
        assert a["tokens"] == b["tokens"]
        route = "host_choices" if top_k else "device_choices"
        assert metrics.counter("serving.decode." + route).value() == 10
        other = eng.generate([3, 1, 4], max_new_tokens=5,
                             temperature=0.9, top_k=top_k, seed=1235)
        assert other["tokens"] != a["tokens"]       # the seed decides
        # a different engine shape AND concurrent traffic: same tokens
        eng2 = _engine(name="toy_s2", slots=[1, 2, 4], num_pages=16)
        try:
            noise = [eng2.submit([7], max_new_tokens=3,
                                 temperature=0.5, seed=i)
                     for i in range(3)]
            c = eng2.generate([3, 1, 4], max_new_tokens=5,
                              temperature=0.9, top_k=top_k, seed=1234)
            for r in noise:
                assert r.ev.wait(120) and r.error is None
            assert c["tokens"] == a["tokens"]
        finally:
            eng2.stop()
    finally:
        eng.stop()
    # the pure sampler: top_k masks everything below the k-th logit
    row = np.array([0.1, 2.0, -1.0, 1.5, 0.0], np.float32)
    for pos in range(32):
        tok = sample_token(row, temperature=5.0, top_k=2, seed=9,
                           position=pos)
        assert tok in (1, 3), tok


def test_temperature_zero_and_topk1_match_greedy():
    eng = _engine()
    try:
        greedy = eng.generate([5, 2], max_new_tokens=4)
        t0 = eng.generate([5, 2], max_new_tokens=4, temperature=0.0,
                          seed=77)
        k1 = eng.generate([5, 2], max_new_tokens=4, temperature=2.0,
                          top_k=1, seed=77)
        assert t0["tokens"] == greedy["tokens"]
        assert k1["tokens"] == greedy["tokens"]
        with pytest.raises(ValueError, match="temperature"):
            eng.submit([1], max_new_tokens=2, temperature=-0.5)
        with pytest.raises(ValueError, match="top_k"):
            eng.submit([1], max_new_tokens=2, top_k=-1)
    finally:
        eng.stop()


def test_sampling_rpc_roundtrip(decode_server):
    """Sampling params thread through generate on the wire; the result
    is deterministic given the seed, so a retransmitted frame answered
    from the dedup cache equals what a re-decode would have produced."""
    srv, cli, _addr = decode_server
    out1 = cli.generate("gen", [3, 1], max_new_tokens=4, temperature=0.8,
                        top_k=4, seed=42)
    out2 = cli.generate("gen", [3, 1], max_new_tokens=4, temperature=0.8,
                        top_k=4, seed=42)
    assert out1["tokens"] == out2["tokens"] and len(out1["tokens"]) == 4
    with pytest.raises(ValueError, match="temperature"):
        cli.generate("gen", [1], max_new_tokens=2, temperature=-1.0)


# --- the choice made by the step's program (ISSUE 29) --------------------

def test_device_draw_matches_softmax_by_chi_square():
    """choose_tokens at temperature > 0 is an exact draw from
    softmax(logits / T): 4000 (seed, position) pairs on one 5-lane row,
    held to the chi-square bound of 4 degrees of freedom (18.47 is its
    99.9th percentile; the seeds are fixed, so this never flakes), at
    two temperatures in one batch."""
    from paddle_tpu.serving.decode import choose_tokens

    row = np.array([0.1, 2.0, -1.0, 1.5, 0.0], np.float32)
    n = 4000
    seeds = (np.arange(n, dtype=np.uint64) * 2654435761 + 17) % (1 << 32)
    positions = np.arange(n, dtype=np.int32) % 97 + 3
    for temp in (0.7, 2.5):
        ids = np.asarray(choose_tokens(
            np.tile(row, (n, 1)), np.full(n, temp, np.float32),
            seeds.astype(np.uint32), positions))
        assert ids.dtype == np.int32 and ids.shape == (n,)
        p = np.exp(row.astype(np.float64) / temp)
        p /= p.sum()
        seen = np.bincount(ids, minlength=5)
        chi2 = float(((seen - n * p) ** 2 / (n * p)).sum())
        assert chi2 < 18.47, (temp, seen.tolist(), (n * p).tolist())


def test_device_choice_is_pure_in_seed_and_position_and_greedy_at_zero():
    """A row's id depends on (its logits, temperature, seed, position)
    and on nothing else of the batch: the same row drawn alone, in
    another batch size and at another row index is the same id; rows at
    temperature 0 are np.argmax, the first index on ties, whatever
    their seed."""
    from paddle_tpu.serving.decode import choose_tokens

    rng = np.random.RandomState(3)
    logits = rng.randn(6, 64).astype(np.float32)
    logits[1, [5, 9, 40]] = logits[1].max() + 1.0       # a three-way tie
    logits[4, :] = 0.25                                 # all lanes tie
    temp = np.array([0.0, 0.0, 1.0, 0.6, 0.0, 1.0], np.float32)
    seed = np.array([7, 0xFFFFFFFF, 11, 0xFFFFFFFF, 3, 11], np.uint32)
    pos = np.array([1, 2, 30, 2 ** 31 - 1, 5, 31], np.int32)
    ids = np.asarray(choose_tokens(logits, temp, seed, pos))
    for i in (0, 1, 4):
        assert ids[i] == np.argmax(logits[i])
    assert ids[1] == 5 and ids[4] == 0
    # rows 2 and 5 share a seed and differ in position: over the 64
    # lanes of two unrelated rows that is two draws, not one
    order = [5, 3, 2]
    again = np.asarray(choose_tokens(logits[order], temp[order],
                                     seed[order], pos[order]))
    assert again.tolist() == ids[order].tolist()
    for i in (2, 3, 5):
        alone = np.asarray(choose_tokens(
            logits[i:i + 1], temp[i:i + 1], seed[i:i + 1], pos[i:i + 1]))
        assert alone[0] == ids[i]
    # seed and position both move the draw somewhere in 40 tries
    base = [int(np.asarray(choose_tokens(
        logits[2:3], temp[2:3], np.array([s], np.uint32),
        np.array([p], np.int32)))[0]) for s, p in
        [(11, q) for q in range(40)] + [(r, 30) for r in range(40)]]
    assert len(set(base[:40])) > 1 and len(set(base[40:])) > 1


def test_step_hands_back_ids_and_a_row_only_on_request():
    """The step's program hands back [slots] int32 ids beside the
    logits, which stay on the device; _fetch_row brings one row, bitwise
    the batch's; the call takes its five shapes by position and the
    sampling arrays by keyword."""
    import jax

    from paddle_tpu.serving.decode import choose_tokens

    eng = _engine()
    try:
        tokens = np.array([[3], [9]], np.int32)
        positions = np.zeros((2, 1), np.int32)
        ones = np.ones(2, np.int32)
        tables = np.full((2, 1), GARBAGE_PAGE, np.int32)
        sampling = dict(temperature=np.array([0.0, 0.8], np.float32),
                        seed=np.array([0, 77], np.uint32))
        base = metrics.counter("serving.decode.compiles").value()
        ids, logits = eng._run_step_arrays(tokens, positions, ones,
                                           tables, ones, **sampling)
        assert isinstance(ids, jax.Array) and isinstance(logits, jax.Array)
        assert ids.shape == (2,) and ids.dtype == np.int32
        assert logits.shape == (2, 32) and logits.dtype == np.float32
        whole = np.asarray(logits)
        assert not np.array_equal(whole[0], whole[1])
        for i in range(2):
            row = eng._fetch_row(logits, i)
            assert row.shape == (32,) and np.array_equal(row, whole[i])
        assert np.asarray(ids)[0] == np.argmax(whole[0])
        # the draw's position is the call's own lens
        assert np.asarray(ids).tolist() == np.asarray(choose_tokens(
            logits, *sampling.values(), ones)).tolist()
        # five positional arguments alone are an all-greedy call of the
        # same compiled shape (warm()'s, and the benchmark's log of
        # shapes reads them by position)
        ids0, logits0 = eng._run_step_arrays(tokens, positions, ones,
                                             tables, ones)
        assert np.array_equal(np.asarray(logits0), whole)
        assert np.asarray(ids0).tolist() == whole.argmax(-1).tolist()
        assert metrics.counter("serving.decode.compiles").value() == base
    finally:
        eng.stop()


def test_host_route_requests_answer_as_before(monkeypatch):
    """top_k > 0, a constraint mask and the first position's first_topk
    order still go through the host's numpy choice, now on ONE fetched
    row: sample_token sees a [vocab] row a token and its answer is the
    token; first_topk is the stable order of the row the greedy token
    leads; a request with neither never reaches the host sampler."""
    from paddle_tpu.serving import decode
    from paddle_tpu.serving.workloads import TokenMaskSpec

    seen = []
    real = decode.sample_token

    def recorded(row, *a, **k):
        tok = real(row, *a, **k)
        seen.append((np.array(row), a, tok))
        return tok

    monkeypatch.setattr(decode, "sample_token", recorded)
    eng = _engine()
    try:
        out = eng.generate([3, 1, 4], max_new_tokens=4, temperature=0.9,
                           top_k=8, seed=5)
        assert [t for _r, _a, t in seen] == out["tokens"]
        assert all(r.shape == (32,) for r, _a, _t in seen)
        assert [a for _r, a, _t in seen] == [
            (0.9, 8, 5, 3 + i) for i in range(4)]
        del seen[:]
        eng.generate([3, 1, 4], max_new_tokens=4, temperature=0.9,
                     seed=5)                    # full vocabulary
        plain = eng.generate([3, 1, 4], max_new_tokens=4)
        assert seen == []
        beam = eng.generate([3, 1, 4], max_new_tokens=4, topk_first=5)
        assert beam["tokens"] == plain["tokens"]
        assert len(beam["first_topk"]) == 5
        assert beam["first_topk"][0] == plain["tokens"][0]
        masked = eng.generate(
            [3, 1, 4], max_new_tokens=4, temperature=0.9, seed=5,
            mask=TokenMaskSpec.regex("( 5 | 6 | 7 ) *").compile())
        assert all(t in (5, 6, 7) for t in masked["tokens"])
        assert len(seen) == 4           # the masked draw is the host's
        counts = metrics.snapshot("serving.decode.")
        assert counts["serving.decode.host_choices"] == 4 + 1 + 4
        assert counts["serving.decode.device_choices"] == 4 + 4 + 3
    finally:
        eng.stop()


@pytest.mark.parametrize("k", [0, 1, 7, 63, 64, 99])
def test_first_topk_order_is_the_whole_rows_stable_sort(k):
    """The first position's token order comes from a partition and a
    sort of the lanes that can be in it: lane for lane what the stable
    sort of the whole row gave, through ties that straddle the cut,
    -inf lanes (a mask's) and a row that is one tie."""
    from paddle_tpu.serving.decode import _top_order

    rng = np.random.RandomState(k)
    rows = [rng.randn(64).astype(np.float32),
            rng.randint(-2, 3, size=64).astype(np.float32),
            np.full(64, 0.5, np.float32)]
    rows.append(np.where(rng.rand(64) < 0.5, -np.inf, rows[1])
                .astype(np.float32))
    for row in rows:
        want = np.argsort(-row.astype(np.float64), kind="stable")[:k]
        assert _top_order(row, k) == want.tolist()


def test_a_step_nobody_reads_a_token_of_is_not_waited_for(monkeypatch):
    """A step whose chunks all end inside their prompts hands back ids
    nobody reads: the next step is dispatched before it is done, as the
    one after a step that reads is, and its ids never come to the host
    (at most two steps queued); the answer is what an engine that waits
    for every step gives."""
    prompt = list(range(1, 14))                 # 13 tokens, chunks of 4
    ahead = _observed(monkeypatch, "ahead_pct")
    fetched = []
    eng = _engine(max_seq_len=24, num_pages=16)
    every = _engine(max_seq_len=24, num_pages=16, name="every")
    try:
        real = eng._collect

        def recorded(rnd, sp=None):
            real(rnd, sp)
            fetched.append(rnd.out is not None)

        eng._collect = recorded
        every._waits_first = lambda pend, live: True
        kw = dict(max_new_tokens=5, temperature=0.8, seed=21)
        got = eng.generate(prompt, **kw)
        # 4 + 4 + 4 inside the prompt, then its last token and 4 more;
        # every step but the first went ahead of the one before it
        assert fetched == [False] * 3 + [True] * 5
        assert ahead == [0.0] + [100.0] * 7
        assert got["tokens"] == every.generate(prompt, **kw)["tokens"]
        assert ahead[8:] == [0.0] * 8
        assert got["tokens"] == eng.generate(prompt, **kw)["tokens"]
    finally:
        eng.stop()
        every.stop()


def test_choice_counters_on_a_mixed_batch_and_no_compile_after_warm():
    """device_choices / host_choices count one a chosen token by the
    route its request takes, in one shared batch; device_choice_pct
    observes once a step that chose a token; sample_ms reads 0.0 on the
    steps without a host row; and a churn that mixes both routes
    compiles nothing after warm(), the row fetch included."""
    eng = _engine(slots=[1, 2, 4], num_pages=64, max_seq_len=16)
    try:
        warm = metrics.counter("serving.decode.compiles").value()
        # the step ladder, one row-fetch program a slot count, and the
        # device feed of a call from the one before: slots x chunks x
        # the slots before
        assert warm == len(eng.stats()["compiled_shapes"]) + 3 + 3 * 2 * 3
        metrics.reset_metrics("serving.decode.")
        reqs = _drive(eng, [
            ([1, 2, 3], dict(max_new_tokens=6)),
            ([4, 5], dict(max_new_tokens=5, temperature=1.0, seed=8)),
            ([6], dict(max_new_tokens=4, temperature=1.0, top_k=4,
                       seed=8)),
            ([7, 8, 9], dict(max_new_tokens=3, topk_first=3))],
            together=True)
        assert all(r.error is None for r in reqs)
        snap = metrics.snapshot("serving.decode.")
        assert snap["serving.decode.device_choices"] == 6 + 5 + (3 - 1)
        assert snap["serving.decode.host_choices"] == 4 + 1
        assert snap["serving.decode.tokens"] == 18
        pct = snap["serving.decode.device_choice_pct"]
        # the longest answer takes six choosing steps; a prefill-only
        # step observes nothing
        assert pct["count"] == 6 <= snap["serving.decode.steps"]
        assert 0.0 < pct["min"] < pct["max"] == 100.0
        sample = snap["serving.decode.sample_ms"]
        assert sample["count"] == snap["serving.decode.steps"]
        assert sample["min"] == 0.0 < sample["max"]
        # ragged churn over both routes: nothing compiles
        rng = np.random.RandomState(2)
        how = [{}, dict(temperature=0.7, seed=1),
               dict(temperature=0.7, top_k=3, seed=1),
               dict(topk_first=2)]
        churn = [eng.submit(rng.randint(0, 32, size=1 + int(rng.randint(5))),
                            max_new_tokens=1 + int(rng.randint(6)),
                            **how[i % 4]) for i in range(12)]
        for r in churn:
            assert r.ev.wait(120) and r.error is None
        assert metrics.counter("serving.decode.compiles").value() == 0
    finally:
        eng.stop()


def test_continuous_beats_drain_by_exact_step_count():
    """The continuous-batching claim, proven with counters: 2 slots,
    one long sequence (prompt 1 + 9 new = 9 steps) + two short ones
    (1 step each). Drain-per-batch runs 9 + 1 = 10 steps (the second
    wave waits for the long straggler; a finished slot idles).
    Continuous admits the third sequence into the long one's in-flight
    steps: short steps co-ride long steps, total = the long sequence's
    own 9 (modulo submission racing, bounded below)."""
    results = {}
    for mode, continuous in (("drain", False), ("cont", True)):
        eng = _engine(name=f"m_{mode}", slots=[2], max_seq_len=12,
                      num_pages=12, continuous=continuous)
        try:
            base = metrics.counter("serving.decode.steps").value()
            long = eng.submit([1], max_new_tokens=9)      # 9 steps
            s1 = eng.submit([2], max_new_tokens=1)        # 1 step
            s2 = eng.submit([3], max_new_tokens=1)        # 1 step
            for r in (long, s1, s2):
                assert r.ev.wait(120) and r.error is None, r.error
            results[mode] = \
                metrics.counter("serving.decode.steps").value() - base
        finally:
            eng.stop()
    # drain is exactly 10 no matter how admission raced: waves are
    # {long}, {s1, s2} (9+1) or {long, s1}, {s2} (9+1)
    assert results["drain"] == 10, results
    # continuous: s1/s2 ride the long sequence's steps; even if the
    # submitting thread lost a couple of races the total stays below
    # drain (9 in the common schedule)
    assert results["cont"] < results["drain"], results
    occ = metrics.snapshot()["serving.decode.occupancy"]
    assert occ["count"] > 0


# --- chunked prefill (ISSUE 10) ------------------------------------------

def test_chunked_prefill_steps_counter_pinned():
    """THE ISSUE 10 acceptance: a P-token prompt (P = 4*chunk) prefills
    in exactly ceil(P/chunk) scheduler steps (vs P before), total steps
    = ceil(P/chunk) + (max_new - 1), serving.decode.compiles stays at
    its post-warm value across the churn, and the prefill_* metrics
    surface the budget spend."""
    # pool sized for the whole churn burst: pages are reserved at
    # admission (up to 6 x 4 pages live at once in the churn below)
    eng = _engine(name="chunky", max_seq_len=20, num_pages=26,
                  prefill_chunk=4)
    try:
        base_c = metrics.counter("serving.decode.compiles").value()
        base_s = metrics.counter("serving.decode.steps").value()
        base_p = metrics.counter("serving.decode.prefill_tokens").value()
        prompt = list(np.random.RandomState(5).randint(0, 32, size=16))
        out = eng.generate(prompt, max_new_tokens=3)     # P = 4 * chunk
        assert out["steps_to_first_token"] == 4, out     # ceil(16/4)
        assert metrics.counter("serving.decode.steps").value() \
            - base_s == 4 + 2
        assert metrics.counter("serving.decode.compiles").value() \
            == base_c, "chunked prefill escaped the warmed ladder"
        # every prompt token rode a prefill grant, and the per-step
        # budget histogram priced them
        assert metrics.counter(
            "serving.decode.prefill_tokens").value() - base_p == 16
        hist = metrics.snapshot()
        assert hist["serving.decode.prefill_tokens_per_step"]["count"] > 0
        assert hist["serving.decode.steps_to_first_token"]["count"] > 0
        # more churn at ragged prompt lengths: still zero new compiles
        rng = np.random.RandomState(6)
        reqs = [eng.submit(rng.randint(0, 32, size=1 + int(rng.randint(12))),
                           max_new_tokens=2) for _ in range(6)]
        for r in reqs:
            assert r.ev.wait(120) and r.error is None, r.error
        assert metrics.counter("serving.decode.compiles").value() == base_c
        assert eng.cache.allocator.stats()["pages_used"] == 0
    finally:
        eng.stop()


def test_greedy_tokens_identical_chunking_on_vs_off():
    """Chunking is pure packing: the same prompt greedy-decodes to the
    SAME tokens at chunk 4 and chunk 1 (the PR 6 one-token-per-step
    schedule) — only the step counts differ (4 vs 13 to first token)."""
    prompt = list(np.random.RandomState(9).randint(0, 32, size=13))
    outs = {}
    for chunk in (4, 1):
        eng = _engine(name=f"ab{chunk}", max_seq_len=20, num_pages=16,
                      prefill_chunk=chunk)
        try:
            outs[chunk] = eng.generate(prompt, max_new_tokens=4)
        finally:
            eng.stop()
    assert outs[4]["tokens"] == outs[1]["tokens"], outs
    assert outs[4]["steps_to_first_token"] == 4      # ceil(13/4)
    assert outs[1]["steps_to_first_token"] == 13


def test_mixed_step_decode_never_stalls_behind_prefill():
    """Sarathi-style mixed batches: a sequence mid-decode co-rides a
    fresh prompt's prefill chunks — the prompt still prefills in
    ceil(P/chunk) of ITS OWN steps (prefill budget untouched by decode
    slots), and the decoding sequence's tokens keep arriving (both
    complete; neither waits for the other)."""
    eng = _engine(name="mixed", slots=[2], max_seq_len=20, num_pages=16,
                  prefill_chunk=4)
    try:
        a = eng.submit([1], max_new_tokens=10)
        # wait until A is decoding (its 1-token prompt consumed)
        for _ in range(2000):
            with eng._cond:
                sa = next((s for s in eng._slots if s.req is a), None)
                if sa is not None and a.produced:
                    break
            time.sleep(0.002)
        b = eng.submit(list(range(16)), max_new_tokens=2)
        assert a.ev.wait(120) and a.error is None, a.error
        assert b.ev.wait(120) and b.error is None, b.error
        assert len(a.result["tokens"]) == 10
        # B's prompt prefilled at the full budget despite A decoding
        # alongside: ceil(16/4) steps from B's admission
        assert b.result["steps_to_first_token"] == 4, b.result
    finally:
        eng.stop()


def test_reserve_at_admission_holds_exactly_under_chunking():
    """The ISSUE 10 small fix: admission reserves
    ceil((prompt+max_new)/page_size) pages up front, and chunked
    multi-token appends never write outside that reservation — proven
    by a pool sized EXACTLY for one request (reserve + garbage page):
    if any chunk escaped its reservation the step would trip the
    engine's reservation assert (failing the request) or corrupt page
    accounting (pages_used != 0 after completion)."""
    from paddle_tpu.serving import PageAllocator

    # 16 prompt + 4 new = 20 tokens = 5 pages of 4; pool = 5 + garbage
    eng = _engine(name="exact", slots=[1], max_seq_len=20, num_pages=6,
                  prefill_chunk=4)
    try:
        out = eng.generate(list(range(16)), max_new_tokens=4)
        assert len(out["tokens"]) == 4
        st = eng.cache.allocator.stats()
        assert st["pages_used"] == 0 and st["pages_free"] == 5
    finally:
        eng.stop()
    # the allocator-side bound the engine asserts against: a
    # reservation's token capacity is pages * page_size and never grows
    a = PageAllocator(num_pages=8, page_size=4)
    a.alloc(1, 10)                       # 3 pages -> 12-token capacity
    assert a.reserved_tokens(1) == 12
    a.note_tokens_many({1: 10})          # a chunked append's accounting
    assert a.reserved_tokens(1) == 12    # capacity unchanged
    assert a.stats()["tokens"] == 10
    a.free(1)
    assert a.reserved_tokens(1) == 0


# --- the scheduler round's spans and host time (ISSUE 27) ---------------

# a round's top-level spans: admit; the answer of the round before it
# where this one cannot go ahead of it (wait, release, answer); then the
# plan and the step, and the answer of the round before it where the
# step went ahead of that round
_ROUND = re.compile(r"A(WRN)?(PLS(N)?)?")
_LETTER = {"admit": "A", "wait": "W", "release": "R", "answer": "N",
           "prepare": "P", "plan": "L", "step": "S"}
# the device call's three stretches, in order: a call that went ahead of
# the round before it waits for that round's ids inside it
_CALL = ["dispatch", "wait", "release"]


def _rounds(top):
    """The scheduler thread's top-level spans, checked round by round
    against ``_ROUND``: each step that went ahead (its device call holds
    the wait) is followed by the answer it waited for, and every round
    dispatched is answered once. Returns the rounds' strings."""
    text = "".join(_LETTER[_short(e)] for e, _kids in top)
    rounds = [m.group(0) for m in _ROUND.finditer(text)]
    assert "".join(rounds) == text, text
    steps = [kids for e, kids in top if _short(e) == "step"]
    ahead = [[_short(k) for k, _ in kids[1][1]] == _CALL for kids in steps]
    assert [bool(r.endswith("SN")) for r in rounds if "S" in r] == ahead
    assert text.count("N") == text.count("S")
    return rounds


def _short(e):
    return e["name"][len("serving.decode."):]


def _nest(events):
    """Events of one thread, by start and the longer first, as a forest
    of ``(event, children)``: each child inside its parent, none
    overlapping its sibling."""
    top, stack = [], []
    for e in events:
        while stack and stack[-1][0]["end"] <= e["start"]:
            stack.pop()
        node = (e, [])
        if stack:
            parent, kids = stack[-1]
            assert e["end"] <= parent["end"], (_short(e), _short(parent))
            assert not kids or kids[-1][0]["end"] <= e["start"]
            kids.append(node)
        else:
            top.append(node)
        stack.append(node)
    return top


def _drive(eng, requests, together=False):
    """Submit ``(prompt, kwargs)`` requests one after another (each waits
    for the last), or — ``together`` — all under the engine's condition,
    so that one scheduler round admits them all."""
    if together:
        with eng._cond:
            reqs = [eng.submit(p, **kw) for p, kw in requests]
        for r in reqs:
            assert r.ev.wait(60)
        return reqs
    reqs = []
    for p, kw in requests:
        reqs.append(eng.submit(p, **kw))
        assert reqs[-1].ev.wait(60)
    return reqs


@pytest.mark.parametrize("sampled", [False, True])
def test_round_histograms_one_observation_a_step_and_inside_the_wall(
        sampled):
    """serving.decode.sample_ms and .sched_ms observe once a scheduler
    step, as step_ms does, and the three are disjoint stretches of the
    scheduler thread's time: together they never exceed the wall time
    the rounds took. Greedy slots read no clock for sampling."""
    eng = _engine(max_seq_len=16)
    kw = (dict(temperature=1.0, seed=5) if sampled else {})
    t0 = time.perf_counter()
    _drive(eng, [([1, 2, 3, 4, 5, 6], dict(max_new_tokens=6, **kw)),
                 ([7, 8], dict(max_new_tokens=5, topk_first=4 * sampled))])
    _drive(eng, [([3, 1, 4], dict(max_new_tokens=7, **kw)),
                 ([9] * 9, dict(max_new_tokens=4))], together=True)
    eng.stop()      # joins the scheduler: every observation is in
    wall_ms = (time.perf_counter() - t0) * 1e3
    snap = metrics.snapshot("serving.decode.")
    steps = snap["serving.decode.steps"]
    step, sample, sched = (snap["serving.decode." + n] for n in
                           ("step_ms", "sample_ms", "sched_ms"))
    assert steps > 10
    assert step["count"] == sample["count"] == sched["count"] == steps
    assert sample["min"] >= 0.0 and sched["min"] > 0.0
    assert (sample["sum"] > 0.0) == sampled
    assert step["sum"] + sample["sum"] + sched["sum"] <= wall_ms


def _fed(prompt_len, max_new, chunk=4, alone=True):
    """(query tokens, keys in view summed over the calls, causal pairs)
    one request puts through the step's device calls: every prompt token
    and every generated token but the last is fed once; alone, a prompt
    goes in chunks of ``chunk`` and each call sees the keys up to its
    chunk's end."""
    total = prompt_len + max_new - 1
    ends = list(range(chunk, prompt_len, chunk)) + list(
        range(prompt_len, total + 1))
    return total, sum(ends) if alone else None, total * (total + 1) // 2


@pytest.mark.parametrize("together", [False, True])
def test_round_spans_in_order_on_the_profilers_clock(profiler_session,
                                                     together):
    """Under a profiler session (the ring off) the scheduler thread's
    line holds a round's spans in order: admit, prepare, plan, step
    (build then device_call inside it, and inside that dispatch, wait,
    release), answer (sample inside it), none overlapping its sibling;
    device_call's args add up to the work the submitted prompts need."""
    eng = _engine(max_seq_len=16)
    requests = [([1, 2, 3, 4, 5, 6], dict(max_new_tokens=3, top_k=8,
                                          temperature=1.0, seed=3)),
                ([7, 8], dict(max_new_tokens=4, topk_first=4)),
                ([5] * 9, dict(max_new_tokens=2))]
    profiler_session.start()
    _drive(eng, requests, together)
    eng.stop()
    events = profiler_session.stop(prefix="serving.decode.")
    line, = {e["line"] for e in events}         # the scheduler's thread
    top = _nest(events)
    # whole rounds; an admit pass that found nothing to run (the engine
    # idle between two requests, and at the end) is followed by another
    rounds = _rounds(top)
    assert sum("S" in r for r in rounds) >= 5
    # the top_k request's rounds wait for the answer before them
    assert any(r.startswith("AWRN") and "S" in r for r in rounds)
    calls = []
    for e, kids in top:
        if _short(e) == "step":
            assert [_short(k) for k, _ in kids] == ["build", "device_call"]
            call, inside = kids[1]
            assert [_short(k) for k, _ in inside] in (_CALL[:1], _CALL)
            assert not kids[0][1] and not any(g for _k, g in inside)
            calls.append(call["args"])
        elif _short(e) == "answer":
            assert {_short(k) for k, _ in kids} <= {"sample"}
            assert not any(g for _k, g in kids)
        else:
            assert kids == []
    # the top_k request's tokens and the first_topk sort took the host
    # route, inside sample spans; a slot whose token the step's program
    # chose (greedy here) opens none
    n_sample = sum(_short(e) == "sample" for e in events)
    assert n_sample == 3 + 1
    want = [_fed(len(p), kw["max_new_tokens"], alone=not together)
            for p, kw in requests]
    assert sum(c["q_tokens"] for c in calls) == sum(w[0] for w in want)
    assert sum(c["attn_pairs"] for c in calls) == sum(w[2] for w in want)
    if together:
        assert max(c["slots"] for c in calls) == 2      # a shared step
    else:
        assert sum(c["kv_tokens"] for c in calls) == \
            sum(w[1] for w in want)
    assert all(c["slots"] in (1, 2) and c["chunk"] in (1, 4)
               and c["width"] in (1, 2, 4) for c in calls)
    from paddle_tpu.observability import tracing
    assert tracing.trace_events() == []         # the ring stayed off


def test_grid_live_histogram_and_span_args_read_what_the_build_sent(
        profiler_session):
    """serving.decode.attn_grid_live_pct observes once a step call, span
    or no span, and device_call's args say what the build sent: the
    compiled buckets beside the live sums (what the grid walks empty is
    the histogram's, not an arg's). One request
    alone, page 4, chunk 4: a prompt of 6 goes in as 4 + 2, then two
    decode steps — keys in view 4, 6, 7, 8 on 1, 2, 2, 2 pages."""
    eng = _engine(max_seq_len=16)
    profiler_session.start()
    _drive(eng, [([1, 2, 3, 4, 5, 6], dict(max_new_tokens=3))])
    events = profiler_session.stop(prefix="serving.decode.device_call")
    calls = [e["args"] for e in events]
    assert [c["kv_tokens"] for c in calls] == [4, 6, 7, 8]
    assert [c["q_tokens"] for c in calls] == [4, 2, 1, 1]
    assert [c["chunk"] for c in calls] == [4, 4, 1, 1]
    assert all(set(c) == {"slots", "chunk", "width", "q_tokens",
                          "kv_tokens", "attn_pairs"} for c in calls)
    # the request holds 3 pages (6 + 3 tokens): width bucket 4, one slot
    assert {(c["slots"], c["width"]) for c in calls} == {(1, 4)}
    # no session open, the histogram alone: two prompts of 5 and 3 in
    # one round share the step's budget of 4 prompt tokens, a token each
    # at least — keys in view (4, 1) then (5, 3) on slots 2 x width 2
    _drive(eng, [([11, 12, 13, 14, 15], dict(max_new_tokens=1)),
                 ([7, 8, 9], dict(max_new_tokens=1))], together=True)
    eng.stop()
    snap = metrics.snapshot("serving.decode.")
    live = snap["serving.decode.attn_grid_live_pct"]
    assert live["count"] == snap["serving.decode.steps"] == 4 + 2
    want = [100.0 * pages / grid for pages, grid in
            [(1, 4), (2, 4), (2, 4), (2, 4), (1 + 1, 4), (2 + 1, 4)]]
    assert live["sum"] == pytest.approx(sum(want))
    assert (live["min"], live["max"]) == (25.0, 75.0)


# --- the device call's stretches and the device's host gap --------------

def test_the_device_calls_stretches_nest_on_both_clocks(profiler_session):
    """With the ring on AND a profiler session collecting, each clock
    holds the round's plan between prepare and step, and dispatch, wait
    and release in that order inside device_call, on the scheduler's
    thread; device_call keeps its args."""
    from paddle_tpu.observability import tracing

    eng = _engine(max_seq_len=16)
    tracing.trace_reset()
    tracing.trace_enable()
    try:
        profiler_session.start()
        _drive(eng, [([1, 2, 3, 4, 5, 6], dict(max_new_tokens=3)),
                     ([7, 8], dict(max_new_tokens=2))], together=True)
        eng.stop()
        events = profiler_session.stop(prefix="serving.decode.")
        ring = [e for e in tracing.trace_events()
                if e["name"].startswith("serving.decode.")]
    finally:
        tracing.trace_disable()
        tracing.trace_reset()
    ring = sorted(({"name": e["name"], "start": e["ts"],
                    "end": e["ts"] + e["dur"], "line": e["tid"],
                    "args": e.get("args", {})} for e in ring),
                  key=lambda e: (e["start"], -e["end"]))
    for clock in (events, ring):
        assert len({e["line"] for e in clock}) == 1  # the scheduler's
        top = _nest(clock)
        names = [_short(e) for e, _kids in top]
        n_steps = names.count("step")
        assert n_steps >= 3
        assert names.count("plan") == n_steps
        for i, n in enumerate(names):
            if n == "plan":
                assert names[i - 1:i + 2] == ["prepare", "plan", "step"]
        calls = [kids[1] for e, kids in top if _short(e) == "step"]
        assert len(calls) == n_steps
        for call, inside in calls:
            assert _short(call) == "device_call"
            assert [_short(k) for k, _ in inside] in (_CALL[:1], _CALL)
            assert {"slots", "chunk", "width", "q_tokens", "kv_tokens",
                    "attn_pairs"} <= set(call["args"])
        # every round but the first went ahead of the one before it; the
        # last is answered with nothing queued behind it
        assert [[_short(k) for k, _ in inside] for _c, inside in calls] \
            == [_CALL[:1]] + [_CALL] * (n_steps - 1)
        assert [r for r in _rounds(top) if r != "A"][-1] == "AWRN"
    # the ring's own parentage says the same: a dispatch's parent is the
    # device call, and so is a wait's and a release's but for the last
    # round's, which no dispatch holds
    by_id = {e["args"]["span_id"]: _short(e) for e in ring}
    parents = [by_id.get(e["args"].get("parent_span_id"))
               for e in ring if _short(e) in _CALL]
    assert parents.count(None) == 2
    assert set(parents) == {"device_call", None}


def _observed(monkeypatch, name):
    """Every observation of the engine's histogram ``_m_<name>``, in
    order."""
    from paddle_tpu.serving import decode

    seen, hist = [], getattr(decode, "_m_" + name)
    real = hist.observe
    monkeypatch.setattr(hist, "observe",
                        lambda v: (seen.append(v), real(v)))
    return seen


def test_the_call_histograms_observe_once_a_target_step(monkeypatch):
    """serving.decode.dispatch_ms, .release_ms, .host_gap_ms and
    .ahead_pct observe once a step that dispatches the target program,
    as step_ms does; the host gap is 0 exactly where the step went ahead
    of the one before it, and elsewhere holds the dispatch, so it is
    never the shorter of the two."""
    gaps = _observed(monkeypatch, "host_gap_ms")
    sent = _observed(monkeypatch, "dispatch_ms")
    ahead = _observed(monkeypatch, "ahead_pct")
    eng = _engine(max_seq_len=16)
    _drive(eng, [([1, 2, 3, 4, 5, 6], dict(max_new_tokens=4)),
                 ([7, 8], dict(max_new_tokens=3))])
    _drive(eng, [([3, 1, 4], dict(max_new_tokens=5)),
                 ([9] * 9, dict(max_new_tokens=2))], together=True)
    eng.stop()
    snap = metrics.snapshot("serving.decode.")
    steps = snap["serving.decode.steps"]
    assert steps > 10
    for name in ("dispatch_ms", "release_ms", "host_gap_ms", "step_ms",
                 "ahead_pct"):
        h = snap["serving.decode." + name]
        assert h["count"] == steps, name
        assert h["min"] >= 0.0
    assert snap["serving.decode.dispatch_ms"]["min"] > 0.0
    assert len(gaps) == len(sent) == len(ahead) == steps
    assert set(ahead) == {0.0, 100.0}
    assert sum(a == 100.0 for a in ahead) > steps // 2
    assert [g == 0.0 for g in gaps] == [a == 100.0 for a in ahead]
    # a gap ends where its round's dispatch does and began before it
    assert all(g >= d for g, d in zip(gaps, sent) if g > 0)


def test_a_steps_logits_are_let_go_inside_its_answer_span(monkeypatch):
    """A target step's logits stay on the device through the answer phase
    (the host route's rows come from them) and are let go inside that
    round's serving.decode.answer span, not when the round's frame ends
    after it: the k-th step's logits go inside the k-th answer span."""
    import weakref

    from paddle_tpu.observability import tracing

    gone, armed = [], []
    real = DecodeEngine._run_step_arrays

    def run(self, *args, **kw):
        ids, logits = real(self, *args, **kw)
        if armed:                                # not warm()'s calls
            weakref.finalize(logits, lambda: gone.append(
                (time.perf_counter() - tracing._EPOCH) * 1e6))
        return ids, logits

    monkeypatch.setattr(DecodeEngine, "_run_step_arrays", run)
    eng = _engine(max_seq_len=16)
    tracing.trace_reset()
    tracing.trace_enable()
    armed.append(True)
    try:
        _drive(eng, [([1, 2, 3, 4, 5, 6], dict(max_new_tokens=4)),
                     ([7, 8], dict(max_new_tokens=3))], together=True)
        eng.stop()
        answers = sorted((e["ts"], e["ts"] + e["dur"])
                         for e in tracing.trace_events()
                         if e["name"] == "serving.decode.answer")
    finally:
        tracing.trace_disable()
        tracing.trace_reset()
    assert len(gone) == len(answers) > 3
    for t, (start, end) in zip(sorted(gone), answers):
        assert start <= t <= end


def test_the_host_gap_is_0_behind_an_unread_step_and_skips_the_idle_wait(
        monkeypatch):
    """A prompt of 9 at chunk 4 goes in as 4, 4, 1: the first two chunks
    end inside the prompt and nobody reads their ids, the third and the
    two decode steps after it are read; every step after the first is
    dispatched before the ids of the one before it are on the host, so
    each reads a gap of 0 (the device still held a step). An engine idle
    for a second before a request does not count the idle second in that
    request's first gap (the second prompt shares no prefix with the
    first: no page of it is cached)."""
    gaps = _observed(monkeypatch, "host_gap_ms")
    eng = _engine(max_seq_len=16)
    time.sleep(1.0)                              # idle from the start
    _drive(eng, [(list(range(1, 10)), dict(max_new_tokens=3))])
    first = list(gaps)
    time.sleep(1.0)                              # idle between requests
    _drive(eng, [(list(range(11, 20)), dict(max_new_tokens=3))])
    eng.stop()
    second = gaps[len(first):]
    # 4 + 4 (unread), 1 (the first token), two decode steps
    for run in (first, second):
        assert len(run) == 5
        assert run[1:] == [0.0] * 4, run
        assert 0.0 < run[0] < 500.0      # not the idle second


def test_dot_fold_share_counts_folds_by_the_kernels_predicate():
    """`_dot_fold_pct` by hand: a chunk slot of 64 lanes at 100 keys, one
    of 2 lanes (under the predicate's lane count) at 37, two decoding
    slots and a dead one, pages of 16. Full layers fold a lane into every
    page up to kv_len; a window layer into those from its oldest lane's
    window on."""
    from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa
    from paddle_tpu.serving.decode import _dot_fold_pct

    q = np.array([64, 2, 1, 1, 0])
    kv = np.array([100, 37, 300, 16, 0])
    assert list(pa.folds_by_dot(64, 8, "bfloat16", q)) == [
        True, False, False, False, False]
    full = np.array([64 * 7, 2 * 3, 19, 1, 0])
    got = _dot_fold_pct(64, 8, "bfloat16", q, kv, page_size=16)
    assert got == pytest.approx(100.0 * full[0] / full.sum())
    # window 64: the oldest lanes sit at 36, 35, 299, 15 and see from key
    # 0, 0, 236, 0 on: pages 0.., 0.., 14.., 0..
    window = np.array([64 * 7, 2 * 3, 19 - 14, 1, 0])
    got = _dot_fold_pct(64, 8, "bfloat16", q, kv, page_size=16, window=64,
                        layers=(1, 4))
    assert got == pytest.approx(100.0 * (full[0] + 4 * window[0])
                                / (full.sum() + 4 * window.sum()))
    # a geometry whose program has no dot fold, and a call of dead slots
    assert not pa.folds_by_dot(16, 1, "float32")
    assert _dot_fold_pct(16, 1, "float32", q, kv, page_size=16) == 0.0
    assert _dot_fold_pct(64, 8, "bfloat16", q * 0, kv * 0,
                         page_size=16) == 0.0


@pytest.mark.parametrize("route", ["paged_kernel", "paged_reference"])
def test_dot_fold_histogram_observes_once_a_step_call(route):
    """serving.decode.attn_dot_fold_pct (ISSUE 35) observes once a step
    call what the kernel's predicate gives for the call's slots: a prompt
    of 66 alone at a chunk of 64 goes in as 64 lanes (every fold the dot
    fold's: 100), then 2 (under the predicate's lane count: 0), then
    decodes (0). Where the attention is not the kernel's, nothing is the
    dot fold's: 0 throughout."""
    from paddle_tpu.fluid.flags import FLAGS, set_flags
    from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa

    spec = DecoderSpec(vocab=32, d_model=16, n_layers=1, n_heads=8,
                       n_kv_heads=1, seed=7)
    assert pa.folds_by_dot(64, 8, spec.pool_dtype)
    assert pa.folds_by_dot(64, 8, spec.pool_dtype, 64)
    assert not pa.folds_by_dot(64, 8, spec.pool_dtype, 2)
    was = FLAGS["use_pallas_kernels"]
    set_flags({"use_pallas_kernels": route == "paged_kernel"})
    try:
        eng = DecodeEngine(spec, name="toy", slots=[1], page_size=16,
                           num_pages=8, max_seq_len=80, max_queue=4,
                           prefill_chunk=64, prefix_cache=False)
        assert eng.stats()["attention_route"] == [route]
        metrics.reset_metrics("serving.decode.")
        _drive(eng, [(list(range(1, 32)) * 2 + [1, 2, 3, 4],
                      dict(max_new_tokens=3))])
        eng.stop()
    finally:
        set_flags({"use_pallas_kernels": was})
    snap = metrics.snapshot("serving.decode.")
    share = snap["serving.decode.attn_dot_fold_pct"]
    assert share["count"] == snap["serving.decode.steps"] == 4
    assert share["count"] == snap["serving.decode.attn_grid_live_pct"][
        "count"]
    first = 100.0 if route == "paged_kernel" else 0.0
    assert (share["sum"], share["max"], share["min"]) == (first, first, 0.0)


@pytest.mark.parametrize("all_lanes", [False, True])
def test_decoder_step_names_its_device_work(all_lanes):
    """decoder_step_chunked's lowered text holds every decoder.* scope,
    and the Pallas kernel its name inside decoder.attn (interpreted
    here; Mosaic gets the same name on the chip)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid.flags import FLAGS, set_flags
    from paddle_tpu.serving.decode import (build_decoder_params,
                                           decoder_step_chunked)

    spec = _spec()
    params = build_decoder_params(spec)
    pool = jnp.zeros((spec.n_layers, 10, 4, spec.n_kv_heads,
                      spec.head_dim), jnp.float32)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    was = FLAGS["use_pallas_kernels"]
    set_flags({"use_pallas_kernels": True})
    try:
        text = jax.jit(lambda p, *a: decoder_step_chunked(
            p, spec, *a, all_lanes=all_lanes)).lower(
            params, i32(2, 4), i32(2, 4), i32(2), pool, pool, i32(2, 2),
            i32(2)).as_text(debug_info=True)
    finally:
        set_flags({"use_pallas_kernels": was})
    for scope in ("embed", "kv_write", "attn", "mlp", "head"):
        assert f"/decoder.{scope}/" in text, scope
    assert "/decoder.attn/paged_attention/pallas_call" in text


# --- admission / deadlines ----------------------------------------------

def test_decode_admission_refusals_are_typed():
    eng = _engine(max_queue=2)
    try:
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit([])
        with pytest.raises(ValueError, match="token ids"):
            eng.submit([99])
        with pytest.raises(RequestTooLarge, match="max_seq_len"):
            eng.submit([1, 2, 3], max_new_tokens=20)

        # page exhaustion: pool is 9 usable pages of 4 tokens; three
        # 8-token sequences take 2 pages each, the queue bound (2) is
        # irrelevant because slots drain — so grab pages directly too
        held = [eng.cache.allocator.alloc(1000 + i, 12) for i in range(3)]
        base_over = metrics.counter("serving.decode.overloads").value()
        with pytest.raises(ServerOverloaded, match="page pool exhausted"):
            eng.submit([1, 2, 3, 4], max_new_tokens=4)   # needs 2 pages
        assert metrics.counter("serving.decode.overloads").value() \
            == base_over + 1
        for i in range(3):
            eng.cache.allocator.free(1000 + i)
        # pool recovered: the same request is admitted now
        out = eng.generate([1, 2, 3, 4], max_new_tokens=4)
        assert len(out["tokens"]) == 4
    finally:
        eng.stop()


def test_finished_result_delivered_even_if_deadline_lapsed():
    """A request whose FINAL token lands in the same step its deadline
    lapses gets the fully-computed result, not DeadlineExceeded — the
    deadline sheds remaining work; it never discards paid-for output."""
    eng = _engine()
    try:
        req = eng.submit([1], max_new_tokens=2)  # no deadline yet
        # wait for the first generated token, then lapse the deadline
        # so the step producing token 2 sees finished AND lapsed
        deadline = time.monotonic()
        for _ in range(2000):
            with eng._cond:
                slot = next((s for s in eng._slots if s.req is req), None)
                if slot is not None and len(req.produced) >= 1:
                    req.deadline = deadline  # already in the past
                    break
            if req.ev.is_set():
                break  # scheduler outran the poll: delivery still asserted
            time.sleep(0.002)
        assert req.ev.wait(60)
        assert req.error is None, f"completed result discarded: {req.error}"
        assert len(req.result["tokens"]) == 2
    finally:
        eng.stop()


def test_decode_deadline_miss_frees_pages():
    eng = _engine()
    try:
        with pytest.raises(DeadlineExceeded):
            eng.generate([1, 2], max_new_tokens=6, deadline_ms=0.0)
        assert metrics.counter(
            "serving.decode.deadline_misses").value() >= 1
        # the lapsed sequence's pages went back to the pool
        assert eng.cache.allocator.stats()["pages_used"] == 0
    finally:
        eng.stop()


# --- registry / hot-swap -------------------------------------------------

def test_cancel_withdraws_abandoned_request_and_frees_pages():
    """An abandoned generate (wait timeout) cancels its sequence: the
    page reservation frees immediately and no decode steps are spent
    completing a result nobody reads."""
    eng = _engine(slots=[1])   # one slot: the second submit queues
    try:
        first = eng.submit([1, 2], max_new_tokens=6)
        waiting = eng.submit([3, 4], max_new_tokens=6)
        assert eng.cancel(waiting, msg="test walked away")
        assert waiting.ev.is_set()
        assert isinstance(waiting.error, ServingError)
        assert "canceled" in str(waiting.error)
        assert metrics.counter("serving.decode.cancels").value() == 1
        assert first.ev.wait(60) and first.error is None
        assert len(first.result["tokens"]) == 6
        # canceling a finished request is a no-op
        assert not eng.cancel(first)
        assert metrics.counter("serving.decode.cancels").value() == 1
        assert eng.cache.allocator.stats()["pages_used"] == 0
    finally:
        eng.stop()


def test_step_failure_with_donated_pools_retires_engine():
    """With donation active a raising step has already consumed the KV
    pools — the engine must retire (fail everything, refuse submits)
    instead of admitting requests doomed to fail on deleted buffers."""
    eng = _engine()
    try:
        def _boom(*a, **k):
            raise RuntimeError("injected step failure")
        eng._donate = True      # CPU tests never donate; force the path
        with eng._step_mu:      # the program table is _step_mu-guarded
            eng._programs["target"] = \
                eng._programs["target"]._replace(fn=_boom)
        req = eng.submit([1, 2], max_new_tokens=4)
        assert req.ev.wait(60)
        assert isinstance(req.error, ServingError)
        assert "injected step failure" in str(req.error)
        # the scheduler retired the engine: new submits are refused so
        # the server's resubmit loop lands on a redeployed engine
        with pytest.raises(EngineRetired):
            eng.submit([3], max_new_tokens=2)
        # nothing leaked: pages back, gauges zeroed
        assert eng.cache.allocator.stats()["pages_used"] == 0
        assert metrics.gauge(
            "serving.decode.live_slots.toy.v1").value() == 0
        assert metrics.gauge(
            "serving.decode.queue_depth.toy.v1").value() == 0
    finally:
        eng.stop()


def test_registry_hot_swaps_decoders_with_release():
    reg = ModelRegistry()
    reg.deploy("g", lambda: _engine(name="g", version=1))
    out1 = reg.get("g").generate([5, 6], max_new_tokens=3)
    assert out1["version"] == 1
    old = reg.get("g")
    reg.deploy("g", lambda: _engine(name="g", version=2))
    out2 = reg.get("g").generate([5, 6], max_new_tokens=3)
    assert out2["version"] == 2
    # same seeded spec -> the swap is invisible in the tokens
    assert out2["tokens"] == out1["tokens"]
    # the retired engine released its params and KV pool (white-box
    # reads under each attr's guard: this file runs sanitized too)
    with old._cond:
        assert old._released
    with old._step_mu:
        assert old._params is None
    assert old.cache.k is None
    # ... and zeroed its per-version gauges — no phantom load on a
    # dead engine (live_slots included: the scheduler can exit between
    # steps without a final answer phase)
    assert metrics.gauge("serving.decode.queue_depth.g.v1").value() == 0
    assert metrics.gauge("serving.decode.live_slots.g.v1").value() == 0
    assert metrics.gauge("serving.kv.pages_used.g.v1").value() == 0
    assert metrics.counter("serving.hot_swaps").value() == 1
    reg.unload_all()


def test_swap_drains_in_flight_sequences():
    """A sequence admitted before the flip finishes on the OLD decoder
    (its KV history lives in the old pool) — zero dropped sequences."""
    reg = ModelRegistry()
    reg.deploy("g", lambda: _engine(name="g", version=1))
    req = reg.get("g").submit([1], max_new_tokens=7)
    reg.deploy("g", lambda: _engine(name="g", version=2))
    assert req.ev.wait(120), "in-flight sequence dropped by hot-swap"
    assert req.error is None
    assert req.result["version"] == 1 and len(req.result["tokens"]) == 7
    reg.unload_all()


# --- RPC / chaos ---------------------------------------------------------

@pytest.fixture
def decode_server():
    srv = ServingServer()
    addr = srv.serve()
    cli = ServingClient(addr)
    cli.load_decoder("gen", _spec().to_dict(), slots=[1, 2], page_size=4,
                     num_pages=10, max_seq_len=8, prefill_chunk=4)
    yield srv, cli, addr
    cli.close()
    srv.shutdown()


def test_generate_rpc_roundtrip(decode_server):
    srv, cli, _addr = decode_server
    out = cli.generate("gen", [3, 1, 4], max_new_tokens=5)
    assert out["version"] == 1 and len(out["tokens"]) == 5
    # wrong-kind calls are typed errors, not crashes
    with pytest.raises(ServingError, match="is a decoder"):
        cli.infer("gen", {"x": np.zeros((1, 2), np.float32)})
    listed = cli.list_models()
    assert listed["gen"]["kind"] == "decoder"
    assert listed["gen"]["kv"]["pages_used"] == 0
    # redeploying the LIVE version is refused before anything is built:
    # a same-version engine would mint the same per-version gauge
    # series and its retirement would zero the live engine's gauges
    with pytest.raises(ValueError, match="already the live version"):
        cli.load_decoder("gen", _spec().to_dict(), version=1,
                         slots=[1, 2], page_size=4, num_pages=10,
                         max_seq_len=8)
    assert metrics.gauge("serving.kv.pages_total.gen.v1").value() == 10


@pytest.mark.chaos
def test_generate_reply_dropped_retry_is_dedup_exact(decode_server):
    """Kill the generate REPLY mid-frame: the retransmit is answered
    from the dedup cache WITHOUT re-decoding — the decode step counter
    proves the sequence ran exactly once."""
    from paddle_tpu.distributed import faults

    srv, cli, _addr = decode_server
    metrics.reset_metrics()  # isolate the faulted call's counters
    with faults.scoped("drop@recv.generate:0") as plan:
        out = cli.generate("gen", [2, 7], max_new_tokens=4)
    assert [(k, s) for k, s, _i in plan.injected()] == \
        [("drop", "recv.generate")]
    assert len(out["tokens"]) == 4
    assert metrics.counter("rpc.client.retries").value() == 1
    assert metrics.counter("rpc.server.dedup_hits").value() == 1
    assert metrics.counter("serving.decode.requests").value() == 1
    assert metrics.counter("serving.decode.completions").value() == 1
    # chunked prefill: the 2-token prompt is one chunk (one step, whose
    # logits sample the first token) + 3 more decode steps, run ONCE
    assert metrics.counter("serving.decode.steps").value() == 4


# --- rpc zero-copy satellite --------------------------------------------

def test_from_wire_zero_copy_view_is_readonly():
    from paddle_tpu.distributed.rpc import from_wire, to_wire

    arr = np.arange(24, dtype=np.float32).reshape(4, 6)
    segs = []
    wire = to_wire({"w": arr}, segs)

    copied = from_wire(wire, segs)["w"]
    assert copied.flags.writeable
    copied[0, 0] = -1  # writable copy: mutation fine

    view = from_wire(wire, segs, copy=False)["w"]
    np.testing.assert_array_equal(view, arr)
    assert not view.flags.writeable
    with pytest.raises(ValueError):
        view[0, 0] = -1  # loud, never silent corruption
    # it really is backed by the frame bytes, not a copy
    assert view.base is not None


def test_get_param_zero_copy_and_exact_wire_bytes():
    """The client-side satellite end to end: get_param returns a
    read-only view; wire-byte counters are IDENTICAL to the copying
    path (the satellite changed host copies, not wire bytes)."""
    from paddle_tpu.distributed.rpc import RpcClient, RpcServer

    table = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    srv = RpcServer({"get_param": lambda n: table[n]},
                    idempotent={"get_param"})
    addr = srv.serve()
    try:
        cli = RpcClient(addr)
        bytes_in = metrics.counter("rpc.client.bytes_in")
        b0 = bytes_in.value()
        got_copy = cli.call("get_param", "w")           # default: copy
        per_call = bytes_in.value() - b0
        got_view = cli.call("get_param", "w", copy_result=False)
        assert bytes_in.value() - b0 == 2 * per_call  # exact, both modes
        np.testing.assert_array_equal(got_view, table["w"])
        assert got_copy.flags.writeable
        assert not got_view.flags.writeable
        # jnp.asarray (the real consumer) accepts the view fine
        import jax.numpy as jnp

        assert float(jnp.asarray(got_view).sum()) == float(table["w"].sum())
        cli.close()
    finally:
        srv.shutdown()


# --- slow lane: bench smoke ----------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_decode_bench_smoke():
    proc = subprocess.run(
        [sys.executable, "benchmarks/decode_bench.py", "--smoke"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    ev = json.loads(proc.stdout.strip().splitlines()[-1])
    res = ev["results"]
    # identical workload across all three strategies
    gens = {m: r["generated_tokens"] for m, r in res.items()}
    assert len(set(gens.values())) == 1 and gens["continuous"] > 0, gens
    # the compile-bound claim holds inside the bench too
    assert res["continuous"]["post_warm_compiles"] == 0
    assert res["drain"]["post_warm_compiles"] == 0
    # continuous needs FEWER decode steps for the same tokens — the
    # scheduler-shape claim, counter-based so host load can't flake it
    assert res["continuous"]["decode_steps"] <= res["drain"]["decode_steps"]
    assert "framework_metrics" in ev and ev["results"]["reprefill"][
        "full_forwards"] == gens["reprefill"]
    # chunked prefill (ISSUE 10): the long-prompt rows reach their
    # first token in strictly fewer scheduler steps than the
    # one-token-per-step baseline, still with zero post-warm compiles,
    # and the observed prompt-length histogram rides the evidence
    lp = ev["long_prompt"]["results"]
    assert lp["chunked"]["steps_to_first_token_mean"] \
        < lp["unchunked"]["steps_to_first_token_mean"]
    assert lp["chunked"]["post_warm_compiles"] == 0
    assert lp["unchunked"]["post_warm_compiles"] == 0
    assert ev["shape_histogram"].get("prefill_chunk"), \
        "prompt-length histogram missing from the bench evidence"
    # speculative decoding (ISSUE 14): the bench itself asserts bitwise
    # token equality across rows — here we pin the headline shape
    sk = ev["speculative"]
    assert sk["tokens_bitwise_equal_all_modes"] is True
    assert sk["target_steps_per_token_speedup"] >= 1.5
    for row in sk["results"].values():
        assert row["post_warm_compiles"] == 0
    assert sk["results"]["self_draft"]["accept_rate"] == 1.0
    assert "best" in ev["spec_k_tuning"]
