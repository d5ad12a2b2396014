"""Ragged paged attention for decode serving (PAPERS.md: Ragged Paged
Attention) — single-token decode AND multi-token prefill chunks.

The decode-serving shape problem: each live sequence has a different KV
length that grows every step. Dense batched attention would need either
one compiled program per ragged length combination (O(shapes) jit
entries) or padding every sequence's K/V to max length (HBM ∝ max_len).
Here K/V live in a paged pool (serving/kv_cache.py) and the kernel
reads them THROUGH per-sequence page tables, so one compiled shape —
``[slots, table_width]`` — serves every ragged length mix up to
``table_width * page_size`` tokens.

Chunked prefill (ISSUE 10) adds the second ragged axis: a slot may
carry a CHUNK of ``q_len ∈ [0, C]`` query tokens (a slice of its
prompt) instead of exactly one, attending causally within the chunk —
query ``j`` of the chunk sees keys up to absolute position
``kv_len - q_len + j``. One compiled ``[slots, C, ...]`` shape then
serves every mix of prefill chunks and single-token decode slots
(Sarathi-style mixed batches; serving/decode.py packs them).

Layouts:

    q            [B, Hq, D]            single token per slot, OR
                 [B, C, Hq, D]         a chunk of C query tokens/slot
    q_lens       [B] int32             valid query tokens per slot
                                       (chunked form only; 0 = dead)
    k/v_pages    [P, page_size, Hkv, D]   the shared page pool
    page_tables  [B, W] int32          page ids per slot, GARBAGE-padded
    kv_lens      [B] int32             valid keys per slot INCLUDING
                                       this call's q_len tokens

GQA: ``Hq % Hkv == 0``; query head h attends kv head ``h // (Hq/Hkv)``.
Dead slots (q_lens == 0, or kv_lens == 0 in the single-token form)
produce exact zeros; so do dead query lanes ``j >= q_len`` of a live
slot.

Two implementations with IDENTICAL semantics (A/B-tested against each
other and against the flash kernel's dense path in
tests/test_decode_serving.py):

  - ``paged_attention_reference`` — pure-jax gather (k_pages[tables]):
    the CPU path tier-1 exercises, and the numerics oracle.
  - ``_paged_attention_pallas`` — a Pallas TPU kernel on grid
    ``(B, W)`` with the page table (and both length vectors) as
    SCALAR-PREFETCH operands: the BlockSpec index_map reads
    ``tables[b, w]`` so the pipeline DMAs the pages each sequence owns,
    page by page, with an online softmax across pages (flash-attention
    style running max/sum) — the [B, C, W*page_size] score tensor never
    materializes. The grid is the compiled ``(B, W)`` bucket, the WORK
    is what the two length vectors say (ISSUE 31): a column past a
    slot's last live page names that page again (no new DMA: neither a
    garbage column nor a page held past ``kv_len`` is fetched) and runs
    two scalar compares; a live page is folded into lanes
    ``0 .. q_len - 1`` only. What lies past either length cannot reach
    the output, NaN included.

Two FOLDS of a live page into that one online softmax (ISSUE 35), and
``folds_by_dot`` says which a slot takes, from what the call carries:

  - the LANE LOOP walks the slot's lanes one at a time: a lane's scores
    are a broadcast multiply and a reduce over D, ``p . v`` another over
    the page's rows, all float32 on the VPU with K and V repeated to the
    query heads. A slot with few lanes takes it (every decoding slot,
    ``q_len`` 1: one lane costs the latency of the page's fetch), and it
    is the only fold in a program whose ``C * rep`` is too small for a
    product to pay (``DOT_MIN_ROWS``): that program is traced as it
    always was.
  - the DOT FOLD takes all of a slot's lanes against the page at once, a
    kv head at a time: the head's ``C * rep`` query rows (no repeat of K
    or V: the head group is the product's row axis) against the page's
    keys in one product on the MXU, ``p . v`` in a second, float32
    accumulation, the mask and the statistics as the lane loop's. No
    operand is narrowed: bfloat16 times bfloat16 is exact in float32 in
    one pass, and ``p`` (float32) goes as the three bfloat16 terms that
    hold its 24 bits (``_mxu``). A slot of ``DOT_MIN_LANES`` lanes or
    more takes it: a prefill chunk, whose cost a page then does not grow
    with its lanes.

A LATENT pool (``paged_latent_attention``): one array ``[P, page_size,
D]`` a layer, ONE key a token shared by every query head (multi-query over
a latent attention layer's compressed entry) whose first ``value_dim``
columns are also its value. The page is ``[page_size, D]`` as it lies,
with no head axis to tile (a ``[P, ps, 1, D]`` pool pads its unit head
axis and is turned at every call), and every slot's lanes take the dot
fold: all ``C * H`` query rows against the keys in one product, whatever
``q_len``, since a single head already gives a decoding lane ``H`` rows.
Its grid step folds a BLOCK of ``latent_pages_per_step`` pages (128 keys,
never more than the table's width) and not one page: the grid is ``(B,
ceil(W / k))``, the pool stays in HBM where it lies, and the kernel copies
each live page of a block by its own DMA, the next live block's while it
folds this one (``_latent_kernel``).

A WINDOW (ISSUE 34, ``window=`` static; ``None`` = none, the programs
that pass none are the programs they were): a query lane at position
``p`` sees keys in ``(p - window, p]``. The table of such a call STARTS
AT THE WINDOW'S FIRST PAGE: ``table_starts [B]`` int32 gives the logical
page index of each slot's column 0 (a cache that gives window layers'
pages back as the sequence grows holds, and hands in, only the pages from
there on: ``serving/kv_cache.py``), so column ``w`` holds the keys at
positions ``(table_starts[b] + w) * page_size ...``, and the table is as
wide as the window and a chunk, not as the sequence. The work follows the
window as it follows the two lengths: a column every key of which lies
behind the window of the slot's OLDEST live lane (position ``kv_len -
q_len``) names the first live page again and is neither fetched nor
folded.

The single-token form is exactly the chunked form at C=1 with
``q_len = (kv_len > 0)`` — both implementations canonicalize to the
chunked layout internally, so the two forms cannot drift.

``paged_attention`` routes between them via flags (the same
``use_pallas_kernels`` surface that routes flash attention) plus a
``flash_min_seq``-style crossover, ``paged_min_slots``: the kernel
engages at batches of at least that many slots. The cold-cache default
is 1 (kernel at every batch; the kernel-vs-reference crossover has no
chip measurement yet — ROADMAP S2), and the threshold reads through the
autotune cache (``fluid.flags.effective_flag``), so a device kind where
the crossover sits elsewhere re-routes without a code change (ISSUE 8;
Ragged Paged Attention motivates per-chip routing). A caller whose
pools are sharded over a mesh names the reference itself
(``impl="reference"``, see ``paged_route``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....observability import metrics as _metrics

NEG_INF = -1e30

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_route", "folds_by_dot", "paged_latent_attention",
           "paged_latent_attention_reference", "latent_pages_per_step"]

# trace-time routing counters (this function body runs once per
# compiled shape, n_layers times per decoder trace — not per step):
# the autotune per-device-kind override test pins these
_m_route_kernel = _metrics.counter("attention.route.paged_kernel")
_m_route_ref = _metrics.counter("attention.route.paged_reference")


def _check_shapes(q, k_pages, v_pages, page_tables, kv_lens, q_lens):
    if q.ndim not in (3, 4):
        raise ValueError(f"q must be [B, Hq, D] or [B, C, Hq, D], got "
                         f"{q.shape}")
    b = q.shape[0]
    c = q.shape[1] if q.ndim == 4 else 1
    hq, d = q.shape[-2], q.shape[-1]
    p, ps, hkv, d2 = k_pages.shape
    if v_pages.shape != k_pages.shape:
        raise ValueError(f"k_pages {k_pages.shape} != v_pages "
                         f"{v_pages.shape}")
    if d2 != d:
        raise ValueError(f"head_dim mismatch: q has {d}, pages have {d2}")
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads "
                         f"{hkv}")
    if page_tables.shape[0] != b or page_tables.ndim != 2:
        raise ValueError(f"page_tables {page_tables.shape} does not match "
                         f"batch {b}")
    if kv_lens.shape != (b,):
        raise ValueError(f"kv_lens {kv_lens.shape} != ({b},)")
    if q.ndim == 4:
        if q_lens is None:
            raise ValueError("chunked q [B, C, Hq, D] requires q_lens")
        if q_lens.shape != (b,):
            raise ValueError(f"q_lens {q_lens.shape} != ({b},)")
    elif q_lens is not None:
        raise ValueError("q_lens only applies to chunked q [B, C, Hq, D]")
    return b, c, hq, d, ps, hkv, page_tables.shape[1]


def _canon_chunked(q, kv_lens, q_lens):
    """Canonicalize both call forms to (q [B, C, Hq, D], q_lens [B]):
    the single-token form is C=1 with one valid query iff the slot is
    live (kv_len > 0) — the PR 6 dead-slot convention."""
    if q.ndim == 3:
        q = q[:, None]
        q_lens = (kv_lens > 0).astype(jnp.int32)
    return q, q_lens


def _key_limit(kv_len, q_len, lane, block_length: int):
    """The last key position query lane ``lane`` sees. The lane sits at
    absolute position ``pos = kv_len - q_len + lane``. Causal
    (``block_length`` 1): its own, ``pos``. Block diffusion (ISSUE 30,
    ``block_length`` B > 1, chunks of whole blocks starting at a multiple
    of B): causal between blocks and both ways inside one, so the last
    key of its block that exists, ``min(kv_len, (pos // B + 1) * B) -
    1``. The causal form is spelt as it always was: B = 1 traces the
    program it always did."""
    pos = kv_len - q_len + lane
    if block_length == 1:
        return pos
    return jnp.minimum(kv_len,
                       (pos // block_length + 1) * block_length) - 1


def _table_starts(table_starts, b: int):
    """``table_starts`` as [B] int32 (``None``: every table starts at its
    sequence's page 0)."""
    if table_starts is None:
        return jnp.zeros((b,), jnp.int32)
    if table_starts.shape != (b,):
        raise ValueError(f"table_starts {table_starts.shape} != ({b},)")
    return table_starts.astype(jnp.int32)


# --- which fold a slot's live pages take (ISSUE 35) -----------------------
# Measured on the kernel alone on a v5e (PERF.md section 6, PR 35).
# DOT_MIN_LANES: the live lanes at which a slot takes the dot fold: a page
# costs it the same whatever its lanes, the lane loop 0.4 us more a lane,
# and they cross between two lanes and three. DOT_MIN_ROWS: the query rows
# ``C * rep`` a kv head's product needs before the dot fold is traced at
# all, by the pools' dtype. bfloat16: a block of four lanes on a head
# group of eight already wins three to one. float32 products run at
# HIGHEST, six passes each: at the dense family's 16 rows they win at a
# full chunk and lose at eight lanes, and ISSUE 35 holds that geometry to
# the program it has; the number keeps every served float32 geometry
# there and lets tier-1 run the float32 products.
DOT_MIN_LANES = 3
DOT_MIN_ROWS = {"bfloat16": 32, "float32": 512}


def folds_by_dot(c: int, rep: int, dtype, q_len=None):
    """Whether a slot's live pages are folded by matrix products (or lane
    by lane), from what a call carries: its chunk width ``c``, head group
    ``rep = Hq // Hkv`` and pools' ``dtype``, which decide when the
    program is traced whether the dot fold exists in it at all
    (``q_len=None`` asks that alone), and the slot's ``q_len`` (a traced
    scalar in the kernel, a numpy vector in the engine's counter). The
    kernel and ``serving.decode.attn_dot_fold_pct`` both ask here, so
    that what is counted is what ran."""
    traced = c >= DOT_MIN_LANES and c * rep >= DOT_MIN_ROWS.get(
        jnp.dtype(dtype).name, math.inf)
    if q_len is None:
        return traced
    return (q_len >= DOT_MIN_LANES) & traced


def _mxu(a, b, contract):
    """``a . b`` over ``contract`` (one axis of each) on the MXU, float32
    accumulation, neither operand narrowed. Two bfloat16 operands
    multiply exactly in float32 in one pass. A float32 ``b`` beside a
    bfloat16 ``a`` goes as the three bfloat16 terms that hold its 24
    bits, stacked along the contracted axis beside ``a`` three times:
    one product, every partial product exact. Anything else goes as
    float32 at HIGHEST precision."""
    bf16, dims = jnp.bfloat16, (contract, ((), ()))
    if a.dtype == bf16 and b.dtype == jnp.float32:
        terms = []
        for _ in range(3):
            terms.append(b.astype(bf16))
            b = b - terms[-1].astype(jnp.float32)
        a = jnp.concatenate([a] * 3, axis=contract[0][0])
        b = jnp.concatenate(terms, axis=contract[1][0])
    if a.dtype == b.dtype == bf16:
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), dims,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def paged_attention_reference(q, k_pages, v_pages, page_tables, kv_lens,
                              *, q_lens=None,
                              scale: Optional[float] = None,
                              block_length: int = 1,
                              window: Optional[int] = None,
                              table_starts=None):
    """Pure-jax oracle: gather the pages, mask causally past each
    query's visibility limit (and behind its ``window``, with column 0 at
    logical page ``table_starts``), dense softmax. Same signature/semantics
    as the kernel. Returns the same rank as ``q``. Its two dots run at
    HIGHEST precision: the kernel's lane loop multiplies in float32 on
    the VPU and its dot fold narrows no operand of its products on the
    MXU (module docstring), and a float32 oracle that let the TPU's
    default single bf16 pass stand in for float32 could not be compared
    with either (nor serve the same tokens where the engine names it
    under a mesh)."""
    b, c, hq, d, ps, hkv, w = _check_shapes(q, k_pages, v_pages,
                                            page_tables, kv_lens, q_lens)
    squeeze = q.ndim == 3
    q, q_lens = _canon_chunked(q, kv_lens, q_lens)
    scale = float(scale) if scale else d ** -0.5
    rep = hq // hkv
    # [B, W, ps, Hkv, D] -> [B, T, Hkv, D], T = W * ps
    k = k_pages[page_tables].reshape(b, w * ps, hkv, d)
    v = v_pages[page_tables].reshape(b, w * ps, hkv, d)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.astype(jnp.float32) * scale
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bchd,bthd->bcht", qf, k.astype(jnp.float32),
                   precision=hi)
    # visibility: query j (absolute position kv_len - q_len + j) sees
    # keys at positions <= its own (its block's end under block
    # diffusion, _key_limit); dead lanes (j >= q_len) see nothing ->
    # exact-zero rows
    lane = jnp.arange(c)[None, :]                       # [1, C]
    limit = _key_limit(kv_lens[:, None], q_lens[:, None], lane,
                       block_length)                    # [B, C]
    valid = lane < q_lens[:, None]                      # [B, C]
    t = jnp.arange(w * ps)[None, None, :]               # [1, 1, T]
    if window is not None:
        # column 0 is logical page table_starts: key t sits at position
        # table_starts * ps + t, and lane j sees (pos - window, pos]
        t = t + _table_starts(table_starts, b)[:, None, None] * ps
    keep = (t <= limit[:, :, None]) & valid[:, :, None]  # [B, C, T]
    if window is not None:
        pos = kv_lens[:, None] - q_lens[:, None] + lane   # [B, C]
        keep &= t > (pos - int(window))[:, :, None]
    keep = keep[:, :, None, :]                          # [B, C, 1, T]
    s = jnp.where(keep, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * keep
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bcht,bthd->bchd", p, v.astype(jnp.float32),
                   precision=hi)
    o = (o / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)).astype(q.dtype)
    return o[:, 0] if squeeze else o


def _paged_kernel(tables_ref, kv_lens_ref, q_lens_ref, *refs, scale,
                  page_size, rep, block_length, window=None, dot=None):
    """One (sequence b, table column w) grid step: fold this page's keys
    into the running online softmax of the slot's LIVE query lanes. W
    iterates innermost (TPU grids run sequentially), so the scratch
    accumulators carry across a sequence's pages and reset at its
    first. The work follows the two length vectors, not the compiled
    ``(C, W)`` buckets (ISSUE 31): a column past the slot's last live
    page runs the two scalar compares and nothing else (its K/V block is
    the one already fetched, see ``_live_columns``), and the fold walks
    lanes ``0 .. q_len - 1`` only — a decoding slot in a ``C = 16`` step
    pays for one lane. A lane or a slot nothing was folded into keeps
    the zero accumulator and emits exact zeros.

    Two folds (ISSUE 35), one online softmax: ``_fold_lanes`` walks the
    slot's lanes one at a time on the VPU; ``_fold_dot`` takes all of a
    slot's lanes against the page in two matrix products a kv head. A
    program whose geometry ``folds_by_dot`` rejects (``dot`` is None)
    traces the lane loop alone, as it always did; in one that it accepts
    (``dot`` is the static triple ``(c, rep, dtype)``) each slot takes,
    for all its pages, the fold the same predicate names for its
    ``q_len``, and the two folds have their own operands, scratch and
    output (``_paged_attention_pallas`` says how they are joined).

    Under a ``window`` a fourth prefetched vector, the logical page of
    each slot's column 0, leads ``refs``: column ``w`` is page
    ``starts[b] + w``, a column wholly behind the oldest live lane's
    window is skipped like one past ``kv_len``, and a lane keeps the keys
    in ``(pos - window, pos]``."""
    if window is not None:
        starts_ref, *refs = refs
    if dot is None:
        q_ref, k_ref, v_ref, o_ref, *lane_sc = refs
        folds = [(lane_sc, o_ref)]
    else:
        (q_ref, qg_ref, k_ref, v_ref, o_ref, og_ref, *sc) = refs
        lane_sc, dot_sc = sc[:3], sc[3:]
        folds = [(lane_sc, o_ref), (dot_sc, og_ref)]
    w = pl.program_id(1)
    nw = pl.num_programs(1)

    @pl.when(w == 0)
    def _init():
        for (m_sc, l_sc, acc_sc), _out in folds:
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
            acc_sc[...] = jnp.zeros_like(acc_sc)

    b = pl.program_id(0)
    kv_len = kv_lens_ref[b]
    q_len = q_lens_ref[b]

    if window is None:
        first_page, live = w, w * page_size < kv_len
    else:
        first_page = starts_ref[b] + w
        live = (first_page * page_size < kv_len) & (
            (first_page + 1) * page_size
            > _window_floor(kv_len, q_len, window))

    def _fold_lanes():
        """The page stays ``[ps, H, D]`` as it lies in the pool, K and V
        repeated to the query heads: a lane's scores are a reduce over D
        of ``q[None] * k`` (``[ps, H, 1]``), and the softmax statistics
        and ``p . v`` reduce over the page's rows, the LEADING axis —
        plain adds of whole registers, no transpose. All float32 on the
        VPU."""
        m_sc, l_sc, acc_sc = lane_sc
        k = k_ref[0].astype(jnp.float32)              # [ps, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)            # [ps, Hq, D]
            v = jnp.repeat(v, rep, axis=1)
        # this page covers absolute key positions [w*ps, w*ps + ps)
        offs = first_page * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1, 1), 0)          # [ps, 1, 1]

        def _lane(j, carry):
            # query lane j sits at absolute position kv_len - q_len + j
            # and sees keys at positions <= its own (chunk-causal; up
            # to its block's end under block diffusion, _key_limit)
            keep = offs <= _key_limit(kv_len, q_len, j, block_length)
            if window is not None:
                keep &= offs > kv_len - q_len + j - window
            q = q_ref[0, j].astype(jnp.float32) * scale   # [Hq, D]
            # s[p, h] = q[h, :] . k[p, h, :]  (float32 on the VPU:
            # elementwise + reduce)
            s = jnp.sum(q[None] * k, axis=-1, keepdims=True)  # [ps, Hq, 1]
            s = jnp.where(keep, s, NEG_INF)
            m_old = m_sc[j]                           # [Hq, 1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=0))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new[None]) * keep       # [ps, Hq, 1]
            m_sc[j] = m_new
            l_sc[j] = l_sc[j] * alpha + jnp.sum(p, axis=0)
            # pv[h, d] = sum_p p[p, h] * v[p, h, d]
            acc_sc[j] = acc_sc[j] * alpha + jnp.sum(p * v, axis=0)
            return carry

        jax.lax.fori_loop(0, q_len, _lane, 0)

    def _fold_dot():
        """All of the slot's lanes against the page, a kv head at a
        time: the head's ``N = C * rep`` query rows (lane ``c``, query
        head ``g * rep + r`` is row ``c * rep + r``; no repeat of K or
        V) lie along the LANES of everything the fold keeps, so the
        scores are ``[ps, N]``, the statistics ``[1, N]`` and the
        accumulator ``[D, N]``, transposed once a call by the wrapper.
        Two products on the MXU with float32 accumulation and no
        operand narrowed (``_mxu``); mask and statistics as the lane
        loop's, vectorised over lanes."""
        m_sc, l_sc, acc_sc = dot_sc
        n = acc_sc.shape[-1]
        # [Hkv, ps, D]: a head's keys as one matrix
        k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)
        v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)
        offs = first_page * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)             # [ps, 1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) // rep
        keep = (offs <= _key_limit(kv_len, q_len, lane, block_length)) & (
            lane < q_len)                             # [ps, N]
        if window is not None:
            keep &= offs > kv_len - q_len + lane - window
        for g in range(k.shape[0]):
            # s[p, n] = k[p, :] . q[n, :]
            s = _mxu(k[g].astype(k_ref.dtype), qg_ref[0, g],
                     ((1,), (1,))) * scale            # [ps, N]
            s = jnp.where(keep, s, NEG_INF)
            m_old = m_sc[g]                           # [1, N]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new) * keep             # [ps, N]
            m_sc[g] = m_new
            l_sc[g] = l_sc[g] * alpha + jnp.sum(p, axis=0, keepdims=True)
            # pv[d, n] = sum_p v[p, d] * p[p, n]
            acc_sc[g] = acc_sc[g] * alpha + _mxu(
                v[g].astype(v_ref.dtype), p, ((0,), (0,)))

    if dot is None:
        pl.when(live)(_fold_lanes)
    else:
        @pl.when(live)
        def _fold():
            jax.lax.cond(folds_by_dot(*dot, q_len), _fold_dot, _fold_lanes)

    @pl.when(w == nw - 1)
    def _emit():
        for (_m, l_sc, acc_sc), out in folds:
            l = jnp.maximum(l_sc[...], jnp.finfo(jnp.float32).tiny)
            out[0] = (acc_sc[...] / l).astype(out.dtype)


def _window_floor(kv_len, q_len, window: int):
    """The first key position a call's slot can see under ``window``: the
    oldest live lane sits at ``kv_len - q_len`` and sees ``window`` keys,
    its own among them."""
    return jnp.maximum(kv_len - q_len - window + 1, 0)


def _live_columns(tables, kv_lens, page_size: int, first=None, floor=None):
    """The page table with every column past a slot's last live page
    naming that last page again (and, under a window, every column before
    the first page the slot's oldest lane sees naming that one: ``first``
    [B] is column 0's logical page, ``floor`` [B] the first key position
    in view). The grid walks all W columns of every
    slot; consecutive grid steps on one block make the pipeline issue no
    new DMA, so neither the table's garbage columns nor a page the slot
    holds past ``kv_len`` is ever fetched (a dead slot fetches its
    column 0 once and folds nothing). Done here, once a call, and not in
    the index map, which runs twice every grid step."""
    last = jnp.maximum(pl.cdiv(kv_lens, page_size) - 1, 0)       # [B]
    col = jnp.arange(tables.shape[1], dtype=jnp.int32)[None]     # [1, W]
    if first is None:
        return jnp.take_along_axis(
            tables, jnp.minimum(col, last[:, None]), axis=1)
    lo = jnp.clip(floor // page_size - first, 0, tables.shape[1] - 1)
    hi = jnp.maximum(last - first, lo)
    return jnp.take_along_axis(
        tables, jnp.clip(col, lo[:, None], hi[:, None]), axis=1)


def _paged_attention_pallas(q, k_pages, v_pages, page_tables, kv_lens,
                            *, q_lens=None,
                            scale: Optional[float] = None,
                            interpret: bool = False,
                            block_length: int = 1,
                            window: Optional[int] = None,
                            table_starts=None):
    b, c, hq, d, _ps, hkv, _w = _check_shapes(q, k_pages, v_pages,
                                              page_tables, kv_lens, q_lens)
    squeeze = q.ndim == 3
    q, q_lens = _canon_chunked(q, kv_lens, q_lens)
    # a program that holds the dot fold lowers three times as slowly: its
    # layers share one trace and one lowering (as moe_gmm's do)
    call = (_paged_call_shared if folds_by_dot(c, hq // hkv, k_pages.dtype)
            else _paged_call)
    out = call(q, k_pages, v_pages, page_tables.astype(jnp.int32),
               kv_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
               None if window is None else _table_starts(table_starts, b),
               scale=float(scale) if scale else d ** -0.5,
               interpret=interpret, block_length=int(block_length),
               window=None if window is None else int(window))
    return out[:, 0] if squeeze else out


def _paged_call(q, k_pages, v_pages, tables, kv_l, q_l, starts, *, scale,
                interpret, block_length, window):
    """The kernel's call on canonical operands: ``q [B, C, Hq, D]``, int32
    tables and lengths, ``starts`` a windowed call's ``table_starts``."""
    b, c, hq, d = q.shape
    _pages, ps, hkv, _d = k_pages.shape
    w = tables.shape[1]
    rep = hq // hkv
    if window is None:
        prefetch = (_live_columns(tables, kv_l, ps), kv_l, q_l)
    else:
        prefetch = (_live_columns(tables, kv_l, ps, starts,
                                  _window_floor(kv_l, q_l, window)),
                    kv_l, q_l, starts)
    dot = (c, rep, k_pages.dtype)
    if not folds_by_dot(*dot):
        dot = None
    # the lane loop's operands: q and the output as the caller has them,
    # the lane the leading index, so the fold takes one lane's [Hq, .]
    # slab by a dynamic first-axis index. In a program that has the dot
    # fold the loop walks slots of under DOT_MIN_LANES lanes only, and its
    # operands are cut to those
    cl = c if dot is None else min(c, DOT_MIN_LANES - 1)
    by_slot = lambda *block: pl.BlockSpec(
        (1,) + block, lambda bb, ww, *_: (bb,) + (0,) * len(block))
    # THE paged read: the index map picks each sequence's w-th live page
    # out of the pool (_live_columns)
    page = pl.BlockSpec((1, ps, hkv, d),
                        lambda bb, ww, t, *_: (t[bb, ww], 0, 0, 0))
    operands, in_specs = [q if cl == c else q[:, :cl]], [by_slot(cl, hq, d)]
    out_specs = [by_slot(cl, hq, d)]
    out_shape = [jax.ShapeDtypeStruct((b, cl, hq, d), q.dtype)]
    scratch = [pltpu.VMEM((cl, hq, 1), jnp.float32),    # running max
               pltpu.VMEM((cl, hq, 1), jnp.float32),    # running sum
               pltpu.VMEM((cl, hq, d), jnp.float32)]    # accumulator
    if dot is not None:
        # the dot fold's: a kv head's C * rep query rows as one matrix
        # (row c * rep + r), and its output with those rows along the
        # lanes, [Hkv, D, N]: both turned here, once a call, by XLA
        n = c * rep
        operands.append(q.reshape(b, c, hkv, rep, d).transpose(
            0, 2, 1, 3, 4).reshape(b, hkv, n, d))
        in_specs.append(by_slot(hkv, n, d))
        out_specs.append(by_slot(hkv, d, n))
        out_shape.append(jax.ShapeDtypeStruct((b, hkv, d, n), q.dtype))
        scratch += [pltpu.VMEM((hkv, 1, n), jnp.float32),
                    pltpu.VMEM((hkv, 1, n), jnp.float32),
                    pltpu.VMEM((hkv, d, n), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # page_tables, kv_lens, q_lens (and a window's table_starts) in
        # SMEM
        num_scalar_prefetch=len(prefetch),
        grid=(b, w),
        in_specs=in_specs + [page, page],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=ps, rep=rep,
        block_length=block_length, window=window, dot=dot)
    out, *by_dot = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        # the kernel's name in the compiled program and a device trace
        name="paged_attention",
    )(*prefetch, *operands, k_pages, v_pages)
    if by_dot:
        # each slot's output is the one its fold wrote
        out = jnp.where(
            folds_by_dot(*dot, q_l)[:, None, None, None],
            by_dot[0].reshape(b, hkv, d, c, rep).transpose(
                0, 3, 1, 4, 2).reshape(b, c, hq, d),
            jnp.pad(out, ((0, 0), (0, c - cl), (0, 0), (0, 0))))
    return out


_paged_call_shared = jax.jit(_paged_call, static_argnames=(
    "scale", "interpret", "block_length", "window"))


def paged_route(slots: int, impl: Optional[str] = None) -> str:
    """Name of the implementation a ``[slots, ...]`` batch takes:
    ``"paged_kernel"`` or ``"paged_reference"``. ``impl="reference"``
    is the caller choosing the reference BY NAME — the decode engine
    does under a mesh, because a Mosaic kernel has no SPMD partitioning
    rule and must not be handed to GSPMD. ``impl=None`` lets the flags
    decide: the ``use_pallas_kernels`` surface flash attention uses,
    plus the ``paged_min_slots`` crossover read through the autotune
    cache per device kind."""
    from ...flags import effective_flag, pallas_enabled

    if impl not in (None, "reference"):
        raise ValueError(f"paged attention impl must be None or "
                         f"'reference', got {impl!r}")
    if impl is None and pallas_enabled() and \
            slots >= int(effective_flag("paged_min_slots")):
        return "paged_kernel"
    return "paged_reference"


def paged_attention(q, k_pages, v_pages, page_tables, kv_lens,
                    *, q_lens=None, scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    impl: Optional[str] = None,
                    block_length: int = 1,
                    window: Optional[int] = None,
                    table_starts=None):
    """Route between the Pallas kernel (compiled on TPU; interpret mode
    off-TPU when forced via ``use_pallas_kernels=True`` for tests) and
    the pure-jax reference, as ``paged_route`` names it; every trace
    counts its route. ``q`` may be ``[B, Hq, D]`` (one token per slot)
    or ``[B, C, Hq, D]`` with ``q_lens`` (a prefill chunk per slot,
    causal within the chunk). ``block_length`` (static) is the mask's
    block: 1 is causal; B > 1 lets a lane see its whole block of B
    (``_key_limit``), for chunks of whole blocks. ``window`` (static;
    causal masks only) keeps a lane's newest ``window`` keys, its own
    among them, and ``table_starts [B]`` then says at which logical page
    each slot's table begins (module docstring)."""
    from ...flags import pallas_interpret

    if window is None:
        if table_starts is not None:
            raise ValueError("table_starts is a windowed call's argument")
        more = {}
    else:
        if int(window) < 1 or block_length != 1:
            raise ValueError(
                f"window must be >= 1 under the causal mask, got window "
                f"{window} with block_length {block_length}")
        more = {"window": int(window), "table_starts": table_starts}

    if paged_route(q.shape[0], impl) == "paged_kernel":
        _m_route_kernel.inc()
        return _paged_attention_pallas(
            q, k_pages, v_pages, page_tables, kv_lens, q_lens=q_lens,
            scale=scale,
            interpret=pallas_interpret() if interpret is None
            else interpret, block_length=block_length, **more)
    _m_route_ref.inc()
    return paged_attention_reference(q, k_pages, v_pages, page_tables,
                                     kv_lens, q_lens=q_lens, scale=scale,
                                     block_length=block_length, **more)


# --- a latent pool: one key a token, multi-query -------------------------

def paged_latent_attention_reference(q, pages, page_tables, kv_lens, *,
                                     q_lens, scale: float, value_dim: int):
    """Pure-jax oracle of ``paged_latent_attention``: gather the pages,
    causal mask, dense softmax at HIGHEST. ``q [B, C, H, D]``, ``pages
    [P, ps, D]``; returns ``[B, C, H, value_dim]`` in q's dtype."""
    b, c, h, d = q.shape
    _p, ps, _d = pages.shape
    w = page_tables.shape[1]
    keys = pages[page_tables].reshape(b, w * ps, d).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bchd,btd->bcht", q.astype(jnp.float32) * scale, keys,
                   precision=hi)
    lane = jnp.arange(c)[None, :]
    limit = _key_limit(kv_lens[:, None], q_lens[:, None], lane, 1)
    t = jnp.arange(w * ps)[None, None, :]
    keep = ((t <= limit[:, :, None])
            & (lane < q_lens[:, None])[:, :, None])[:, :, None, :]
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)) * keep
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bcht,btv->bchv", p, keys[..., :value_dim], precision=hi)
    return (o / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)).astype(q.dtype)


# keys a grid step of the latent kernel folds: a block of whole pages
LATENT_BLOCK_KEYS = 128


def latent_pages_per_step(page_size: int, width: int) -> int:
    """Pages of a slot's table the latent kernel folds a grid step: as
    many as make ``LATENT_BLOCK_KEYS`` keys, never more than the table's
    ``width`` columns. The kernel and
    ``serving.decode.attn_latent_steps_live_pct`` both ask here."""
    return max(1, min(LATENT_BLOCK_KEYS // page_size, width))


def _latent_kernel(tables_ref, kv_lens_ref, q_lens_ref, first_ref, after_ref,
                   q_ref, pool_ref, o_ref, buf, sems, m_sc, l_sc, acc_sc, *,
                   scale, page_size, pages, heads, value_dim):
    """One (sequence b, block j) grid step of the latent form: the
    block's ``pages`` pages, ``bk = pages * page_size`` keys, folded into
    the online softmax of all the slot's ``N = C * H`` query rows at once
    (row ``c * H + h``), which lie along the LANES of everything it keeps
    (scores ``[bk, N]``, statistics ``[1, N]``, accumulator ``[value_dim,
    N]``), as ``_fold_dot`` has them. Two products on the MXU a block,
    float32 accumulation, no operand narrowed (``_mxu``). A block past
    ``kv_len`` runs one compare.

    The pool stays in HBM: each live page of a block is copied by its own
    DMA into one half of a double buffer ``buf [2, pages, ps, D]``, with a
    semaphore a page. The live blocks of a call form one chain, slot by
    slot (``first_ref[b]`` is the chain's index of slot b's first block,
    ``after_ref[b]`` the next slot with a live block, B where none is): a
    live block starts the copies of the next one into the other half
    before it waits for its own, and the chain's first starts its own.
    Pages past ``kv_len`` are neither copied nor waited for, and their
    rows are zeroed before the fold, so whatever the buffer held there
    cannot reach the output."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(0)
    bk = pages * page_size
    kv_len = kv_lens_ref[b]
    q_len = q_lens_ref[b]

    def _copies(bb, jj, half):
        """The block's page copies, each with whether its page is live."""
        out = []
        for i in range(pages):
            col = jj * pages + i
            out.append((col * page_size < kv_lens_ref[bb],
                        pltpu.make_async_copy(pool_ref.at[tables_ref[bb, col]],
                                              buf.at[half, i],
                                              sems.at[half, i])))
        return out

    def _start(bb, jj, half):
        for live, copy in _copies(bb, jj, half):
            pl.when(live)(copy.start)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(j * bk < kv_len)
    def _fold():
        link = first_ref[b] + j
        half = link % 2

        @pl.when(link == 0)
        def _first():
            _start(b, j, half)

        more = (j + 1 < pl.num_programs(1)) & ((j + 1) * bk < kv_len)
        next_b = jnp.where(more, b, after_ref[b])

        @pl.when(next_b < nb)
        def _next():
            _start(next_b, jnp.where(more, j + 1, 0), 1 - half)

        for live, copy in _copies(b, j, half):
            pl.when(live)(copy.wait)
        n = acc_sc.shape[-1]
        offs = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        keys = buf[half].reshape(bk, -1)                   # [bk, D]
        keys = jnp.where(offs < kv_len, keys, jnp.zeros_like(keys))
        s = _mxu(keys, q_ref[0], ((1,), (1,))) * scale     # [bk, N]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) // heads
        keep = (offs <= _key_limit(kv_len, q_len, lane, 1)) & (lane < q_len)
        s = jnp.where(keep, s, NEG_INF)
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new) * keep
        m_sc[...] = m_new
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        # pv[v, n] = sum_t keys[t, v] * p[t, n], the value the key's first
        # value_dim columns
        acc_sc[...] = acc_sc[...] * alpha + _mxu(
            keys[:, :value_dim], p, ((0,), (0,)))

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        l = jnp.maximum(l_sc[...], jnp.finfo(jnp.float32).tiny)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "value_dim",
                                             "interpret"))
def _latent_call(q, pages, tables, kv_l, q_l, *, scale, value_dim,
                 interpret):
    b, c, h, d = q.shape
    _p, ps, _d = pages.shape
    w = tables.shape[1]
    n = c * h
    k = latent_pages_per_step(ps, w)
    nj = pl.cdiv(w, k)
    # the table padded to whole blocks. A column past a slot's kv_len is
    # never read from the pool: the kernel copies live pages only, so the
    # table goes in as it is (no _live_columns gather)
    tables = jnp.pad(tables, ((0, 0), (0, nj * k - w)), mode="edge")
    # the chain of live blocks (_latent_kernel): each slot's first link
    # and the next slot that has one
    blocks = jnp.minimum(pl.cdiv(kv_l, k * ps), nj)
    first = jnp.cumsum(blocks) - blocks
    slot = jnp.arange(b, dtype=jnp.int32)
    after = jax.lax.cummin(jnp.where(blocks > 0, slot, b), reverse=True)
    after = jnp.concatenate([after[1:], jnp.full((1,), b, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, nj),
        in_specs=[pl.BlockSpec((1, n, d), lambda bb, jj, *_: (bb, 0, 0)),
                  # THE paged read: the pool where it lies, copied page by
                  # page by the kernel
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, value_dim, n),
                               lambda bb, jj, *_: (bb, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, k, ps, d), pages.dtype),
                        pltpu.SemaphoreType.DMA((2, k)),
                        pltpu.VMEM((1, n), jnp.float32),
                        pltpu.VMEM((1, n), jnp.float32),
                        pltpu.VMEM((value_dim, n), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, page_size=ps,
                          pages=k, heads=h, value_dim=value_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, value_dim, n), q.dtype),
        # a copy started in one grid step is waited for in a later one:
        # the steps run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # the kernel's name in the compiled program and a device trace
        name="paged_latent_attention",
    )(tables, kv_l, q_l, first.astype(jnp.int32), after, q.reshape(b, n, d),
      pages)
    return out.reshape(b, value_dim, c, h).transpose(0, 2, 3, 1)


def paged_latent_attention(q, pages, page_tables, kv_lens, *, q_lens,
                           scale: float, value_dim: int,
                           interpret: Optional[bool] = None,
                           impl: Optional[str] = None):
    """Causal attention of ``q [B, C, H, D]`` (``q_lens`` live lanes a
    slot) over ONE key a token read through the page tables from a latent
    pool ``pages [P, ps, D]``, the value the key's first ``value_dim``
    columns: ``[B, C, H, value_dim]`` in q's dtype, dead lanes exact
    zeros. Routed as ``paged_attention`` is (``paged_route``)."""
    from ...flags import pallas_interpret

    if pages.ndim != 3 or q.ndim != 4 or q.shape[-1] != pages.shape[-1]:
        raise ValueError(f"a latent pool is [P, ps, D] and q [B, C, H, D]; "
                         f"got pages {pages.shape}, q {q.shape}")
    if not 0 < value_dim <= q.shape[-1]:
        raise ValueError(f"value_dim {value_dim} outside (0, {q.shape[-1]}]")
    args = (q, pages, page_tables.astype(jnp.int32),
            kv_lens.astype(jnp.int32), q_lens.astype(jnp.int32))
    if paged_route(q.shape[0], impl) == "paged_kernel":
        _m_route_kernel.inc()
        return _latent_call(*args, scale=float(scale),
                            value_dim=int(value_dim),
                            interpret=pallas_interpret() if interpret is None
                            else bool(interpret))
    _m_route_ref.inc()
    return paged_latent_attention_reference(
        *args[:4], q_lens=args[4], scale=float(scale),
        value_dim=int(value_dim))
