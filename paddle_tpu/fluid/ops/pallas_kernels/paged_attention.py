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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....observability import metrics as _metrics

NEG_INF = -1e30

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_route"]

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
    HIGHEST precision: the kernel multiplies in float32 on the VPU, and
    a float32 oracle that let the TPU's default single bf16 pass stand
    in for float32 could not be compared with it (nor serve the same
    tokens where the engine names it under a mesh)."""
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
                  page_size, rep, block_length, window=None):
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

    The page stays ``[ps, H, D]`` as it lies in the pool: a lane's
    scores are a reduce over D of ``q[None] * k`` (``[ps, H, 1]``), and
    the softmax statistics and ``p . v`` reduce over the page's rows,
    the LEADING axis — plain adds of whole registers, no transpose.

    Under a ``window`` a fourth prefetched vector, the logical page of
    each slot's column 0, leads ``refs``: column ``w`` is page
    ``starts[b] + w``, a column wholly behind the oldest live lane's
    window is skipped like one past ``kv_len``, and a lane keeps the keys
    in ``(pos - window, pos]``."""
    if window is not None:
        starts_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc = refs
    w = pl.program_id(1)
    nw = pl.num_programs(1)

    @pl.when(w == 0)
    def _init():
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

    @pl.when(live)
    def _fold():
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

    @pl.when(w == nw - 1)
    def _emit():
        l = jnp.maximum(l_sc[...], jnp.finfo(jnp.float32).tiny)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


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
    b, c, hq, d, ps, hkv, w = _check_shapes(q, k_pages, v_pages,
                                            page_tables, kv_lens, q_lens)
    squeeze = q.ndim == 3
    q, q_lens = _canon_chunked(q, kv_lens, q_lens)
    scale = float(scale) if scale else d ** -0.5
    rep = hq // hkv
    kv_l = kv_lens.astype(jnp.int32)
    q_l = q_lens.astype(jnp.int32)
    if window is None:
        prefetch = (_live_columns(page_tables.astype(jnp.int32), kv_l, ps),
                    kv_l, q_l)
    else:
        starts = _table_starts(table_starts, b)
        prefetch = (_live_columns(page_tables.astype(jnp.int32), kv_l, ps,
                                  starts, _window_floor(kv_l, q_l,
                                                        int(window))),
                    kv_l, q_l, starts)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # page_tables, kv_lens, q_lens (and a window's table_starts) in
        # SMEM
        num_scalar_prefetch=len(prefetch),
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((1, c, hq, d), lambda bb, ww, *_: (bb, 0, 0, 0)),
            # THE paged read: the index map picks each sequence's w-th
            # live page out of the pool (_live_columns)
            pl.BlockSpec((1, ps, hkv, d),
                         lambda bb, ww, t, *_: (t[bb, ww], 0, 0, 0)),
            pl.BlockSpec((1, ps, hkv, d),
                         lambda bb, ww, t, *_: (t[bb, ww], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, hq, d),
                               lambda bb, ww, *_: (bb, 0, 0, 0)),
        # the lane is the leading index, so the fold takes one lane's
        # [Hq, .] slab by a dynamic first-axis index
        scratch_shapes=[
            pltpu.VMEM((c, hq, 1), jnp.float32),    # running max
            pltpu.VMEM((c, hq, 1), jnp.float32),    # running sum
            pltpu.VMEM((c, hq, d), jnp.float32),    # output accumulator
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=ps, rep=rep,
        block_length=int(block_length),
        window=None if window is None else int(window))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, hq, d), q.dtype),
        interpret=interpret,
        # the kernel's name in the compiled program and a device trace
        name="paged_attention",
    )(*prefetch, q, k_pages, v_pages)
    return out[:, 0] if squeeze else out


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
