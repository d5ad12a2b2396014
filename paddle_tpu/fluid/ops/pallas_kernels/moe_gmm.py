"""Grouped expert products for a served sparse-expert layer (ISSUE 33):
``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``, the rows
already sorted by group, as ``jax.lax.ragged_dot`` — tiled for SERVING,
where a step hands each expert a handful of rows and the products are a
stream of expert weights.

Layouts:

    lhs      [M, K]       rows sorted by expert; rows ``>= counts.sum()``
                          belong to no expert (dead lanes sort there)
    rhs      [E, K, N]    expert-major weights as the checkpoint holds them
    counts   [E] int32    rows of each expert's group, ``sum <= M``

XLA's own ``ragged_dot`` on a v5e is a Mosaic grouped product tiled
``512 x 512 x 256``: every group it meets costs a 512-row tile's
multiplications in twelve grid steps, 30 % of the weight stream's speed at
4-5 rows a group (PERF.md §6, PR 33). Here
the grid walks the (row tile, group) VISITS that hold a live row, from
scalar-prefetched metadata (``group_visits``): an expert with no row is
never visited and none of its weights are fetched, a row tile behind the
last group is never visited, and a visit multiplies ``ROW_TILE`` rows. The
weight block is the expert's whole ``[K, tn]`` panel (all of ``[K, N]``
where it fits ``PANEL_BYTES``), so a fetch is megabytes long and a product
is a few hundred grid steps. Visits are ordered by row tile, then group: a
group that straddles two row tiles is two CONSECUTIVE visits naming the
same weight block, which the pipeline does not fetch again; consecutive
visits of one row tile keep its rows and its output block in VMEM. So
every touched expert's weights are read once, and nothing else twice.

The gated form (``gate=``) is a SwiGLU's first half in one call: the rows
are read once and ``silu(lhs @ gate[g]) * (lhs @ rhs[g])`` is written in
the output dtype (both products accumulated in float32, the gated product
rounded once), with no float32 round trip between.

Rows are independent: what an unvisited or dead row holds, NaN included,
reaches no other row. Inside a visited row tile the rows of no group are
written as zeros; a row tile never visited is never written (the caller
never lets such a row reach a result: ``moe_layer`` masks a dead lane's
gathered rows after its combine).

``moe_route`` names the implementation as ``paged_route`` does for the
attention: the kernel on a TPU (``use_pallas_kernels``) at widths that are
multiples of 128, ``ragged_dot`` elsewhere and where the caller names the
reference (the decode engine under a mesh: a Mosaic kernel has no SPMD
partitioning rule).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....observability import metrics as _metrics

__all__ = ["moe_gmm", "grouped_dot", "group_visits", "moe_route"]

# rows a visit multiplies. A step carries 4-5 rows an expert (~26 in the
# fullest), so a visit's rows are mostly masked whatever the tile, and the
# weight stream hides the MXU's time for them: on the v5e 32, 64 and 128
# read within 2 % of each other, 64 never the slowest; 16 pays for its
# straddling visits and 256 for its rows (the table: PERF.md §6, PR 33)
ROW_TILE = 64
# the largest weight block, bytes: one expert's [K, tn] panel. Double
# buffered, twice for the gated form: 4 panels of 3 MB at the served
# widths. Half panels (tn 384, 1024) measured 2-3 % slower
PANEL_BYTES = 4 * 1024 * 1024
LANES = 128

# trace-time routing counters, as attention.route.paged_*: the body runs
# once per compiled shape and layer, not per step
_m_route_kernel = _metrics.counter("moe.route.gmm_kernel")
_m_route_ragged = _metrics.counter("moe.route.ragged_dot")


def group_visits(counts, rows: int, tm: int):
    """The (row tile, group) pairs that hold a live row, in the order the
    grid walks them: ``(offsets [E+1], groups [V], tiles [V], n)``, all
    int32, ``V = rows/tm + E - 1`` the most there can be and ``n >= 1`` how
    many there are. Group ``g`` owns rows ``[offsets[g], offsets[g+1])``
    and is visited once per row tile it touches, an empty group never.
    Entries past ``n`` repeat in-range indices; with no live row at all
    the one visit left is an empty group's, which writes zeros."""
    e = counts.shape[0]
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    spans = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(spans)
    v = jnp.arange(rows // tm + e - 1, dtype=jnp.int32)
    # visit v is group g's where visit_ends[g-1] <= v < visit_ends[g]: a
    # count over [V, E], one fusion where a binary search is a device loop
    groups = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        e - 1)
    tiles = first[groups] + v - (visit_ends - spans)[groups]
    tiles = jnp.clip(tiles, 0, rows // tm - 1)
    return offsets, groups, tiles, jnp.maximum(visit_ends[-1], 1)


def _gmm_kernel(offsets_ref, groups_ref, tiles_ref, x_ref, *refs, tm: int):
    """One visit: the row tile's ``[tm, K]`` rows times the group's
    ``[K, tn]`` panel, stored where the rows are the group's. ``refs`` is
    ``(w, out)`` or, gated, ``(gate, w, out)``."""
    *w_refs, o_ref = refs
    v = pl.program_id(1)
    g, t = groups_ref[v], tiles_ref[v]
    x = x_ref[...]
    y = jnp.dot(x, w_refs[-1][...], preferred_element_type=jnp.float32)
    if len(w_refs) == 2:
        gate = jnp.dot(x, w_refs[0][...],
                       preferred_element_type=jnp.float32)
        y = gate * jax.nn.sigmoid(gate) * y
    y = y.astype(o_ref.dtype)
    row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    # the output block stays in VMEM over a row tile's consecutive
    # visits: the first lays zeros under its group's rows, the others
    # keep what the groups before them stored
    opens_tile = (v == 0) | (tiles_ref[jnp.maximum(v - 1, 0)] != t)

    @pl.when(opens_tile)
    def _first():
        o_ref[...] = jnp.where(mine, y, jnp.zeros_like(y))

    @pl.when(jnp.logical_not(opens_tile))
    def _later():
        o_ref[...] = jnp.where(mine, y, o_ref[...])


def _panel_width(k: int, n: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``n`` whose ``[k, tn]``
    panel fits ``PANEL_BYTES`` (at least one lane tile)."""
    fits = [tn for tn in range(LANES, n + 1, LANES)
            if n % tn == 0 and k * tn * itemsize <= PANEL_BYTES]
    return max(fits, default=LANES)


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile"))
def moe_gmm(lhs, rhs, counts, *, gate=None, interpret: bool = False,
            row_tile: Optional[int] = None):
    """The Pallas grouped product (module docstring): float32 out (the
    accumulator's), the gated form in ``lhs.dtype``. ``K`` and ``N`` must
    be multiples of 128. ``row_tile`` is the measurement's and the tests'
    (default ``ROW_TILE``). Jitted, so that a step's layers share one
    trace and one lowering of each form: un-jitted, the twelve kernels of
    a six-layer step added 0.5 s to every program's lowering, 7 s of the
    block cell's warm."""
    m, k = lhs.shape
    e, k2, n = rhs.shape
    if k2 != k or counts.shape != (e,):
        raise ValueError(f"moe_gmm: lhs {lhs.shape}, rhs {rhs.shape}, "
                         f"counts {counts.shape} do not agree")
    if gate is not None and gate.shape != rhs.shape:
        raise ValueError(f"moe_gmm: gate {gate.shape} != rhs {rhs.shape}")
    if k % LANES or n % LANES:
        raise ValueError(f"moe_gmm: K {k} and N {n} must be multiples of "
                         f"{LANES}")
    out_dtype = jnp.dtype(jnp.float32 if gate is None else lhs.dtype)
    tm = int(row_tile or ROW_TILE)
    rows = pl.cdiv(m, tm) * tm
    if rows != m:
        lhs = jnp.pad(lhs, ((0, rows - m), (0, 0)))
    w_size = jnp.dtype(rhs.dtype).itemsize
    tn = _panel_width(k, n, w_size)
    weights = (rhs,) if gate is None else (gate, rhs)
    offsets, groups, tiles, n_visits = group_visits(counts, rows, tm)
    panel = pl.BlockSpec((None, k, tn), lambda j, v, o, g, t: (g[v], 0, j))
    # double-buffered blocks plus the float32 products of one visit
    vmem = (2 * (len(weights) * k * tn * w_size
                 + tm * k * jnp.dtype(lhs.dtype).itemsize
                 + tm * tn * out_dtype.itemsize)
            + (len(weights) + 2) * tm * tn * 4)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # offsets, groups, tiles in SMEM
            grid=(n // tn, n_visits),   # the walk is as long as the work
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda j, v, o, g, t: (t[v], 0))]
            + [panel] * len(weights),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, o, g, t: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem * 1.25) + (4 << 20)),
        interpret=interpret,
        # the kernel's name in the compiled program and a device trace
        name="moe_gmm",
    )(offsets, groups, tiles, lhs, *weights)
    return out if rows == m else out[:m]


def moe_route(k: int, n: int, impl: Optional[str] = None) -> str:
    """Name of the implementation a grouped product over ``[E, k, n]``
    weights takes: ``"gmm_kernel"`` or ``"ragged_dot"``. ``impl`` is what
    ``paged_route`` takes: ``"reference"`` is the caller choosing XLA's
    product by name (the decode engine under a mesh), ``None`` lets
    ``use_pallas_kernels`` and the widths decide."""
    from ...flags import pallas_enabled

    if impl not in (None, "reference"):
        raise ValueError(f"grouped product impl must be None or "
                         f"'reference', got {impl!r}")
    if impl is None and pallas_enabled() and not (k % LANES or n % LANES):
        return "gmm_kernel"
    return "ragged_dot"


def grouped_dot(lhs, rhs, counts, *, gate=None,
                impl: Optional[str] = None):
    """Route between the kernel (compiled on a TPU; interpret mode off it
    when ``use_pallas_kernels`` is forced, for tests) and
    ``jax.lax.ragged_dot``, as ``moe_route`` names it; every trace counts
    its route. Same arguments and result as ``moe_gmm``, but that the
    rows of no group are exact zeros under ``ragged_dot`` and whatever a
    row tile held under the kernel."""
    from ...flags import pallas_interpret

    if moe_route(lhs.shape[1], rhs.shape[2], impl) == "gmm_kernel":
        _m_route_kernel.inc()
        return moe_gmm(lhs, rhs, counts, gate=gate,
                       interpret=pallas_interpret())
    _m_route_ragged.inc()
    y = jax.lax.ragged_dot(lhs, rhs, counts,
                           preferred_element_type=jnp.float32)
    if gate is None:
        return y
    return (jax.nn.silu(jax.lax.ragged_dot(
        lhs, gate, counts, preferred_element_type=jnp.float32))
        * y).astype(lhs.dtype)
