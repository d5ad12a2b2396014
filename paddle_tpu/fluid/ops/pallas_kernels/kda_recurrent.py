"""The gated delta rule of a KDA layer (Kimi Delta Attention) over the
lanes of one served step: each sequence's fixed-size state carried from
step to step in a per-slot array, decode and prefill chunks alike.

For one head, with ``S`` the ``[K, V]`` state, a lane ``t`` does::

    S <- Diag(exp g_t) S                   (g_t [K] <= 0: per-channel decay)
    S <- S + beta_t k_t (v_t - S^T k_t)^T  (the delta rule)
    o_t = S^T q_t

The state is held TRANSPOSED, ``S^T [V, K]``: the decay then scales the
lanes by a row (``g_t`` as it lies), ``S^T k_t`` and ``S^T q_t`` are
reduces over the lanes (columns ``[V, 1]``), and the update is a column
times a row, so no lane ever needs a transpose.

Layouts (``B`` call rows, ``C`` lanes a row, ``H`` heads, ``R`` state rows):

    q, k       [B, C, H, K] float32   q L2-normed and scaled, k L2-normed
    v          [B, C, H, V] float32
    g          [B, C, H, K] float32   log-decay
    beta       [B, C, H]    float32
    state      [L, R, H, V, K] float32   every KDA layer's state rows,
                            one a sequence, ``S^T`` a head; a call
                            reads and writes layer ``layer``
    rows       [B] int32    the state row of each call row
    q_lens     [B] int32    live lanes of each call row (0: dead)
    fresh      [B] int32    1 where the row's sequence starts in this
                            call: its state starts from zero, whatever its
                            state row held

Returns ``(o [B, C, H, V] float32, state)``: a dead lane's output is exact
zeros, and a dead row's state row, every other layer's and every row no
call row names are left as they were.

Two implementations with the same semantics:

  - ``kda_recurrent_reference``: an XLA scan over the lanes on the call's
    gathered rows — every lane rewrites the whole gathered state, the CPU
    path and the oracle;
  - the Pallas kernel (``name="kda_recurrent"``): grid ``(B, H / HEADS)``
    with the layer, ``rows``, ``q_lens`` and ``fresh`` scalar-prefetched;
    a program
    takes a call row's group of heads, loads that state row's block ONCE
    (the index map reads ``rows``), loops over the row's ``q_len`` live
    lanes in VMEM and writes the block back once. The state is aliased
    input to output, so the rows no call row names stay where they are.

``kda_route`` names the implementation as ``paged_route`` does: the
kernel on a TPU (``use_pallas_kernels``), the reference elsewhere and
where the caller names it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....observability import metrics as _metrics
from .paged_attention import _mxu

__all__ = ["kda_recurrent", "kda_recurrent_reference", "kda_route"]

# heads a program carries: 8 heads' S^T of 128 x 128 float32 are 512 KiB,
# in and out double-buffered 2 MiB of VMEM
HEADS = 8

_m_route_kernel = _metrics.counter("kda.route.kernel")
_m_route_ref = _metrics.counter("kda.route.reference")


def _lane_step(st, q, k, v, g, beta):
    """One lane on the transposed state ``st [..., V, K]``: ``q``, ``k``,
    ``g`` [..., K] rows, ``v`` [..., V], ``beta`` [...]. Returns ``(st,
    o [..., V])``."""
    st = st * jnp.exp(g)[..., None, :]
    kv = jnp.sum(st * k[..., None, :], axis=-1)            # S^T k: [.., V]
    u = (v - kv) * beta[..., None]
    st = st + u[..., :, None] * k[..., None, :]
    return st, jnp.sum(st * q[..., None, :], axis=-1)


def kda_recurrent_reference(q, k, v, g, beta, state, rows, q_lens, fresh,
                            *, layer: int = 0):
    """The lane scan in XLA over the call's gathered state rows (module
    docstring for the layouts)."""
    c = q.shape[1]
    live = q_lens > 0
    held = state[layer, rows]
    st0 = jnp.where(((fresh > 0) & live)[:, None, None, None], 0.0, held)

    def lane(st, x):
        j, qj, kj, vj, gj, bj = x
        new, o = _lane_step(st, qj, kj, vj, gj, bj)
        on = (j < q_lens)[:, None]                          # [B, 1]
        return (jnp.where(on[:, :, None, None], new, st),
                jnp.where(on[:, :, None], o, 0.0))

    xs = (jnp.arange(c),) + tuple(jnp.swapaxes(a, 0, 1)
                                  for a in (q, k, v, g, beta))
    st, o = jax.lax.scan(lane, st0, xs)
    # a dead row keeps what its state row held (rows may repeat there: the
    # engine's dead rows all name its garbage row)
    st = jnp.where(live[:, None, None, None], st, held)
    return jnp.swapaxes(o, 0, 1), state.at[layer, rows].set(st)


def _kda_kernel(layer_ref, rows_ref, q_lens_ref, fresh_ref, q_ref, k_ref,
                v_ref, g_ref, beta_ref, st_in_ref, o_ref, st_ref):
    """One (call row, head group): ``st_ref`` is the state row's block
    ``[1, 1, HEADS, V, K]``, the aliased output; lanes ``0 .. q_len - 1``
    run in order, a head at a time. A lane's ``v`` comes in as rows
    ``[HEADS, V]`` and is turned into columns ``[V, HEADS]`` by a product
    with the identity on the MXU; its output columns go back the same way.
    No operand is narrowed: the identity is exact in bfloat16 and ``v`` goes
    as the three bfloat16 terms of its float32 (``_mxu``)."""
    bb = pl.program_id(0)
    q_len = q_lens_ref[bb]
    hg, vd = v_ref.shape[2], v_ref.shape[3]
    fresh = (fresh_ref[bb] != 0) & (q_len > 0)

    @pl.when(fresh)
    def _fresh():
        st_ref[...] = jnp.zeros_like(st_ref)

    @pl.when(jnp.logical_not(fresh))
    def _carry():
        st_ref[...] = st_in_ref[...]

    o_ref[...] = jnp.zeros_like(o_ref)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (vd, vd), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (vd, vd), 1))
    head = jax.lax.broadcasted_iota(jnp.int32, (1, hg), 1)

    def _lane(j, carry):
        vcol = _mxu(eye.astype(jnp.bfloat16), v_ref[0, j],
                    ((1,), (1,)))                           # [V, Hg]
        decay = jnp.exp(g_ref[0, j])                        # [Hg, K]
        k, q, beta = k_ref[0, j], q_ref[0, j], beta_ref[0, j]
        ocol = jnp.zeros((vd, hg), jnp.float32)
        for h in range(hg):
            st = st_ref[0, 0, h] * decay[h:h + 1]           # [V, K]
            kh = k[h:h + 1]                                 # [1, K]
            kv = jnp.sum(st * kh, axis=1, keepdims=True)    # S^T k: [V, 1]
            vh = jnp.sum(jnp.where(head == h, vcol, 0.0), axis=1,
                         keepdims=True)                     # [V, 1]
            st = st + (vh - kv) * beta[h:h + 1] * kh
            st_ref[0, 0, h] = st
            ocol = ocol + jnp.where(
                head == h, jnp.sum(st * q[h:h + 1], axis=1, keepdims=True),
                0.0)
        o_ref[0, j] = _mxu(ocol, eye.astype(jnp.float32), ((0,), (0,)))
        return carry

    jax.lax.fori_loop(0, q_len, _lane, 0)


def _kda_pallas(q, k, v, g, beta, state, rows, q_lens, fresh, *,
                layer: int = 0, interpret: bool = False):
    """The kernel's call; ``layer`` rides as data, so that every layer of a
    step shares one trace and one lowering."""
    return _kda_call(q, k, v, g, beta, state,
                     jnp.full((1,), layer, jnp.int32), rows, q_lens, fresh,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_call(q, k, v, g, beta, state, layer, rows, q_lens, fresh, *,
              interpret: bool = False):
    b, c, h, kd = q.shape
    vd = v.shape[-1]
    hg = HEADS if h % HEADS == 0 else h
    lanes = lambda d: pl.BlockSpec(
        (1, c, hg, d), lambda bb, hh, *_: (bb, 0, hh, 0))
    # THE state read: the index map picks the call row's state row
    state_block = pl.BlockSpec(
        (1, 1, hg, vd, kd), lambda bb, hh, l, r, *_: (l[0], r[bb], hh, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, h // hg),
        in_specs=[lanes(kd), lanes(kd), lanes(vd), lanes(kd), lanes(1),
                  state_block],
        out_specs=[lanes(vd), state_block],
    )
    return pl.pallas_call(
        _kda_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, c, h, vd), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state (operand 9: four prefetched vectors, five inputs) is
        # updated in place: rows no program names keep their contents
        input_output_aliases={9: 1},
        interpret=interpret,
        # the kernel's name in the compiled program and a device trace
        name="kda_recurrent",
    )(layer, rows, q_lens, fresh, q, k, v, g, beta[..., None], state)


def kda_route(impl: Optional[str] = None) -> str:
    """``"kda_kernel"`` or ``"kda_reference"``: ``impl="reference"`` is
    the caller naming the reference, ``None`` lets ``use_pallas_kernels``
    decide (the kernel on a TPU)."""
    from ...flags import pallas_enabled

    if impl not in (None, "reference"):
        raise ValueError(f"kda impl must be None or 'reference', "
                         f"got {impl!r}")
    if impl is None and pallas_enabled():
        return "kda_kernel"
    return "kda_reference"


def kda_recurrent(q, k, v, g, beta, state, rows, q_lens, fresh, *,
                  layer: int = 0, impl: Optional[str] = None,
                  interpret: Optional[bool] = None):
    """Route between the kernel and the reference (``kda_route``); every
    trace counts its route. Module docstring for the operands."""
    from ...flags import pallas_interpret

    args = (q, k, v, g, beta, state, rows.astype(jnp.int32),
            q_lens.astype(jnp.int32), fresh.astype(jnp.int32))
    if kda_route(impl) == "kda_kernel":
        _m_route_kernel.inc()
        return _kda_pallas(*args, layer=int(layer),
                           interpret=pallas_interpret()
                           if interpret is None else bool(interpret))
    _m_route_ref.inc()
    return kda_recurrent_reference(*args, layer=int(layer))
