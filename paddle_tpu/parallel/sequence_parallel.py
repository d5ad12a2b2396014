"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference's long-sequence story is LoD variable-length tensors
(`paddle/fluid/framework/lod_tensor.h:44-110`) — 2018 has no sequence
parallelism. The TPU-native capability extension (SURVEY.md §5.7) shards the
*sequence axis* of attention across the ICI mesh:

  - **Ring attention** (`ring_attention_shard`): each device holds a sequence
    chunk of Q/K/V; K/V blocks rotate around the ring via `lax.ppermute`
    while a flash-style online softmax (running max / sum) accumulates the
    local queries' output. Memory per device is O(S/n), and each ppermute
    overlaps with the next block's matmuls. The backward pass is a second
    ring pass (custom_vjp): dK/dV accumulators travel with their K/V blocks.
  - **Ulysses** (`ulysses_attention_shard`): `lax.all_to_all` re-shards
    [B, S/n, H, D] -> [B, S, H/n, D] so each device runs full-sequence
    attention on a head subset, then the inverse all_to_all restores
    sequence sharding. Differentiable through the collectives' transposes.

Both are per-shard functions to be run under `shard_map`;
`sequence_parallel_attention` is the global-array wrapper.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _bhq_to_bqh1(x):
    # [B,H,Sq] -> [B,Sq,H,1] (broadcast factor for the [B,Sq,H,D] accumulator)
    return x.transpose(0, 2, 1)[..., None]


def _block_scores(q32, k, scale, mask):
    s = jnp.einsum("bqhd,bkhd->bhqk", q32, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    return s


def _online_softmax_block(q32, k, v, m, l, o, mask, scale):
    """One flash-attention block update. m,l: [B,H,Sq] f32 running max/sum;
    o: [B,Sq,H,D] f32 unnormalized output accumulator."""
    s = _block_scores(q32, k, scale, mask)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # rows with no valid key yet keep m = NEG_INF; exp(0)=1 there would
    # poison p, so masked score entries are explicitly zeroed
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_new = o * _bhq_to_bqh1(alpha) + pv
    return m_new, l_new, o_new


def _ring_perm(n):
    return [(j, (j + 1) % n) for j in range(n)]


def _causal_mask(my, src, sq, sk):
    """Block mask for query chunk `my` against key chunk originally at `src`
    (chunks are contiguous sequence slices of equal length per device)."""
    qpos = my * sq + jnp.arange(sq)
    kpos = src * sk + jnp.arange(sk)
    return (qpos[:, None] >= kpos[None, :])[None, None]  # [1,1,Sq,Sk]


def _axis_size(axis_name) -> int:
    """Static axis size: lax.psum of a concrete 1 constant-folds to the
    axis size at trace time — no collective, no device code."""
    if axis_name is None:
        return 1
    return lax.psum(1, axis_name)


def _ring_fwd_pass(q, k, v, my, axis_name, causal, scale):
    n = _axis_size(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    q32 = q.astype(jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, sq, h, d), jnp.float32)

    def step(carry, i):
        m, l, o, kk, vv = carry
        src = (my - i) % n
        mask = _causal_mask(my, src, sq, sk) if causal else None
        m, l, o = _online_softmax_block(q32, kk, vv, m, l, o, mask, scale)
        kk = lax.ppermute(kk, axis_name, _ring_perm(n))
        vv = lax.ppermute(vv, axis_name, _ring_perm(n))
        return (m, l, o, kk, vv), None

    # scan the first n-1 blocks (each ends with a K/V rotation), then fold in
    # the final block outside the loop — its rotation would be discarded
    if n > 1:
        (m, l, o, k, v), _ = lax.scan(
            step, (m0, l0, o0, k, v), jnp.arange(n - 1)
        )
    else:
        m, l, o = m0, l0, o0
    last_src = (my - (n - 1)) % n
    last_mask = _causal_mask(my, last_src, sq, sk) if causal else None
    m, l, o = _online_softmax_block(q32, k, v, m, l, o, last_mask, scale)
    l_safe = jnp.maximum(l, jnp.finfo(jnp.float32).tiny)
    out = (o / _bhq_to_bqh1(l_safe)).astype(q.dtype)
    lse = m + jnp.log(l_safe)  # [B,H,Sq]
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ring_attention_impl(q, k, v, my_idx, axis_name, causal, scale):
    """custom_vjp core: ``my_idx`` is an int32[1] array carrying THIS
    shard's ring position. It is a primal input (zero float0 cotangent)
    rather than ``lax.axis_index(axis_name)``: a sharded-iota input says
    the same thing in data, so the shard's position is an ordinary
    sharded operand and no ``partition-id`` HLO is involved."""
    out, _ = _ring_fwd_pass(q, k, v, my_idx[0], axis_name, causal, scale)
    return out


def ring_attention_shard(q, k, v, axis_name=None, causal=False,
                         scale: Optional[float] = None, my_idx=None):
    """Per-shard ring attention. q: [B, Sq_local, H, D]; k/v: [B, Sk_local,
    H, D], sequence-sharded over `axis_name` (None = single chunk, plain
    flash attention). Softmax in f32; output in q.dtype.

    ``my_idx`` (int32[1]): this shard's ring position, normally threaded
    in by ``sequence_parallel_attention`` as a P(seq_axis)-sharded iota.
    Direct shard_map users may omit it (falls back to
    ``lax.axis_index``)."""
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if my_idx is None:
        if axis_name is None:
            my_idx = jnp.zeros((1,), jnp.int32)
        else:
            my_idx = lax.axis_index(axis_name).reshape(1).astype(jnp.int32)
    return _ring_attention_impl(q, k, v, my_idx, axis_name, causal, scale)


def _ring_fwd_rule(q, k, v, my_idx, axis_name, causal, scale):
    out, lse = _ring_fwd_pass(q, k, v, my_idx[0], axis_name, causal, scale)
    return out, (q, k, v, my_idx, out, lse)


def _ring_bwd_rule(axis_name, causal, scale, res, dout):
    q, k, v, my_idx, out, lse = res
    n, my = _axis_size(axis_name), my_idx[0]
    b, sq, h, d = q.shape
    sk = k.shape[1]
    q32 = q.astype(jnp.float32)
    do32 = dout.astype(jnp.float32)
    # D_i = sum_d dO_i * O_i, the softmax-jacobian diagonal term: [B,H,Sq]
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1).transpose(0, 2, 1)

    dq0 = jnp.zeros((b, sq, h, d), jnp.float32)
    dk0 = jnp.zeros_like(k, dtype=jnp.float32)
    dv0 = jnp.zeros_like(v, dtype=jnp.float32)

    def step(carry, i):
        dq, dk, dv, kk, vv = carry
        src = (my - i) % n
        mask = _causal_mask(my, src, sq, sk) if causal else None
        s = _block_scores(q32, kk, scale, mask)
        p = jnp.exp(s - lse[..., None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv = dv + jnp.einsum("bhqk,bqhd->bkhd", p, do32,
                             preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do32, vv.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kk.astype(jnp.float32),
                             preferred_element_type=jnp.float32) * scale
        dk = dk + jnp.einsum("bhqk,bqhd->bkhd", ds, q32,
                             preferred_element_type=jnp.float32) * scale
        if axis_name is not None and n > 1:
            # dK/dV accumulators travel with their K/V blocks; after n hops
            # every block is back on its home device with all contributions
            kk, vv, dk, dv = (
                lax.ppermute(x, axis_name, _ring_perm(n))
                for x in (kk, vv, dk, dv)
            )
        return (dq, dk, dv, kk, vv), None

    (dq, dk, dv, _, _), _ = lax.scan(
        step, (dq0, dk0, dv0, k, v), jnp.arange(n)
    )
    # my_idx is an integer primal: its cotangent is the float0 zero
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            np.zeros(np.shape(my_idx), jax.dtypes.float0))


_ring_attention_impl.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ulysses_attention_shard(q, k, v, axis_name, causal=False,
                            scale: Optional[float] = None):
    """Per-shard Ulysses attention: all_to_all heads<->sequence, then full
    attention on a head subset. Requires H %% axis_size == 0."""
    n = _axis_size(axis_name)
    if n > 1:
        if q.shape[2] % n:
            raise ValueError(
                f"ulysses needs heads ({q.shape[2]}) divisible by axis size {n}"
            )
        a2a = functools.partial(lax.all_to_all, axis_name=axis_name,
                                split_axis=2, concat_axis=1, tiled=True)
        q, k, v = a2a(q), a2a(k), a2a(v)  # -> [B, S, H/n, D]
    out = ring_attention_shard(q, k, v, None, causal, scale)
    if n > 1:
        out = lax.all_to_all(out, axis_name=axis_name, split_axis=1,
                             concat_axis=2, tiled=True)
    return out


def sequence_parallel_attention(
    q, k, v, mesh: Mesh, seq_axis: str = "sp",
    batch_axis: Optional[str] = None, head_axis: Optional[str] = None,
    causal: bool = False, scale: Optional[float] = None, impl: str = "ring",
):
    """Global-array entry point: q/k/v are [B, S, H, D] jax.Arrays; the
    sequence dim is sharded over `seq_axis` of `mesh` (batch over
    `batch_axis`, heads over `head_axis` when given) and attention runs
    SPMD via shard_map."""
    if impl == "ring":
        def body(qs, ks, vs, idx):
            return ring_attention_shard(qs, ks, vs, seq_axis, causal,
                                        scale, my_idx=idx)
    elif impl == "ulysses":
        def body(qs, ks, vs, idx):
            del idx  # ulysses needs only the axis SIZE, never the index
            return ulysses_attention_shard(qs, ks, vs, seq_axis,
                                           causal=causal, scale=scale)
    else:
        raise ValueError(f"unknown sequence-parallel impl '{impl}'")
    spec = P(batch_axis, seq_axis, head_axis, None)
    # the ring index rides in as DATA: a P(seq_axis)-sharded iota hands
    # each shard its own position, so the body never calls
    # lax.axis_index
    n_sp = dict(zip(mesh.axis_names, mesh.devices.shape))[seq_axis]
    ring_idx = jnp.arange(n_sp, dtype=jnp.int32)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec, P(seq_axis)),
                   out_specs=spec, check_vma=False)
    # Pin the boundary shardings explicitly. Under GSPMD the producers
    # (e.g. tp column-parallel qkv projections) already carry compatible
    # shardings when head_axis matches the plan; the constraints make that
    # contract visible to the partitioner so it reshards with a local
    # slice/relabel instead of discovering a conflict at the shard_map edge
    # and falling back to full rematerialization (spmd_partitioner.cc:652).
    cons = lambda x: jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))
    idx = jax.lax.with_sharding_constraint(
        ring_idx, jax.sharding.NamedSharding(mesh, P(seq_axis)))
    out = fn(cons(q), cons(k), cons(v), idx)
    return cons(out)
