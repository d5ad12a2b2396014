"""Flash attention as a Pallas TPU kernel.

Forward: grid (batch*heads, Sq/block_q); each program streams K/V blocks
from VMEM with an online softmax (running max / sum), so only
[block_q, block_k] scores ever exist — the [Sq, Sk] matrix never hits HBM.
Backward: two blocked Pallas kernels (the standard flash-attention reverse
pass): a dK/dV kernel gridded over key blocks that streams Q/dO blocks, and
a dQ kernel gridded over query blocks that streams K/V blocks — probability
blocks are recomputed from the saved LSE, so the backward is O(S) memory
like the forward (no [Sq, Sk] matrix in HBM at any point).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, sk_real, precision):
    # bf16 inputs stay bf16 INTO the MXU dots (f32 accumulation via
    # preferred_element_type): native one-pass bf16 matmuls, half the VMEM
    # per block, and half the HBM traffic for Q/K/V. Only the softmax
    # arithmetic runs in f32. f32 inputs keep the old upcast path.
    lowp = q_ref.dtype == jnp.bfloat16
    q = q_ref[0] if lowp else q_ref[0].astype(jnp.float32)  # [bq, D]
    bq = q.shape[0]
    sk_pad = k_ref.shape[1]
    nk = sk_pad // block_k
    iq = pl.program_id(1)
    mask_pad = sk_pad > sk_real  # static: key padding needs masking

    def body(kb, carry):
        m, l, acc = carry  # [bq,1], [bq,1], [bq,D]
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        if not lowp:
            kblk = kblk.astype(jnp.float32)
            vblk = vblk.astype(jnp.float32)
        s = lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        ) * scale  # [bq, bk]
        keep = None
        if causal or mask_pad:
            kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = kpos < sk_real if mask_pad else None
            if causal:
                qpos = iq * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
                c = qpos >= kpos
                keep = c if keep is None else jnp.logical_and(keep, c)
            s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        pv = lax.dot_general(
            # bf16 path: round P to bf16 for the second MXU pass (standard
            # flash-attention practice; the accumulator stays f32)
            p.astype(vblk.dtype) if lowp else p,
            vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        return m_new, l_new, acc * alpha + pv

    d = q.shape[1]
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        # skip K blocks entirely above the diagonal for this query block
        nk_iter = jnp.minimum(nk, pl.cdiv((iq + 1) * bq, block_k))
    else:
        nk_iter = nk
    m, l, acc = lax.fori_loop(0, nk_iter, body, (m0, l0, acc0))
    l = jnp.maximum(l, jnp.finfo(jnp.float32).tiny)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)  # [bq, 1]


def _fwd_pallas(q, k, v, scale, causal, block_q, block_k, interpret,
                precision):
    bh, sq, d = q.shape
    sk = k.shape[1]
    # blocks are multiples of 8 (TPU sublane); inputs are zero-padded to a
    # whole number of blocks and padded keys masked inside the kernel
    bq = min(_round_up(block_q, 8), _round_up(sq, 8))
    bk = min(_round_up(block_k, 8), _round_up(sk, 8))
    sq_pad, sk_pad = _round_up(sq, bq), _round_up(sk, bk)
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0)))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=bk, sk_real=sk, precision=precision)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq_pad // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out[:, :sq], lse[:, :sq, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k, interpret,
                precision):
    out, _ = _fwd_pallas(q, k, v, scale, causal, block_q, block_k, interpret,
                         precision)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               precision):
    out, lse = _fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                           interpret, precision)
    return out, (q, k, v, out, lse)


def _bwd_dkdv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                     dk_ref, dv_ref, *, scale, causal, block_q, sq_real,
                     sk_real, precision):
    """Grid (bh, Sk/block_k): this program owns one K/V block and streams
    Q/dO/LSE/delta blocks, recomputing P per block from the saved LSE."""
    lowp = q_ref.dtype == jnp.bfloat16  # see _fwd_kernel: bf16-native MXU
    k = k_ref[0] if lowp else k_ref[0].astype(jnp.float32)   # [bk, D]
    v = v_ref[0] if lowp else v_ref[0].astype(jnp.float32)
    bk = k.shape[0]
    ik = pl.program_id(1)
    sq_pad = q_ref.shape[1]
    nq = sq_pad // block_q

    def body(qb, carry):
        dk, dv = carry  # [bk, D] each
        qblk = q_ref[0, pl.ds(qb * block_q, block_q), :]
        doblk = do_ref[0, pl.ds(qb * block_q, block_q), :]
        if not lowp:
            qblk = qblk.astype(jnp.float32)
            doblk = doblk.astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), 0]      # [bq]
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), 0]  # [bq]
        s = lax.dot_general(
            qblk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        ) * scale  # [bq, bk]
        p = jnp.exp(s - lse[:, None])
        qpos = qb * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ik * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # padded query rows have lse=0 (p could overflow) and padded key
        # cols never existed: both must be zeroed, not just causal-masked
        keep = jnp.logical_and(qpos < sq_real, kpos < sk_real)
        if causal:
            keep = jnp.logical_and(keep, qpos >= kpos)
        p = jnp.where(keep, p, 0.0)
        dv = dv + lax.dot_general(
            p.astype(doblk.dtype) if lowp else p,
            doblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )  # [bk, D]
        dp = lax.dot_general(
            doblk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )  # [bq, bk]
        ds = p * (dp - delta[:, None])
        dk = dk + lax.dot_general(
            ds.astype(qblk.dtype) if lowp else ds,
            qblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )  # [bk, D]
        return dk, dv

    d = k.shape[1]
    zero = jnp.zeros((bk, d), jnp.float32)
    if causal:
        # query blocks strictly above this key block's diagonal contribute
        # nothing — start at the first block whose last row reaches kpos
        qb_start = (ik * bk) // block_q
    else:
        qb_start = 0
    dk, dv = lax.fori_loop(qb_start, nq, body, (zero, zero))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_k, sq_real, sk_real, precision):
    """Grid (bh, Sq/block_q): this program owns one Q block and streams
    K/V blocks (mirror of the forward's loop)."""
    lowp = q_ref.dtype == jnp.bfloat16  # see _fwd_kernel: bf16-native MXU
    q = q_ref[0] if lowp else q_ref[0].astype(jnp.float32)    # [bq, D]
    do = do_ref[0] if lowp else do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]              # [bq]
    delta = delta_ref[0, :, 0]          # [bq]
    bq = q.shape[0]
    iq = pl.program_id(1)
    sk_pad = k_ref.shape[1]
    nk = sk_pad // block_k

    def body(kb, dq):
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        if not lowp:
            kblk = kblk.astype(jnp.float32)
            vblk = vblk.astype(jnp.float32)
        s = lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        ) * scale  # [bq, bk]
        p = jnp.exp(s - lse[:, None])
        qpos = iq * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = jnp.logical_and(qpos < sq_real, kpos < sk_real)
        if causal:
            keep = jnp.logical_and(keep, qpos >= kpos)
        p = jnp.where(keep, p, 0.0)
        dp = lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )  # [bq, bk]
        ds = p * (dp - delta[:, None])
        return dq + lax.dot_general(
            ds.astype(kblk.dtype) if lowp else ds,
            kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )

    d = q.shape[1]
    if causal:
        nk_iter = jnp.minimum(nk, pl.cdiv((iq + 1) * bq, block_k))
    else:
        nk_iter = nk
    dq = lax.fori_loop(0, nk_iter, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd(scale, causal, block_q, block_k, interpret, precision,
               res, dout):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(_round_up(block_q, 8), _round_up(sq, 8))
    bk = min(_round_up(block_k, 8), _round_up(sk, 8))
    sq_pad, sk_pad = _round_up(sq, bq), _round_up(sk, bk)

    do32 = dout.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [bh, sq, 1]
    lse = lse[:, :, None]           # [bh, sq, 1]

    if sq_pad != sq:
        pad = ((0, 0), (0, sq_pad - sq), (0, 0))
        q = jnp.pad(q, pad)
        dout = jnp.pad(dout, pad)
        lse = jnp.pad(lse, pad)
        delta = jnp.pad(delta, pad)
    if sk_pad != sk:
        pad = ((0, 0), (0, sk_pad - sk), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    dkdv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=bq, sq_real=sq, sk_real=sk,
                          precision=precision),
        grid=(bh, sk_pad // bk),
        in_specs=[
            pl.BlockSpec((1, sq_pad, d), lambda b, j: (b, 0, 0)),  # q
            pl.BlockSpec((1, sq_pad, d), lambda b, j: (b, 0, 0)),  # do
            pl.BlockSpec((1, sq_pad, 1), lambda b, j: (b, 0, 0)),  # lse
            pl.BlockSpec((1, sq_pad, 1), lambda b, j: (b, 0, 0)),  # delta
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),      # k
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),      # v
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_pad, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk_pad, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkdv",
    )(q, dout, lse, delta, k, v)
    dk, dv = dkdv

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=bk, sq_real=sq, sk_real=sk,
                          precision=precision),
        grid=(bh, sq_pad // bq),
        in_specs=[
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0)),  # k
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),      # q
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),      # do
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),      # lse
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),      # delta
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(k, v, q, dout, lse, delta)

    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


_flash_bhsd.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    precision=None):
    """q/k/v: [B, S, H, D] (the layout of layers.ring_attention). Returns
    [B, Sq, H, D].

    `precision`: lax.Precision for the in-kernel MXU dots. None (default)
    is the MXU-native pass (bf16 multiply, f32 accumulate) — the same
    numerics as XLA's default matmul precision on TPU, and what you want
    for training throughput. Pass lax.Precision.HIGHEST for full-f32 dots
    (~3-6x the MXU passes) when validating numerics."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = float(scale) if scale else d ** -0.5

    def to_bhsd(x, s):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    out = _flash_bhsd(to_bhsd(q, sq), to_bhsd(k, sk), to_bhsd(v, sk),
                      scale, causal, block_q, block_k, interpret, precision)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
