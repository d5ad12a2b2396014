"""Fused conv2d + folded-batchnorm + relu as a blocked Pallas GEMM.

The ResNet-50 inference hot path is conv -> batch_norm(is_test) -> relu
(reference operators/conv_mkldnn_op.cc + the conv+bn fusion passes in
inference/analysis — the reference's alternate-kernel axis for exactly
this chain). With frozen statistics, bn folds into a per-output-channel
affine: y = relu(conv(x, W) * scale + shift). This kernel computes the
conv as a blocked im2col GEMM on the MXU and applies the affine + relu
epilogue while the accumulator block is still in VMEM — the fused output
hits HBM exactly once, instead of conv-out / bn-out / relu-out round
trips when the compiler declines to fuse.

Layout: patches P [M, K] (M = N*OH*OW, K = C*KH*KW) x Wt [K, F], grid
(M/bm, F/bf); K stays whole per block (ResNet's largest K = 512*3*3 =
4608 -> ~2.4 MB per operand block in f32, well inside VMEM). bf16 inputs
accumulate in f32 via preferred_element_type (MXU-native).

Backward is a jnp reference under custom_vjp (the standard GEMM
cotangents; dx folds patches back through the patch-extraction vjp), so
the fused op trains too.

`interpret=True` runs the same kernel on CPU for tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _patches(x, kh, kw, stride, padding):
    """im2col: [N, C, H, W] -> [N*OH*OW, C*kh*kw] (channel-major patch
    order, matching w.reshape(F, C*kh*kw))."""
    p = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), window_strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )  # [N, C*kh*kw, OH, OW]
    n, k, oh, ow = p.shape
    return p.transpose(0, 2, 3, 1).reshape(n * oh * ow, k), (oh, ow)


def _gemm_epilogue_kernel(p_ref, w_ref, s_ref, b_ref, y_ref, *, relu):
    acc = jnp.dot(p_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    acc = acc * s_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    y_ref[:] = acc.astype(y_ref.dtype)


def _fused_gemm(p, wt, scale, shift, relu, block_m, block_f, interpret):
    m_real, k = p.shape
    f_real = wt.shape[1]
    bm = min(_round_up(block_m, 8), _round_up(m_real, 8))
    bf = min(_round_up(block_f, 128), _round_up(f_real, 128))
    m, f = _round_up(m_real, bm), _round_up(f_real, bf)
    if m != m_real:
        p = jnp.pad(p, ((0, m - m_real), (0, 0)))
    if f != f_real:
        wt = jnp.pad(wt, ((0, 0), (0, f - f_real)))
        scale = jnp.pad(scale, (0, f - f_real))
        shift = jnp.pad(shift, (0, f - f_real))
    y = pl.pallas_call(
        functools.partial(_gemm_epilogue_kernel, relu=relu),
        grid=(m // bm, f // bf),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, f), p.dtype),
        interpret=interpret,
        name="conv_bn_relu",
    )(p, wt, scale.reshape(1, f), shift.reshape(1, f))
    return y[:m_real, :f_real]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _fused_conv(x, w, scale, shift, stride, padding, relu, block_m,
                block_f, interpret):
    kh, kw = w.shape[2], w.shape[3]
    p, (oh, ow) = _patches(x, kh, kw, stride, padding)
    wt = w.reshape(w.shape[0], -1).T
    y = _fused_gemm(p, wt, scale, shift, relu, block_m, block_f, interpret)
    n = x.shape[0]
    return y.reshape(n, oh, ow, w.shape[0]).transpose(0, 3, 1, 2)


def _fused_conv_fwd(x, w, scale, shift, stride, padding, relu, block_m,
                    block_f, interpret):
    y = _fused_conv(x, w, scale, shift, stride, padding, relu, block_m,
                    block_f, interpret)
    return y, (x, w, scale, shift, y)


def _fused_conv_bwd(stride, padding, relu, block_m, block_f, interpret,
                    res, dy):
    x, w, scale, shift, y = res
    f = w.shape[0]
    kh, kw = w.shape[2], w.shape[3]
    dy32 = dy.astype(jnp.float32)
    if relu:
        dy32 = dy32 * (y > 0)
    # flatten to GEMM cotangent layout [M, F]
    dz = dy32.transpose(0, 2, 3, 1).reshape(-1, f)
    patch_fn = lambda xx: _patches(xx, kh, kw, stride, padding)[0]
    p, p_vjp = jax.vjp(patch_fn, x)
    p32 = p.astype(jnp.float32)
    wt32 = w.reshape(f, -1).T.astype(jnp.float32)
    # One shared GEMM A = P^T dZ [K, F] yields both weight and scale
    # cotangents without recomputing the forward GEMM g = P Wt:
    #   dWt[k,f]    = sum_m P[m,k] dZ[m,f] scale[f] = A[k,f] * scale[f]
    #   dscale[f]   = sum_m dZ[m,f] g[m,f]          = sum_k Wt[k,f] A[k,f]
    # (column scaling commutes through the GEMM; the dscale identity is
    # just reassociating the double sum). Exact for scale == 0 channels
    # too — unlike recovering g from y = g*scale + shift.
    a = p32.T @ dz  # [K, F]
    dscale = jnp.sum(wt32 * a, axis=0).astype(scale.dtype)
    dshift = jnp.sum(dz, axis=0).astype(shift.dtype)
    dg = dz * scale.astype(jnp.float32)[None, :]
    dw = (a * scale.astype(jnp.float32)[None, :]).T.reshape(
        w.shape).astype(w.dtype)
    dp = (dg @ wt32.T).astype(p.dtype)
    (dx,) = p_vjp(dp)
    return dx.astype(x.dtype), dw, dscale, dshift


_fused_conv.defvjp(_fused_conv_fwd, _fused_conv_bwd)


def fused_conv_bn_relu(x, w, scale=None, shift=None, stride: int = 1,
                       padding: int = 0, relu: bool = True,
                       block_m: int = 256, block_f: int = 128,
                       interpret: bool = False):
    """y = relu(conv2d(x, w, stride, padding) * scale + shift), NCHW.

    scale/shift are the FOLDED inference-bn parameters per output channel
    (gamma*rsqrt(var+eps), beta - mean*gamma*rsqrt(var+eps)); None means
    identity (plain conv, or conv+bias with shift). Use fold_bn() to
    build them from bn parameters."""
    f = w.shape[0]
    if scale is None:
        scale = jnp.ones((f,), jnp.float32)
    if shift is None:
        shift = jnp.zeros((f,), jnp.float32)
    return _fused_conv(x, w, scale.reshape(f), shift.reshape(f),
                       int(stride), int(padding), bool(relu), block_m,
                       block_f, interpret)


def fold_bn(gamma, beta, mean, var, eps: float = 1e-5):
    """Fold frozen batch-norm statistics into the per-channel affine the
    kernel's epilogue applies (the reference's conv+bn fusion rewrite)."""
    rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = gamma.astype(jnp.float32) * rstd
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    return scale, shift
