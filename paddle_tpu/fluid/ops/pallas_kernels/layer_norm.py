"""Fused layer norm as a Pallas TPU kernel: one VMEM pass computes
mean/variance/normalize/affine per row block (XLA emits this as several
fusions with an HBM round-trip between moments and normalize on large
rows). Backward is the standard jnp formula under custom_vjp."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _ln_kernel(x_ref, scale_ref, bias_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)  # [bn, F]
    mean = x.mean(axis=1, keepdims=True)
    xc = x - mean
    var = (xc * xc).mean(axis=1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y = xc * rstd
    y = y * scale_ref[:].astype(jnp.float32) + bias_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _ln_pallas(x, scale, bias, eps, block_rows, interpret):
    n_real, f = x.shape
    # zero-pad rows to a whole number of 8-multiple blocks (padded rows
    # compute garbage stats that are sliced off) — same trick as
    # flash_attention; avoids degenerate 1-row programs for prime n
    bn = min(_round_up(block_rows, 8), _round_up(n_real, 8))
    n = _round_up(n_real, bn)
    if n != n_real:
        x = jnp.pad(x, ((0, n - n_real), (0, 0)))
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, f), lambda i: (i, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, f), lambda i: (i, 0)),
            # stats leave as [bn, 1] column blocks: Mosaic refuses 1-D
            # (bn,) outputs once n spans several blocks (its T(128) tile
            # against XLA's T(1024) layout for f32[n])
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, f), x.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm",
    )(x, scale.reshape(1, f), bias.reshape(1, f))
    return y[:n_real], mean[:n_real, 0], rstd[:n_real, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ln(x, scale, bias, eps, block_rows, interpret):
    """Returns (y, mean, rstd). The stats outputs are statistics, not
    differentiable paths (matches the op contract — the reference's
    Mean/Variance are saved intermediates); their cotangents are ignored."""
    return _ln_pallas(x, scale, bias, eps, block_rows, interpret)


def _fused_ln_fwd(x, scale, bias, eps, block_rows, interpret):
    y, mean, rstd = _ln_pallas(x, scale, bias, eps, block_rows, interpret)
    return (y, mean, rstd), (x, scale, mean, rstd)


def _fused_ln_bwd(eps, block_rows, interpret, res, cts):
    dy, _, _ = cts  # stat outputs carry no gradient
    x, scale, mean, rstd = res
    f = x.shape[1]
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    xhat = (x32 - mean[:, None]) * rstd[:, None]
    dscale = jnp.sum(dy32 * xhat, axis=0)
    dbias = jnp.sum(dy32, axis=0)
    dxhat = dy32 * scale.astype(jnp.float32)[None, :]
    dx = (dxhat - dxhat.mean(axis=1, keepdims=True)
          - xhat * (dxhat * xhat).mean(axis=1, keepdims=True)) * rstd[:, None]
    return dx.astype(x.dtype), dscale.astype(scale.dtype), dbias.astype(
        scale.dtype)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, scale=None, bias=None, eps: float = 1e-5,
                     begin_norm_axis: int = 1, block_rows: int = 128,
                     interpret: bool = False):
    """x: any rank; normalized over dims [begin_norm_axis:). Returns
    (y, mean, variance_proxy) matching the layer_norm op contract (mean /
    variance flattened over leading dims; variance reconstructed from
    rstd)."""
    lead = 1
    for s in x.shape[:begin_norm_axis]:
        lead *= s
    f = 1
    for s in x.shape[begin_norm_axis:]:
        f *= s
    x2 = x.reshape(lead, f)
    if scale is None:
        scale = jnp.ones((f,), x.dtype)
    if bias is None:
        bias = jnp.zeros((f,), x.dtype)
    y, mean, rstd = _fused_ln(x2, scale.reshape(f), bias.reshape(f),
                              float(eps), block_rows, interpret)
    var = 1.0 / (rstd * rstd) - eps  # kernel's own stats, no second pass
    return y.reshape(x.shape), mean, var
