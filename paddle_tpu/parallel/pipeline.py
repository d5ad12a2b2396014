"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

No reference counterpart (2018 — the reference's only model partitioning is
per-layer `device` placement in the legacy config, SURVEY.md §2.10). This is
the TPU-native capability: stage parameters live sharded over the `pp` mesh
axis (leading stage dim), activations flow stage-to-stage over ICI via
`lax.ppermute`, and the whole schedule is one XLA computation — fully
differentiable (ppermute transposes to the reverse rotation), so a jitted
training step backpropagates through the pipeline for free.

Layout contract: every stage has the same signature
    stage_fn(stage_params, x) -> y        (x, y same shape [mb, ...])
and `params` is a pytree whose leaves are stacked on a leading stage axis of
size n_stages (shard that axis over `pp`).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _shift_right(x, axis_name, n):
    """Send each device's value to the next stage; stage 0 receives zeros
    (ring edge n-1 -> 0 is cut)."""
    perm = [(j, j + 1) for j in range(n - 1)]
    return lax.ppermute(x, axis_name, perm)


def pipeline_apply_shard(stage_fn: Callable, stage_params, x_mb,
                         axis_name: str):
    """Per-shard GPipe schedule (run under shard_map over `axis_name`).

    stage_params: this device's stage parameters (leading stage axis of size
    1, squeezed here). x_mb: [n_micro, mb, ...] microbatches — replicated
    (every stage sees them; only stage 0 consumes them). Returns
    [n_micro, mb, ...] outputs (valid on the last stage, zeros elsewhere —
    the global wrapper broadcasts them back).

    Schedule: T = n_micro + n_stages - 1 ticks. At tick t, stage s computes
    microbatch t - s (when in range). Each tick every device runs stage_fn
    once (idle ticks compute on garbage and are masked out) — the classic
    GPipe bubble of (n_stages - 1) / T.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: jnp.squeeze(p, 0), stage_params)
    n_micro = x_mb.shape[0]
    mb_shape = x_mb.shape[1:]
    ticks = n_micro + n - 1

    def tick(carry, t):
        recv, outputs = carry
        # stage 0 reads microbatch t (clamped; masked when out of range),
        # other stages read what the previous stage sent last tick
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        first_in = lax.dynamic_index_in_dim(x_mb, mb_idx, 0, keepdims=False)
        cur_in = jnp.where(idx == 0, first_in, recv)
        out = stage_fn(params, cur_in)
        # last stage stores microbatch t - (n-1) when it's valid
        out_idx = jnp.clip(t - (n - 1), 0, n_micro - 1)
        valid = jnp.logical_and(idx == n - 1, t >= n - 1)
        store = jnp.where(valid, out, 0.0)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
            + store,
            out_idx, 0,
        )
        recv = _shift_right(out, axis_name, n) if n > 1 else out
        return (recv, outputs), None

    recv0 = jnp.zeros(mb_shape, x_mb.dtype)
    out0 = jnp.zeros((n_micro,) + mb_shape, x_mb.dtype)
    (_, outputs), _ = lax.scan(tick, (recv0, out0), jnp.arange(ticks))
    # broadcast last stage's outputs to every device so out_specs can be
    # replicated over pp (psum: all other stages hold zeros)
    return lax.psum(outputs, axis_name)


def pipeline_apply(
    stage_fn: Callable, params, x, mesh: Mesh, axis_name: str = "pp",
    n_microbatches: Optional[int] = None,
):
    """Global entry point. params: pytree with leaves stacked on a leading
    stage axis (length = pp axis size); x: [batch, ...] global input.
    Splits batch into microbatches, pipelines them, returns [batch, ...]."""
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    n_micro = n_microbatches or n_stages
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
    x_mb = x.reshape((n_micro, b // n_micro) + x.shape[1:])

    pspec = jax.tree.map(lambda _: P(axis_name), params)
    fn = shard_map(
        functools.partial(pipeline_apply_shard, stage_fn,
                          axis_name=axis_name),
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )
    out_mb = fn(params, x_mb)
    return out_mb.reshape((b,) + out_mb.shape[2:])


def stack_stage_params(per_stage_params):
    """[params_stage0, params_stage1, ...] (matching pytrees) -> one pytree
    with a leading stage axis, ready to shard over pp."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *per_stage_params)


def shard_stage_params(params, mesh: Mesh, axis_name: str = "pp"):
    """Place stacked stage params with the leading axis sharded over pp."""
    def _put(p):
        spec = P(axis_name, *([None] * (p.ndim - 1)))
        return jax.device_put(p, NamedSharding(mesh, spec))

    return jax.tree.map(_put, params)
