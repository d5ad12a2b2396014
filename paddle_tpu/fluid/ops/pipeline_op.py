"""`pipeline` region op — GPipe schedule over the mesh `pp` axis, emitted
from Program-IR stages (layers/pipeline.py builds the region; no 2018
reference counterpart — see parallel/pipeline.py for the design notes).

Lowering:
  * The region sub-block is split at `pipeline_cut` markers into S stages of
    op descs; stage s is re-emitted (exec_op_descs) as a pure function
    activation -> activation, reading its parameters from the op's Params.
  * With a mesh in scope (parallel.mesh_context) that has a `pp` axis of
    size S, the stages run as a shard_map GPipe schedule: each device
    selects its stage with lax.switch(axis_index('pp')), activations flow
    stage-to-stage over ICI via lax.ppermute, microbatches stream through a
    lax.scan of n_micro + S - 1 ticks. Everything is differentiable, so the
    registry's generic vjp yields the reverse (backward) pipeline schedule
    with no extra machinery.
  * Without a `pp` mesh axis the stages run sequentially — identical
    semantics, no pipelining (single-chip debug / CPU tests).

Contract: region input, every cut activation, and the output share one
shape/dtype (validated here via jax.eval_shape before scheduling). Stage
parameters are passed replicated to every device; only the owning stage's
branch reads them (memory trade-off of the switch-based schedule — the
homogeneous-stage stacked layout in parallel/pipeline.py is the
memory-optimal path when all stages share one parameter structure).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..registry import exec_op_descs, register_op
from .common import one


@register_op("pipeline_cut",
             ref="stage marker; consumed by the pipeline emitter")
def pipeline_cut(ctx, ins, attrs):
    return {}


def _split_stages(sub_block, in_var_name, out_var_name):
    """-> [(op_descs, stage_in_name, stage_out_name)] split at cut markers."""
    stages = []
    cur_ops, cur_in = [], in_var_name
    for op in sub_block.ops:
        od = op.desc
        if od.type == "pipeline_cut":
            cut_var = od.input_names()[0]
            stages.append((cur_ops, cur_in, cut_var))
            cur_ops, cur_in = [], cut_var
        else:
            cur_ops.append(od)
    stages.append((cur_ops, cur_in, out_var_name))
    return stages


@register_op("pipeline", no_grad=(),
             ref="TPU-native; reference's closest surface is per-layer "
                 "device placement in trainer_config_helpers")
def pipeline(ctx, ins, attrs):
    from ...parallel.api import current_mesh

    x = one(ins, "X")
    param_names = list(attrs.get("param_var_names", []))
    params = dict(zip(param_names, ins.get("Params", [])))
    sub = ctx.program.block(int(attrs["sub_block"]))
    stages = _split_stages(sub, attrs["in_var_name"], attrs["out_var_name"])
    S = len(stages)
    assert S == int(attrs["n_stages"])

    def run_stage(s, act, env_params):
        ops, in_name, out_name = stages[s]
        env = dict(env_params)
        env[in_name] = act
        exec_op_descs(ctx, ops, env)
        if out_name not in env:
            raise ValueError(
                f"pipeline stage {s} does not produce its cut/output var "
                f"'{out_name}' — each stage must compute the activation it "
                "hands to the next stage")
        return env[out_name]

    mesh = current_mesh()
    pp = (dict(zip(mesh.axis_names, mesh.devices.shape)).get("pp")
          if mesh is not None else None)
    if pp is None or pp == 1:
        act = x
        for s in range(S):
            act = run_stage(s, act, params)
        return {"Out": act}

    if pp != S:
        raise ValueError(
            f"pipeline region has {S} stages but mesh 'pp' axis is {pp} — "
            "cut the region into exactly pp stages")

    n_micro = int(attrs.get("n_microbatches") or 0) or S
    B = x.shape[0]
    if B % n_micro:
        raise ValueError(
            f"pipeline input batch {B} not divisible by n_microbatches "
            f"{n_micro}")
    mb = B // n_micro
    x_mb = x.reshape((n_micro, mb) + x.shape[1:])
    mb_aval = jax.eval_shape(lambda a: a, x_mb[0])

    # every stage must map the microbatch activation to the same aval —
    # check now so a shape break is a build error, not a scan mismatch
    aval = mb_aval
    for s in range(S):
        out_aval = jax.eval_shape(lambda a, s=s: run_stage(s, a, params), aval)
        if (out_aval.shape, out_aval.dtype) != (mb_aval.shape, mb_aval.dtype):
            raise ValueError(
                f"pipeline stage {s} maps {aval.shape}/{aval.dtype} -> "
                f"{out_aval.shape}/{out_aval.dtype}; the GPipe schedule "
                f"needs every stage to preserve {mb_aval.shape}/"
                f"{mb_aval.dtype} (region input, cuts, and output must "
                "agree)")
        aval = out_aval

    axis_name = "pp"
    # replicate over every mesh axis inside the region; dp/tp sharding of
    # the surrounding program is handled by the jit-level shardings outside
    all_axes_spec = P()

    def schedule(xs, ps):
        idx = lax.axis_index(axis_name)
        branches = [
            (lambda args, s=s: run_stage(s, args[0], args[1]))
            for s in range(S)
        ]
        n_ticks = n_micro + S - 1

        def tick(carry, t):
            recv, outputs = carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            first_in = lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False)
            cur_in = jnp.where(idx == 0, first_in, recv)
            out = lax.switch(idx, branches, (cur_in, ps))
            out_idx = jnp.clip(t - (S - 1), 0, n_micro - 1)
            valid = jnp.logical_and(idx == S - 1, t >= S - 1)
            store = jnp.where(valid, out, jnp.zeros_like(out))
            outputs = lax.dynamic_update_index_in_dim(
                outputs,
                lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
                + store,
                out_idx, 0,
            )
            perm = [(j, j + 1) for j in range(S - 1)]
            recv = lax.ppermute(out, axis_name, perm)
            return (recv, outputs), None

        recv0 = jnp.zeros(mb_aval.shape, mb_aval.dtype)
        out0 = jnp.zeros((n_micro,) + mb_aval.shape, mb_aval.dtype)
        (_, outputs), _ = lax.scan(tick, (recv0, out0), jnp.arange(n_ticks))
        # only the last stage holds real outputs; psum broadcasts them
        return lax.psum(outputs, axis_name)

    fn = shard_map(
        schedule, mesh=mesh,
        in_specs=(all_axes_spec, jax.tree.map(lambda _: all_axes_spec,
                                              params)),
        out_specs=all_axes_spec,
        check_vma=False,
    )
    out_mb = fn(x_mb, params)
    return {"Out": out_mb.reshape((B,) + out_mb.shape[2:])}
