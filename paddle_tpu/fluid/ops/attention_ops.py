"""Fused / sequence-parallel attention ops.

The reference has no fused attention op (2018 — attention is composed from
mul/softmax, e.g. `python/paddle/fluid/nets.py:345`
scaled_dot_product_attention). These ops are the TPU-native capability
extension (SURVEY.md §5.7): flash-style attention on one chip, ring or
Ulysses sequence parallelism over a mesh axis when lowered under a mesh.
"""
from __future__ import annotations

from ...observability import metrics as _metrics
from ..registry import register_op
from .common import one

# routing decisions are taken at TRACE time (this op body is Python run
# once per compile, not per step), so these count compiled routes — the
# counter pair the autotune routing tests assert on
_m_route_flash = _metrics.counter("attention.route.flash")
_m_route_dense = _metrics.counter("attention.route.dense")


@register_op("ring_attention", no_grad=(),
             ref="python/paddle/fluid/nets.py:345 (composed attention)")
def ring_attention(ctx, ins, attrs):
    """Q/K/V: [B, S, H, D]. Attrs: causal (bool), scale (float or 0 =
    1/sqrt(D)), impl ('ring' | 'ulysses'), seq_axis, batch_axis, head_axis.

    Under a mesh (ParallelExecutor sets parallel.mesh_context) with the
    seq_axis present, runs SPMD via shard_map; otherwise falls back to the
    same math single-device (one-block flash attention). The custom_vjp on
    the shard function makes the generic grad path take the ring backward.
    """
    from ...parallel import current_mesh
    from ...parallel.sequence_parallel import (
        ring_attention_shard,
        sequence_parallel_attention,
    )

    q, k, v = one(ins, "Q"), one(ins, "K"), one(ins, "V")
    causal = bool(attrs.get("causal", False))
    scale = float(attrs.get("scale", 0.0)) or None
    impl = attrs.get("impl", "ring")
    seq_axis = attrs.get("seq_axis", "sp")

    mesh = current_mesh()
    if mesh is None or seq_axis not in mesh.axis_names:
        from ..flags import effective_flag, pallas_enabled, pallas_interpret

        # route by measured crossover: XLA's dense path beats the flash
        # kernel below flash_min_seq. The FLAGS constant (the v5e bench
        # table) is only the cold-cache default — with autotune on, the
        # tuning cache's per-device-kind value wins (and trace_flags
        # keys the jit cache on the effective value, so a cache update
        # can never replay a stale-routed executable)
        use_flash = (pallas_enabled()
                     and q.shape[1] >= int(effective_flag("flash_min_seq")))
        # counts the THRESHOLD decision (the rare mesh-without-
        # dividable-axis fallthrough below still lands on XLA)
        (_m_route_flash if use_flash else _m_route_dense).inc()
        if use_flash:
            from .pallas_kernels import flash_attention

            if mesh is None:
                return flash_attention(q, k, v, causal=causal, scale=scale,
                                       interpret=pallas_interpret())
            # mesh without a seq axis (dp / dp×tp runs): pallas_call has no
            # GSPMD partitioning rule, so enter manual mode explicitly —
            # shard batch (and heads) over the mesh with shard_map and run
            # the kernel per shard. Attention is embarrassingly parallel in
            # batch/heads, so no collectives are needed.
            from jax.sharding import PartitionSpec as P

            from jax import shard_map

            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            b_ax = attrs.get("batch_axis", "") or None
            if b_ax is not None and (b_ax not in sizes
                                     or q.shape[0] % sizes[b_ax]):
                b_ax = None
            h_ax = attrs.get("head_axis", "") or None
            if h_ax is not None and (h_ax not in sizes
                                     or q.shape[2] % sizes[h_ax]):
                h_ax = None
            if b_ax is not None or h_ax is not None:
                spec = P(b_ax, None, h_ax, None)
                fn = shard_map(
                    lambda qs, ks, vs: flash_attention(
                        qs, ks, vs, causal=causal, scale=scale,
                        interpret=pallas_interpret()),
                    mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                    check_vma=False,
                )
                return fn(q, k, v)
            # no dividable batch/head axis: stay on the XLA path
        return ring_attention_shard(q, k, v, None, causal, scale)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axis = attrs.get("batch_axis", "") or None
    if batch_axis is not None and (batch_axis not in sizes
                                   or q.shape[0] % sizes[batch_axis]):
        batch_axis = None
    head_axis = attrs.get("head_axis", "") or None
    if head_axis is not None and (head_axis not in sizes
                                  or q.shape[2] % sizes[head_axis]):
        head_axis = None
    if (head_axis is not None and impl == "ulysses"
            and (q.shape[2] // sizes[head_axis]) % sizes[seq_axis]):
        # ulysses re-splits the LOCAL head count over the sp axis; with
        # heads already tp-sharded that's H/tp per shard, which must stay
        # divisible by sp or the all_to_all cannot tile
        head_axis = None
    return sequence_parallel_attention(
        q, k, v, mesh, seq_axis=seq_axis, batch_axis=batch_axis,
        head_axis=head_axis, causal=causal, scale=scale, impl=impl,
    )


@register_op("moe_ffn", no_grad=(), ref="(TPU-native capability extension)")
def moe_ffn_op(ctx, ins, attrs):
    """Mixture-of-experts FFN (Switch-style top-1, dense dispatch). Inputs:
    X [.., d], RouterW [d, E], W1 [E, d, ff], W2 [E, ff, d]. Outputs: Out,
    AuxLoss (load-balancing loss — add a multiple of it to the model loss).
    Under a mesh with attr `ep_axis`, experts shard over it and XLA inserts
    the token all-to-alls."""
    from ...parallel import current_mesh
    from ...parallel.moe import moe_ffn

    x = one(ins, "X")
    router_w, w1, w2 = one(ins, "RouterW"), one(ins, "W1"), one(ins, "W2")
    ep_axis = attrs.get("ep_axis", "ep")
    mesh = current_mesh()
    if mesh is not None and ep_axis not in mesh.axis_names:
        mesh = None
    out, aux = moe_ffn(
        x, router_w, w1, w2, mesh=mesh, ep_axis=ep_axis,
        capacity_factor=float(attrs.get("capacity_factor", 1.25)),
    )
    return {"Out": out, "AuxLoss": aux}
