"""Operator registry + emitter contract + generic reverse-mode gradient.

Capability-parity with the reference's op registry and grad-op machinery
(`paddle/fluid/framework/op_registry.h:50-195`,
`paddle/fluid/framework/grad_op_desc_maker.h`), redesigned for XLA:

  - An op is not a C++ kernel pair; it is a JAX *emitter*:
        forward(ctx, ins, attrs) -> {out_slot: [jax.Array, ...]}
    where `ins` maps input slot names to lists of arrays. The executor traces
    emitters in program order into ONE function per block and jit-compiles it,
    so XLA fuses across op boundaries (the reference's per-op kernel dispatch
    loop, executor.cc:344, disappears at runtime).

  - Gradients do not need ~125 hand-written grad kernels: a single generic
    grad emitter re-traces the forward emitter under jax.vjp. Because the
    re-traced forward lives in the same XLA computation as the original, CSE
    deduplicates it — semantically this is the reference's GradOpDescMaker,
    with XLA doing the work of `backward.cc`. Ops may still register a custom
    grad emitter (e.g. fused Pallas kernels) via `grad=`.

  - RNG-consuming ops (dropout, *_random) are deterministic functions of a
    per-op seed attr folded into the step key, so the vjp re-trace reproduces
    the same randomness (the reference stores dropout masks instead).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from .enforce import enforce

# attr key carrying the forward-op metadata on generated grad ops
FWD_META_ATTR = "__fwd__"
RNG_SEED_ATTR = "__rng_seed__"
GRAD_SUFFIX = "@GRAD"


class EmitCtx:
    """Per-trace context handed to emitters (role of the reference's
    ExecutionContext, operator.h:185): RNG access, execution mode, and the
    owning Program (control-flow emitters resolve sub-blocks through it)."""

    def __init__(self, root_key=None, is_test: bool = False, program=None):
        self._root_key = root_key
        self.is_test = is_test
        self.program = program

    def rng(self, attrs: Dict[str, Any]):
        """Deterministic per-op key: fold the op's seed into the step key."""
        if self._root_key is None:
            raise RuntimeError("op requires RNG but no key was provided")
        seed = int(attrs.get("seed", 0) or 0)
        op_seed = int(attrs.get(RNG_SEED_ATTR, 0))
        return jax.random.fold_in(self._root_key, seed * 1000003 + op_seed)


class OpInfo:
    def __init__(
        self,
        type: str,
        forward: Callable,
        needs_rng: bool = False,
        grad: Optional[Callable] = None,
        infer_shape: Optional[Callable] = None,
        no_grad: Sequence[str] = (),
        ref: Optional[str] = None,
    ):
        self.type = type
        self.forward = forward
        self.needs_rng = needs_rng
        self.grad = grad
        self.infer_shape = infer_shape
        self.no_grad = frozenset(no_grad)
        self.ref = ref


OPS: Dict[str, OpInfo] = {}


def register_op(
    type: str,
    needs_rng: bool = False,
    grad: Optional[Callable] = None,
    infer_shape: Optional[Callable] = None,
    no_grad: Sequence[str] = (),
    ref: Optional[str] = None,
):
    """Decorator registering a forward emitter under an op type name
    (role of REGISTER_OPERATOR / REGISTER_OP_CUDA_KERNEL,
    op_registry.h:127,192)."""

    def deco(fn):
        enforce(type not in OPS, "op '%s' registered twice", type,
                context="register_op")
        OPS[type] = OpInfo(
            type, fn, needs_rng=needs_rng, grad=grad, infer_shape=infer_shape,
            no_grad=no_grad, ref=ref,
        )
        return fn

    return deco


def get_op_info(type: str) -> OpInfo:
    if type not in OPS:
        raise KeyError(f"no emitter registered for op type '{type}'")
    return OPS[type]


def has_op(type: str) -> bool:
    return type in OPS


def normalize_outs(outs) -> Dict[str, List[Any]]:
    """Emitters may return a single array, a dict of arrays, or a dict of
    lists; canonicalize to dict slot -> list."""
    if not isinstance(outs, dict):
        outs = {"Out": outs}
    norm = {}
    for slot, v in outs.items():
        if isinstance(v, (list, tuple)):
            norm[slot] = list(v)
        else:
            norm[slot] = [v]
    return norm


def _is_diff(x) -> bool:
    return x is not None and jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)


def run_forward(ctx: EmitCtx, op_type: str, ins, attrs) -> Dict[str, List[Any]]:
    info = get_op_info(op_type)
    return normalize_outs(info.forward(ctx, ins, attrs))


def exec_op_descs(ctx: EmitCtx, op_descs, env: Dict[str, Any],
                  skip_types=("feed", "fetch"), keep=frozenset()):
    """Trace a list of OpDescs into env — the executor's hot loop, also used
    by control-flow emitters on sub-blocks (the reference nests Executors,
    while_op.cc:35; here it's one trace). `keep` protects names (fetch
    targets) from delete_var."""
    for od in op_descs:
        if od.type in skip_types:
            continue
        if od.type == "delete_var":
            # memory_optimization_transpiler.release_memory marker: drop the
            # traced value so XLA's liveness ends here (reference
            # delete_var_op.cc frees the buffer). Fetch targets survive —
            # this executor injects fetches at run time, so program-level
            # liveness can't see them (unlike the reference's fetch ops).
            for n in od.input_names():
                if n not in keep:
                    env.pop(n, None)
            continue
        ins = {
            slot: [env.get(n) if n else None for n in names]
            for slot, names in od.inputs.items()
        }
        # every device operation this op emits carries its Fluid type in
        # its op_name (conv2d, batch_norm_grad, momentum...): trace-time
        # metadata, read off a device trace
        with jax.named_scope(od.type):
            if od.type.endswith("_grad") and FWD_META_ATTR in od.attrs:
                outs = run_grad(ctx, ins, od.attrs)
            else:
                outs = run_forward(ctx, od.type, ins, od.attrs)
        for slot, names in od.outputs.items():
            vals = outs.get(slot, [])
            for i, n in enumerate(names):
                if n and i < len(vals) and vals[i] is not None:
                    env[n] = vals[i]


def run_grad(ctx: EmitCtx, ins: Dict[str, List[Any]], attrs: Dict[str, Any]):
    """Execute a generated `<type>_grad` op.

    Grad op IO convention (mirrors the reference's grad-op descs,
    grad_op_desc_maker.h):
      inputs:  fwd input slots as-is; fwd outputs under 'Out@<slot>';
               incoming output-gradients under 'GRAD@<out_slot>'
               (missing / '' entries mean "no gradient flows here")
      outputs: input-gradients under 'GRAD@<in_slot>'
    """
    meta = attrs[FWD_META_ATTR]
    info = get_op_info(meta["type"])
    fwd_attrs = dict(meta["attrs"])
    fwd_ins = {s: list(ins.get(s, [])) for s in meta["in_slots"]}

    if info.grad is not None:
        fwd_outs = {s: list(ins.get("Out@" + s, [])) for s in meta["out_slots"]}
        out_grads = {s: list(ins.get("GRAD@" + s, [])) for s in meta["out_slots"]}
        return normalize_outs(info.grad(ctx, fwd_ins, fwd_outs, out_grads, fwd_attrs))

    # generic path: vjp through the forward emitter w.r.t. inexact inputs
    diff_paths = [
        (s, i)
        for s, lst in fwd_ins.items()
        for i, x in enumerate(lst)
        if _is_diff(x) and s not in info.no_grad
    ]
    if not diff_paths:
        return {}

    def f(diff_vals):
        cur = {s: list(lst) for s, lst in fwd_ins.items()}
        for (s, i), v in zip(diff_paths, diff_vals):
            cur[s][i] = v
        return normalize_outs(info.forward(ctx, cur, fwd_attrs))

    primals = [fwd_ins[s][i] for s, i in diff_paths]
    out_primals, vjp_fn = jax.vjp(f, primals)

    cts = {}
    for s, lst in out_primals.items():
        gl = ins.get("GRAD@" + s, [])
        cts[s] = [
            gl[i]
            if i < len(gl) and gl[i] is not None
            else jnp.zeros_like(lst[i])
            for i in range(len(lst))
        ]
    (gins,) = vjp_fn(cts)

    result: Dict[str, List[Any]] = {}
    for s in fwd_ins:
        result["GRAD@" + s] = [None] * len(fwd_ins[s])
    for (s, i), g in zip(diff_paths, gins):
        result["GRAD@" + s][i] = g
    return result
