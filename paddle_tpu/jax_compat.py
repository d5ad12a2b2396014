"""Thin helpers over the one installed JAX (0.9.0): named-axis mesh
construction, collective counting over lowered text, and plain-dict
views of XLA's cost and memory analyses."""
from __future__ import annotations


def make_device_mesh(axes, devices=None):
    """Named-axis device Mesh construction (ISSUE 15). ``axes``: ordered
    {name: size}. Uses the first prod(sizes) devices when more are
    available (tier-1's virtual 8-device CPU mesh frequently outnumbers
    a 2-way test mesh); on TPU ``mesh_utils.create_device_mesh`` orders
    them by ICI topology and a topology it cannot lay out raises;
    off-TPU a plain reshape (virtual CPU devices have no topology).
    Typed error when devices run short."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    names = tuple(str(n) for n in axes)
    shape = tuple(int(axes[n]) for n in axes)
    need = int(np.prod(shape))
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < need:
        raise ValueError(
            f"mesh {dict(zip(names, shape))} needs {need} devices, have "
            f"{len(devs)} — off-TPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    devs = devs[:need]
    if devices is None and devs[0].platform == "tpu":
        from jax.experimental import mesh_utils

        return Mesh(mesh_utils.create_device_mesh(shape, devices=devs),
                    names)
    return Mesh(np.asarray(devs).reshape(shape), names)


# collective HLO spellings as they appear in StableHLO / HLO text; the
# keys are the counter suffixes mesh.observe registers
_COLLECTIVE_OPS = (
    ("all_reduce", ("all_reduce", "all-reduce")),
    ("all_gather", ("all_gather", "all-gather")),
    ("reduce_scatter", ("reduce_scatter", "reduce-scatter")),
    ("collective_permute", ("collective_permute", "collective-permute")),
    ("all_to_all", ("all_to_all", "all-to-all")),
)


def collective_counts(lowered_text: str) -> dict:
    """Count collective ops in a lowered/compiled program's text — the
    compile-time evidence of what the SPMD partitioner inserted (host
    code cannot time individual device collectives; it CAN count them
    exactly). Returns {kind: count} with zero entries elided."""
    out = {}
    for kind, spellings in _COLLECTIVE_OPS:
        n = 0
        for s in spellings:
            n += lowered_text.count(f"stablehlo.{s} ") + \
                lowered_text.count(f"stablehlo.{s}(")
            n += lowered_text.count(f" {s}(")  # HLO text form
        if n:
            out[kind] = n
    return out


def cost_analysis_dict(stage) -> dict:
    """`.cost_analysis()` of a Lowered (an HLO walk, no XLA compile) or
    Compiled stage as a {str: float} dict. Returns {} when the backend
    offers no analysis — callers treat cost accounting as best-effort
    evidence, never a hard dependency."""
    try:
        ca = stage.cost_analysis()
    except Exception:
        return {}
    if not isinstance(ca, dict):
        return {}
    return {str(k): float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def memory_analysis_dict(compiled) -> dict:
    """`Compiled.memory_analysis()` -> plain byte-count dict ({} when the
    backend doesn't implement it). Field names follow the XLA
    CompiledMemoryStats attributes present on this jaxlib."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "host_temp_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out
