"""The device a run is on, as JAX reports it."""


class NoAccelerator(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            f"the benchmark measures the TPU; JAX gave platform "
            f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chip(s); JAX sees {len(devs)}")
    return devs[:chips]


def describe(devices):
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices):
    """The peak on the fullest chip, or None where the backend keeps no
    such count (the CPU)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def fetch_scalar(x):
    """Fetch one element that depends on ``x``: the fetch cannot return
    before everything ``x`` depends on has run. The benchmark's barrier."""
    import jax
    import numpy as np

    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(np.asarray(leaf.reshape(-1)[0]))
