"""Core types: places, data types, var types.

Capability-parity with the reference's `paddle/fluid/platform/place.h:25-75`
(Place variant) and `paddle/fluid/framework/framework.proto:94` (VarType),
re-expressed for a JAX/XLA runtime where a "place" maps to a jax.Device set.
"""
from __future__ import annotations

import enum

import jax
import jax.numpy as jnp
import numpy as np


class EOFException(Exception):
    """End of a reader's data stream (reference fluid.core.EOFException,
    thrown by read_op when the underlying reader is exhausted). Catch it
    around Executor.run and reset the reader / end the pass."""


class VarType(enum.Enum):
    # mirrors framework.proto VarType.Type (reference framework.proto:94)
    LOD_TENSOR = "lod_tensor"
    SELECTED_ROWS = "selected_rows"
    FEED_MINIBATCH = "feed_minibatch"
    FETCH_LIST = "fetch_list"
    STEP_SCOPES = "step_scopes"
    LOD_RANK_TABLE = "lod_rank_table"
    LOD_TENSOR_ARRAY = "lod_tensor_array"
    READER = "reader"
    RAW = "raw"


# dtype canonicalization: user-facing dtypes are strings ('float32', ...);
# emitters use jnp dtypes. bf16 is first-class (TPU native), fp16 kept for
# parity with reference platform/float16.h.
_DTYPE_ALIASES = {
    "float": "float32",
    "double": "float64",
    "half": "float16",
    "int": "int32",
    "long": "int64",
    "bfloat16": "bfloat16",
}


def convert_dtype(dtype) -> str:
    if isinstance(dtype, str):
        dtype = _DTYPE_ALIASES.get(dtype, dtype)
        return str(np.dtype(dtype)) if dtype != "bfloat16" else "bfloat16"
    if dtype is jnp.bfloat16 or getattr(dtype, "name", None) == "bfloat16":
        return "bfloat16"
    return str(np.dtype(dtype))


def as_jnp_dtype(dtype):
    dtype = convert_dtype(dtype)
    return jnp.bfloat16 if dtype == "bfloat16" else np.dtype(dtype)


class Place:
    """Device placement tag (reference place.h:25).

    On TPU all compute places resolve to the PJRT TPU client; CPUPlace is the
    host. Kept as API surface — XLA decides actual layout/placement.
    """

    _kind = "base"

    def __repr__(self):
        return f"{type(self).__name__}()"

    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "device_id", 0) == getattr(
            other, "device_id", 0
        )

    def __hash__(self):
        return hash((type(self).__name__, getattr(self, "device_id", 0)))


class CPUPlace(Place):
    _kind = "cpu"


class TPUPlace(Place):
    _kind = "tpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


# CUDAPlace alias kept so reference-era scripts port mechanically.
CUDAPlace = TPUPlace


def default_place() -> Place:
    """The place of the backend JAX gives. A backend that fails to
    initialize raises here; nothing substitutes the CPU for it."""
    if jax.default_backend() == "cpu":
        return CPUPlace()
    return TPUPlace(0)


def is_compiled_with_tpu() -> bool:
    return any(d.platform != "cpu" for d in jax.devices())
