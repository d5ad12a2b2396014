"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
early-2018 PaddlePaddle (reference: zhye5230/Paddle), redesigned for JAX/XLA.

Architecture (vs the reference):
  - The reference builds a protobuf ProgramDesc from Python and interprets it
    op-by-op with a C++ Executor dispatching CUDA kernels
    (reference: paddle/fluid/framework/executor.cc:133).
  - Here the same Program IR is built from Python, but the Executor is a
    *compiler client*: each block is lowered to ONE XLA computation via JAX
    tracing of per-op emitters, jit-compiled and cached, with all state
    (parameters, optimizer accumulators, BN stats) resident in device HBM.
  - Multi-device data/model parallelism is expressed with jax.sharding over a
    device Mesh; XLA inserts ICI collectives where the reference inserted
    NCCLAllReduceOpHandle (reference:
    paddle/fluid/framework/details/multi_devices_graph_builder.cc:167).
"""

__version__ = "0.1.0"

import os as _os


def place_compile_cache():
    """Point JAX's persistent compilation cache at a fixed place before
    anything compiles. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    reads it itself and nothing is set here; otherwise the cache lives
    in ``<checkout>/.jax_cache`` (git-ignored). The path is part of the
    cache key, so it is never a temp name, a pid or a time. Returns the
    directory set in code, or None when the environment placed it."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    path = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


place_compile_cache()

from . import observability  # noqa: F401  (no heavy deps; before fluid)
from . import fluid  # noqa: F401
from . import dataset, reader  # noqa: F401
from .reader import batch  # noqa: F401

# PADDLE_TPU_SANITIZE=guards: instrument the guarded-by-annotated runtime
# classes so every declared-guard access asserts its lock is held (the
# dynamic half of the analysis/guards.py lint). Zero import cost unset.
if fluid.flags.FLAGS["sanitize"]:
    from .analysis import sanitize as _sanitize

    _sanitize.maybe_install()
