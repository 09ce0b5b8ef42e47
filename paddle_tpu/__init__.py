"""paddle_tpu — a TPU-native deep-learning framework.

A from-scratch framework with the training capabilities of the reference
(peif1987/Paddle, a PaddlePaddle fork — see SURVEY.md for the structural
analysis), designed jax/XLA/Pallas/pjit-first rather than ported:

  * eager mode ≙ jax eager; ``@to_static``/static graphs ≙ ``jax.jit`` over
    the functional bridge (`paddle_tpu.nn.functional_call`)
  * the PHI kernel library ≙ XLA + Pallas kernels (`paddle_tpu.ops`)
  * Fleet hybrid parallel (DP/TP/PP/ZeRO/SP/CP/EP) ≙ one jax.sharding.Mesh
    + NamedSharding/shard_map (`paddle_tpu.distributed`)
  * ProcessGroupNCCL/TCPStore ≙ jax.distributed + XLA collectives over ICI/DCN
"""

from . import (amp, distributed, flags, framework, hapi, inference, io,
               jit, metric, nn, observability, optimizer, profiler, static,
               tensor, utils)
from .framework import (device_count, get_default_dtype, is_compiled_with_tpu,
                        load, save, seed, set_default_dtype, to_tensor)
from .flags import get_flags, set_flags
# the tensor-ops surface is top-level, like the reference's
# ``paddle.concat``/``paddle.matmul`` (upstream python/paddle/__init__.py)
from .tensor import *  # noqa: F401,F403
from .tensor import Tensor, __all__ as _tensor_all
from .hapi import Model, summary

__version__ = "0.1.0"

__all__ = [
    "amp", "distributed", "flags", "framework", "hapi", "inference", "io",
    "jit", "metric", "nn", "observability", "optimizer", "profiler",
    "static", "tensor", "utils",
    "Model", "summary",
    "seed", "to_tensor", "device_count", "is_compiled_with_tpu",
    "get_default_dtype", "set_default_dtype", "get_flags", "set_flags",
    "save", "load", "__version__",
] + list(_tensor_all)
