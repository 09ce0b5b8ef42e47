"""Framework core: dtypes, RNG, device helpers."""

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype  # noqa: PLC0414
from . import random as random  # noqa: PLC0414
from .dtype import get_default_dtype, set_default_dtype, to_jax_dtype
from .io import load, save
from .random import get_rng_state_tracker, seed

__all__ = [
    "dtype", "random", "seed", "get_rng_state_tracker",
    "get_default_dtype", "set_default_dtype", "to_jax_dtype",
    "to_tensor", "device_count", "is_compiled_with_tpu", "save", "load",
]


def to_tensor(data, dtype=None, place=None):
    """Parity: ``paddle.to_tensor`` — returns a jax.Array."""
    dt = to_jax_dtype(dtype) if dtype is not None else None
    x = jnp.asarray(data, dtype=dt)
    if place is not None:
        x = jax.device_put(x, place)
    return x


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"
