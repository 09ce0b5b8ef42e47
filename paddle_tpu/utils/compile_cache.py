"""Where the persistent XLA compilation cache lives.

One rule for every entry point that compiles (``chip_smoke.py``, the
``bench.py`` children, ``tests/conftest.py``): if
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing is
set in code — whoever runs the program places the cache.  Otherwise the
cache sits at ``<checkout>/.jax_cache`` (git-ignored): a fixed path, so a
second process or a second run of the same checkout finds what the first
one compiled.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory (see the
    module docstring) and return that directory.  Call before the first
    compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # keep everything, not only compiles over jax's 1 s default: the CPU
    # suite builds the same sub-second programs in thousands of fresh
    # engines, and a hit beats a recompile within one cold run too
    # (tier-1 on 8 cores, PR 21: 547 s warm / 1166 s cold at 0 s, against
    # 914 s / 1235 s at the 0.5 s it used before; ~55 MB on disk)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
