"""Backend dispatch helpers for ops with Pallas fast paths."""

from __future__ import annotations

import contextlib
import functools
import threading

import jax

from .. import flags

# trace-time kernel-path relabel hint (see kernel_path_hint): thread-local
# so concurrent traces (pytest-xdist, background compiles) don't cross
_HINT = threading.local()


@functools.cache
def default_backend() -> str:
    return jax.default_backend()


@contextlib.contextmanager
def kernel_path_hint(op: str):
    """Relabel ``ops.kernel_path`` counts made while the context is open.

    Dispatch counting happens at TRACE time, so a caller that knows what a
    shape *means* — the serving engine tracing its speculative-decode
    verify step, where the q window is draft tokens, not a prefill chunk —
    wraps the traced call and every routing decision inside lands under
    ``op=<hint>`` (e.g. ``spec_verify``) instead of the generic op name.
    Purely an observability relabel: routing itself is unchanged.
    """
    prev = getattr(_HINT, "op", None)
    _HINT.op = op
    try:
        yield
    finally:
        _HINT.op = prev


def kernel_path_op(default: str) -> str:
    """The op label a dispatch site should count under: the innermost
    active :func:`kernel_path_hint`, or ``default``."""
    return getattr(_HINT, "op", None) or default


def use_pallas() -> bool:
    """True when the Pallas TPU path should be taken.

    On TPU: always.  Elsewhere: only when FLAGS_pallas_interpret is set
    (Pallas interpreter mode — used to test the kernels on CPU).
    """
    if flags.flag("pallas_interpret"):
        return True
    return default_backend() == "tpu"


def pallas_interpret() -> bool:
    return bool(flags.flag("pallas_interpret")) or default_backend() != "tpu"


def count_kernel_path(op: str, path: str, **labels) -> None:
    """Count one kernel-routing decision in the shared metrics registry
    (``ops.kernel_path{op=...,path=...}``).

    Dispatch decisions run at TRACE time, so the counter reads as
    "compiled programs that chose this path", not calls — zero per-step
    cost, and a routing regression (a serving shape silently sliding off
    its Pallas kernel onto the XLA fallback) shows up as a counter
    moving in ``observability.snapshot()`` instead of only as a perf
    mystery.  Extra ``labels`` refine the series (``cache="paged"``).
    """
    from .. import observability
    observability.default_registry().counter(
        "ops.kernel_path",
        "kernel-path selections per op, counted at dispatch/trace time",
    ).labels(op=op, path=path, **labels).inc()
