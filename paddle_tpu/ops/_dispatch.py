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


@contextlib.contextmanager
def program_part(program: str, part: str):
    """Name one part of a jitted program for the device trace: a
    ``jax.named_scope(part)`` over everything traced inside, and the
    prefix ``<program>_<part>`` on the name of every Pallas kernel built
    inside (see :func:`kernel_name`).

    XLA names a Mosaic custom call's HLO instruction after the innermost
    name-stack entry around the ``pallas_call`` — without a ``name`` that
    is the jitted function itself, which is why a profile used to show
    the serving step's decode rows and its prompt chunk as one kernel
    ``_mixed_step_impl_paged``.  With the prefix the trace says which
    program and which part of it a kernel call served:
    ``_step_impl_decode_rows_flash_decode`` against
    ``_step_impl_prompt_chunk_flash_decode``.  Trace-time only, like
    :func:`kernel_path_hint`; thread-local for the same reason."""
    prev = getattr(_HINT, "kernel_prefix", None)
    _HINT.kernel_prefix = f"{program}_{part}"
    try:
        with jax.named_scope(part):
            yield
    finally:
        _HINT.kernel_prefix = prev


def kernel_name(base: str) -> str:
    """The ``name=`` of a ``pallas_call`` being built: ``base`` (the
    kernel's own name), led by the innermost :func:`program_part`'s
    ``<program>_<part>_`` when one is open."""
    prefix = getattr(_HINT, "kernel_prefix", None)
    return f"{prefix}_{base}" if prefix else base


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
