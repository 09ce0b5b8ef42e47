"""Collective-order lint — the SPMD deadlock sanitizer.

TPU-native counterpart of the reference's comm sanitizers (SURVEY §5
sanitizers row: upstream relies on NCCL watchdog flags like
FLAGS_nccl_blocking_wait plus the StreamSafeCUDAAllocator's structural
guarantees; "XLA's checker + a shard_map collective-order lint of our own"
is the stated TPU design).

Under GSPMD/shard_map every rank runs ONE traced program, so plain
straight-line code cannot reorder collectives across ranks — the classic
NCCL mismatched-collective hang is impossible by construction.  The
residual risk lives in *control flow*:

  * branches of ``lax.cond`` whose collective sequences differ (jax's vma
    typing already rejects different collective *sets*; the lint also
    catches same-type-different-comm cases — reordered collectives,
    mismatched ppermute rings): if the predicate ever diverges across
    ranks, the program deadlocks on hardware;
  * a collective inside a ``lax.while_loop``'s *cond* function (the final
    failing evaluation may disagree across ranks);
  * a collective inside a while_loop's *body* when the predicate reads
    ``axis_index`` — a statically-visible rank-divergent trip count, so
    ranks issue different collective counts.  Body collectives under a
    rank-uniform predicate are legitimate and pass.

As of ISSUE 8 the walk itself lives in
:mod:`paddle_tpu.static_analysis.mesh_rules` as the
``collective-deadlock`` rule (:func:`~paddle_tpu.static_analysis
.mesh_rules.walk_collectives`), where it runs mesh-wide alongside the
sharding-propagation rules; this module is the original API kept as a
thin shim — same :class:`CollectiveOrderError`, same schedule format,
same violation strings — so every existing caller and test is
untouched.  The schedule is still returned so callers can pin it in
tests (a collective-order regression is then a visible diff, the
reference's "log the NCCL op sequence" debugging technique made
structural).

``FLAGS_collective_lint`` makes every ``build_train_step`` product run
this lint at its first call (the earliest point batch shapes exist) —
one abstract trace, nothing per step after.
"""

from __future__ import annotations

from typing import List

import jax

from ..static_analysis.core import (CANONICAL as _CANONICAL,
                                    sub_jaxprs as _sub_jaxprs)
from ..static_analysis.mesh_rules import (COLLECTIVE_PRIMS
                                          as _COLLECTIVE_PRIMS,
                                          collective_sig as _sig,
                                          walk_collectives
                                          as _walk_collectives)

__all__ = ["CollectiveOrderError", "collective_schedule",
           "check_collective_order", "check_collectives"]


class CollectiveOrderError(RuntimeError):
    """A collective schedule that can diverge across ranks."""


def collective_schedule(fn, *args, **kwargs):
    """Trace ``fn`` and return (schedule, violations) without raising.

    schedule: list of (path, (primitive, params, input_shapes)) in program
    order — identical for every rank on the straight-line path.
    """
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    schedule, violations = _walk_collectives(jaxpr.jaxpr)
    msgs: List[str] = [f"{path}: {msg}" for path, msg in violations]
    return schedule, msgs


def check_collective_order(fn, *args, **kwargs):
    """Lint ``fn``'s collective schedule; raise CollectiveOrderError on a
    rank-divergence hazard, else return the schedule."""
    schedule, violations = collective_schedule(fn, *args, **kwargs)
    if violations:
        raise CollectiveOrderError("\n".join(violations))
    return schedule


# reference-parity alias (the upstream sanitizer surface this shim
# preserves predates the Finding-based rule)
check_collectives = check_collective_order
