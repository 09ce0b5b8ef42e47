"""Mesh pre-flight rules + cost models (ISSUE 8).

The graph-lint suite (rules.py) checks one-device programs; this module
checks the program's *mesh story* before any multi-chip compile — the
three classes of silent SPMD disaster plus the two numbers a capacity
plan needs:

  * **replication-blowup** (error) — a step operand big enough to
    matter, fully replicated along a mesh axis it could shard (a KV
    cache or weight replicated over ``mp`` multiplies its HBM by the
    axis size);
  * **resharding-hazard** (warning) — a ``with_sharding_constraint``
    conflicting with the operand's propagated sharding: GSPMD obeys it
    by inserting a cross-device reshard on the hot path;
  * **collective-deadlock** (error) — the collective-order lint
    (distributed/lint.py) folded into the rules framework: cond
    branches with different collective sequences or axis sets, and
    while-loop predicates that can diverge across ranks;
  * :func:`comm_report` — Megatron-style per-axis communication
    accounting: explicit collectives in the trace (shard_map programs)
    plus the psums GSPMD must insert for dot_generals whose contracted
    dimension is sharded, plus resharding transfers, each costed in
    bytes per step per mesh axis;
  * :func:`estimate_peak_hbm` — donation-aware liveness over the
    top-level eqn buffer lifetimes, yielding predicted peak bytes per
    device given the shardings.  Cross-checked against
    ``ServingEngine.cache_hbm_bytes`` by the engines' pre-flight.

Everything here consumes ONE abstract trace (a
:class:`~.core.MeshLintContext`); no devices, no compile.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from .. import flags as _flags
from . import core
from .rules import Rule

__all__ = ["COLLECTIVE_PRIMS", "collective_sig", "walk_collectives",
           "CollectiveDeadlockRule", "ReplicationBlowupRule",
           "ReshardingHazardRule", "default_mesh_rules",
           "collective_cost_bytes",
           "comm_report", "estimate_peak_hbm"]


# primitive names that lower to cross-replica communication; aliases of
# one collective match through the shared core.CANONICAL table.  The
# replication *cast* ("pvary") moves no data and is deliberately absent.
COLLECTIVE_PRIMS = {
    "psum", "psum_invariant", "pmax", "pmin", "all_gather",
    "all_to_all", "ppermute", "reduce_scatter", "psum_scatter", "pgather",
}
COLLECTIVE_PRIMS |= set(core.CANONICAL)

# params that (a) are not sub-jaxprs and (b) identify the collective
_ID_PARAMS = ("axes", "axis_name", "axis_index_groups", "perm",
              "all_gather_dimension", "scatter_dimension", "split_axis",
              "concat_axis", "tiled")


def collective_sig(eqn) -> Tuple:
    """(canonical name, identifying params, input shapes) — the schedule
    entry tests pin and branch comparison matches on.  Axis SETS are part
    of the identity: a psum over ``mp`` in one branch and over ``dp`` in
    the other is a cross-rank mismatch even though the op name agrees."""
    params = {k: v for k, v in eqn.params.items() if k in _ID_PARAMS}
    shapes = tuple(getattr(v.aval, "shape", ()) for v in eqn.invars)
    name = core.canonical_name(eqn.primitive.name)
    return (name, tuple(sorted(
        (k, str(v)) for k, v in params.items())), shapes)


def _uses_axis_index(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "axis_index":
            return True
        for _, sub in core.sub_jaxprs(eqn):
            if _uses_axis_index(sub):
                return True
    return False


def walk_collectives(jaxpr, path: str = "",
                     schedule: Optional[List] = None,
                     violations: Optional[List] = None
                     ) -> Tuple[List, List]:
    """Extract the ordered collective schedule and the rank-divergence
    violations from a jaxpr (recursing through pjit/shard_map/scan/
    cond/while/remat sub-jaxprs).

    schedule: [(path, sig)] in program order — identical for every rank
    on the straight-line path.  violations: [(path, message)] for the
    control-flow patterns that can deadlock on hardware:

      * ``lax.cond`` branches issuing different collective sequences
        (order, identifying params, or axis sets);
      * a collective inside a ``lax.while_loop`` predicate (ranks can
        disagree on the final failing evaluation);
      * collectives in a while body whose predicate reads
        ``axis_index`` (a statically-visible rank-divergent trip
        count).
    """
    schedule = [] if schedule is None else schedule
    violations = [] if violations is None else violations
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            schedule.append((path, collective_sig(eqn)))
            continue
        if name == "cond":
            # every branch must issue the SAME collective sequence: the
            # predicate may be rank-divergent, so any difference is a
            # potential cross-rank deadlock
            branch_scheds = []
            for i, (_, sub) in enumerate(core.sub_jaxprs(eqn)):
                s: List = []
                walk_collectives(sub, f"{path}/cond.branch{i}", s,
                                 violations)
                branch_scheds.append([sig for _, sig in s])
                schedule.extend(s)
            if len({tuple(map(repr, b)) for b in branch_scheds}) > 1:
                violations.append((path, (
                    f"lax.cond branches issue different collective "
                    f"sequences {branch_scheds} — deadlocks if the "
                    "predicate diverges across ranks")))
            continue
        if name == "while":
            body_colls: List = []
            cond_rank_divergent = False
            for k, sub in core.sub_jaxprs(eqn):
                s: List = []
                walk_collectives(sub, f"{path}/while.{k}", s, violations)
                schedule.extend(s)
                if k == "cond_jaxpr":
                    if s:
                        violations.append((path, (
                            f"collective inside a while_loop predicate "
                            f"({[sig[0] for _, sig in s]}) — ranks can "
                            "disagree on the final (failing) "
                            "evaluation")))
                    if _uses_axis_index(sub):
                        cond_rank_divergent = True
                else:
                    body_colls.extend(s)
            if cond_rank_divergent and body_colls:
                violations.append((path, (
                    "while_loop predicate reads axis_index (a "
                    "rank-divergent trip count) with collectives in the "
                    f"body ({[sig[0] for _, sig in body_colls]}) — ranks "
                    "issue different collective counts")))
            continue
        # transparent containers: pjit, shard_map, scan, remat, custom_*…
        for _, sub in core.sub_jaxprs(eqn):
            walk_collectives(sub, f"{path}/{name}", schedule, violations)
    return schedule, violations


@dataclasses.dataclass
class CollectiveDeadlockRule(Rule):
    """The collective-order lint as a Finding-emitting rule: mismatched
    collective order or axis sets across ``cond`` branches, collectives
    in ``while`` predicates, and rank-divergent while-body collective
    counts.  Works on any LintContext (mesh or not) — the collective
    schedule is a property of the traced program, not of the
    shardings.  ``distributed.lint.check_collective_order`` is now a
    thin shim over :func:`walk_collectives`, so the two surfaces can
    never drift."""

    name = "collective-deadlock"
    severity = "error"

    def run(self, ctx: core.LintContext) -> List[core.Finding]:
        _, violations = walk_collectives(ctx.closed.jaxpr)
        return [self._finding(path, msg) for path, msg in violations]


@dataclasses.dataclass
class ReplicationBlowupRule(Rule):
    """Step operands fully replicated along a mesh axis they could
    shard.  A replicated buffer costs its full bytes on EVERY device of
    that axis — for the KV cache or the weights over ``mp`` that is the
    difference between "the model fits" and an OOM at engine start.

    ``axes`` limits which mesh axes are checked: by default every mesh
    axis EXCEPT ``dp`` (replicating params over dp IS data parallelism;
    replicating anything big over mp/sharding/sep is a blowup).
    ``allow`` matches input-label substrings for buffers that are
    deliberately replicated (rope sin/cos tables: small, read-only,
    sharding them buys nothing)."""

    min_bytes: Optional[int] = None
    axes: Optional[Tuple[str, ...]] = None
    allow: Tuple[str, ...] = ("rope",)

    name = "replication-blowup"
    severity = "error"

    def run(self, ctx: core.LintContext) -> List[core.Finding]:
        if not isinstance(ctx, core.MeshLintContext):
            return []
        thr = (self.min_bytes if self.min_bytes is not None
               else int(_flags.flag("graph_lint_replication_min_bytes")))
        check = (self.axes if self.axes is not None
                 else tuple(a for a in ctx.mesh.names if a != "dp"))
        out: List[core.Finding] = []
        for fi in ctx.inputs:
            b = core.aval_bytes(fi.aval)
            if b is None or b < thr:
                continue
            if any(a in fi.label for a in self.allow):
                continue
            spec = ctx.input_spec(fi)
            used = set(core.spec_axes(spec))
            shape = tuple(getattr(fi.aval, "shape", ()))
            for axis in check:
                n = ctx.mesh.size(axis)
                if n <= 1 or axis in used:
                    continue
                shardable = any(
                    d >= n and d % n == 0
                    for d, e in zip(shape, spec or ((),) * len(shape))
                    if e == ())
                if not shardable:
                    continue
                out.append(self._finding(
                    "",
                    f"input '{fi.label}' ({fi.aval.str_short()}, "
                    f"{b} bytes) is fully replicated along mesh axis "
                    f"'{axis}' ({n}-way) though a dimension divides "
                    f"evenly — every device of that axis keeps the "
                    f"whole buffer, {n}x the HBM a sharded layout "
                    f"needs; add '{axis}' to its PartitionSpec or "
                    f"allowlist a deliberate broadcast",
                    bytes=b))
        return out


@dataclasses.dataclass
class ReshardingHazardRule(Rule):
    """``with_sharding_constraint`` annotations that CONFLICT with the
    operand's propagated sharding: GSPMD honours the constraint by
    materialising a resharding transfer (an all-to-all-shaped data
    movement) right there — silent on a cold path, a per-step tax on a
    hot one.  Only proven conflicts fire: an operand whose spec
    propagation could not establish stays silent."""

    min_bytes: Optional[int] = None

    name = "resharding-hazard"
    severity = "warning"

    def run(self, ctx: core.LintContext) -> List[core.Finding]:
        if not isinstance(ctx, core.MeshLintContext):
            return []
        thr = (self.min_bytes if self.min_bytes is not None
               else int(_flags.flag("graph_lint_reshard_min_bytes")))
        out: List[core.Finding] = []
        for rec in ctx.records:
            if rec.eqn.primitive.name != "sharding_constraint":
                continue
            have = rec.in_specs[0] if rec.in_specs else None
            want = rec.out_specs[0] if rec.out_specs else None
            if have is None or want is None or have == want:
                continue
            av = getattr(rec.eqn.invars[0], "aval", None)
            b = core.aval_bytes(av)
            if b is None or b < thr:
                continue
            out.append(self._finding(
                rec.path,
                f"with_sharding_constraint reshards "
                f"{av.str_short()} from {have} to {want} — GSPMD "
                f"inserts a cross-device transfer here every step; "
                f"align the producer's sharding or drop the "
                f"constraint",
                bytes=b))
        return out


def default_mesh_rules() -> Tuple[Rule, ...]:
    """Fresh instances of the mesh-aware rule set (thresholds read the
    graph-lint flags at run time); run alongside ``default_rules()``
    whenever ``analyze``/``check`` get a ``mesh=``."""
    return (ReplicationBlowupRule(), ReshardingHazardRule(),
            CollectiveDeadlockRule())


# ---------------------------------------------------------------------------
# Collective-cost model
# ---------------------------------------------------------------------------

def collective_cost_bytes(prim: str, nbytes: int, n: int) -> int:
    """Bytes one device moves for a collective over an ``n``-way axis
    group, ring-algorithm accounting (BASELINE.md "Mesh pre-flight
    conventions"): psum/pmax/pmin (all-reduce) 2(n-1)/n·B;
    all_gather (n-1)·B of its per-shard input; reduce_scatter and
    all_to_all (n-1)/n·B; ppermute B (each device forwards its shard
    once)."""
    if n <= 1:
        return 0
    name = core.canonical_name(prim)
    if name in ("psum_invariant", "pmax", "pmin"):
        return int(2 * (n - 1) * nbytes / n)
    if name in ("all_gather", "pgather"):
        return int((n - 1) * nbytes)
    if name in ("reduce_scatter", "psum_scatter", "all_to_all"):
        return int((n - 1) * nbytes / n)
    if name == "ppermute":
        return int(nbytes)
    return int(nbytes)


def _eqn_axes(eqn, mesh: core.MeshInfo) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", ())
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes if str(a) in mesh.names)


def _group_size(mesh: core.MeshInfo, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.size(a)
    return n


def comm_report(ctx: core.MeshLintContext) -> Dict[str, Any]:
    """Per-mesh-axis communication accounting for one step of the traced
    program.  Three site kinds:

      * ``collective`` — explicit collectives in the trace (shard_map /
        pmapped code; operand bytes are PER-SHARD, as traced);
      * ``implied_psum`` — a ``dot_general`` whose contracted dimension
        is sharded over an axis: GSPMD completes the partial products
        with an all-reduce of the output over that axis (the
        Megatron-LM row-parallel pattern);
      * ``reshard`` — a proven sharding_constraint conflict (see
        ReshardingHazardRule), costed as an all_to_all of the tensor.

    Sites inside ``scan`` bodies are multiplied by the static trip
    count; ``while`` bodies count once (a documented lower bound).
    """
    mesh = ctx.mesh
    per_axis: Dict[str, Dict[str, Any]] = {
        a: {"bytes_per_step": 0, "collectives": defaultdict(int)}
        for a, n in mesh.axes}
    sites: List[Dict[str, Any]] = []

    def add(kind, path, prim, axes, bytes_moved, count):
        if not axes or bytes_moved <= 0:
            return
        sites.append({"kind": kind, "path": path, "prim": prim,
                      "axes": list(axes),
                      "bytes_per_step": int(bytes_moved * count),
                      "count": int(count)})
        for a in axes:
            per_axis[a]["bytes_per_step"] += int(bytes_moved * count)
            per_axis[a]["collectives"][prim] += int(count)

    # explicit collectives (records cover every region the propagation
    # walker visited, shard_map/scan/while bodies included)
    for rec in ctx.records:
        name = rec.eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            axes = _eqn_axes(rec.eqn, mesh)
            nbytes = sum(core.aval_bytes(getattr(v, "aval", None)) or 0
                         for v in rec.eqn.invars)
            cost = collective_cost_bytes(name, nbytes, _group_size(mesh,
                                                                   axes))
            add("collective", rec.path, core.canonical_name(name), axes,
                cost, rec.multiplier)
        elif name == "dot_general":
            (lc, rc), _ = rec.eqn.params["dimension_numbers"]
            axes: List[str] = []
            for side, dims in ((0, lc), (1, rc)):
                spec = (rec.in_specs[side]
                        if side < len(rec.in_specs) else None)
                if spec is None:
                    continue
                for d in dims:
                    if int(d) < len(spec):
                        axes.extend(a for a in spec[int(d)]
                                    if a not in axes)
            if axes:
                out_b = sum(
                    core.aval_bytes(getattr(v, "aval", None)) or 0
                    for v in rec.eqn.outvars)
                cost = collective_cost_bytes(
                    "psum", out_b, _group_size(mesh, tuple(axes)))
                add("implied_psum", rec.path, "psum_invariant",
                    tuple(axes), cost, rec.multiplier)
        elif name == "sharding_constraint":
            have = rec.in_specs[0] if rec.in_specs else None
            want = rec.out_specs[0] if rec.out_specs else None
            if have is None or want is None or have == want:
                continue
            changed = tuple(sorted(
                set(core.spec_axes(have)) ^ set(core.spec_axes(want))))
            av = getattr(rec.eqn.invars[0], "aval", None)
            b = core.aval_bytes(av) or 0
            cost = collective_cost_bytes(
                "all_to_all", b, _group_size(mesh, changed))
            add("reshard", rec.path, "all_to_all", changed, cost,
                rec.multiplier)

    sites.sort(key=lambda s: (-s["bytes_per_step"], s["path"], s["prim"]))
    for a in per_axis:
        per_axis[a]["collectives"] = dict(per_axis[a]["collectives"])
    return {"per_axis": per_axis,
            "total_bytes_per_step": sum(v["bytes_per_step"]
                                        for v in per_axis.values()),
            "num_sites": len(sites),
            "sites": sites}


# ---------------------------------------------------------------------------
# HBM-liveness estimator
# ---------------------------------------------------------------------------

def estimate_peak_hbm(ctx: core.LintContext) -> Dict[str, Any]:
    """Donation-aware peak-HBM estimate over the top-level eqn buffer
    lifetimes, per device under the propagated shardings (a plain
    LintContext estimates the single-device program).

    Model: every input is resident at entry.  A NON-donated input
    belongs to the caller and stays resident for the whole call (this
    is why a missed donation shows up here as +1x the carry, the HBM
    view of the donation rule's finding).  A donated input is freeable
    after its last use — and an equation producing an output of the
    same aval as an operand dying at that equation updates IN PLACE
    (XLA's buffer reuse), so a KV cache threaded through per-layer
    scatters counts once, not once per layer.  Sub-jaxpr internals are
    not expanded: transients inside a fused region are invisible, so
    the estimate is a lower bound (documented in BASELINE.md, with the
    tolerance the cross-check uses)."""
    mesh = getattr(ctx, "mesh", None) or core.MeshInfo(())
    var_specs = getattr(ctx, "var_specs", {})
    jaxpr = ctx.closed.jaxpr

    def pd_bytes(v) -> int:
        av = getattr(v, "aval", None)
        return core.sharded_bytes(av, var_specs.get(v), mesh) or 0

    donated_idx = {fi.index for fi in ctx.inputs if fi.donated}
    invars = list(jaxpr.invars)
    donated_vars = {v for i, v in enumerate(invars) if i in donated_idx}
    caller_owned = {v for i, v in enumerate(invars)
                    if i not in donated_idx}

    n_eqns = len(jaxpr.eqns)
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not hasattr(v, "val"):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not hasattr(v, "val"):
            last_use[v] = n_eqns            # live through the end

    input_pd = sum(pd_bytes(v) for v in invars)
    donated_pd = sum(pd_bytes(v) for v in donated_vars)
    current = input_pd + sum(pd_bytes(cv) for cv in jaxpr.constvars)
    peak = current

    live = set(invars) | set(jaxpr.constvars)
    for i, eqn in enumerate(jaxpr.eqns):
        dying: List[Any] = []
        for v in eqn.invars:
            if (not hasattr(v, "val") and last_use.get(v) == i
                    and v in live and v not in caller_owned
                    and v not in dying):
                dying.append(v)
        # in-place matching: an output with the aval (and per-device
        # bytes) of an operand dying at this eqn reuses its buffer —
        # the threaded-carry case (per-layer KV scatter) nets zero
        outs = list(eqn.outvars)
        reused = set()
        matched_out = set()
        for o in outs:
            ob = pd_bytes(o)
            oa = getattr(o, "aval", None)
            for v in dying:
                if v in reused:
                    continue
                va = getattr(v, "aval", None)
                if (oa is not None and va is not None
                        and getattr(oa, "shape", None) == getattr(
                            va, "shape", None)
                        and getattr(oa, "dtype", None) == getattr(
                            va, "dtype", None)
                        and pd_bytes(v) == ob):
                    reused.add(v)
                    matched_out.add(o)
                    break
        current += sum(pd_bytes(o) for o in outs
                       if o not in matched_out)
        peak = max(peak, current)
        for v in dying:
            live.discard(v)
            if v not in reused:
                current -= pd_bytes(v)
        for o in outs:
            if o in last_use:       # consumed later (or a result)
                live.add(o)
            else:                   # dead on arrival: buffer freed now
                current -= pd_bytes(o)
        # matched pairs: buffer ownership transfers v -> o; bytes stay
        # in `current` (counted once) until o itself dies

    def _in_spec(fi):
        specs = getattr(ctx, "in_specs", None)
        return (specs[fi.index]
                if specs is not None and fi.index < len(specs) else None)

    cache_pd = sum(core.sharded_bytes(fi.aval, _in_spec(fi), mesh) or 0
                   for fi in ctx.inputs if fi.label.startswith("cache"))
    cache_shards = max([mesh.nshards(_in_spec(fi))
                        for fi in ctx.inputs
                        if fi.label.startswith("cache")] or [1])
    params_pd = sum(core.sharded_bytes(fi.aval, _in_spec(fi), mesh) or 0
                    for fi in ctx.inputs
                    if fi.label.startswith("params"))
    return {"peak_bytes_per_device": int(peak),
            "input_bytes_per_device": int(input_pd),
            "donated_bytes_per_device": int(donated_pd),
            "params_bytes_per_device": int(params_pd),
            "cache_bytes_per_device": int(cache_pd),
            "cache_shards": int(cache_shards),
            "top_level_eqns": n_eqns}
