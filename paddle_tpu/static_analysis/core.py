"""Shared jaxpr-walking core for paddle_tpu's static analyzers.

PAPER.md's sanitizer row names the TPU-native answer to upstream
Paddle's NCCL watchdog + StreamSafeCUDAAllocator as "XLA's checker + a
shard_map collective-order lint of our own".  The collective lint
(distributed/lint.py) was the first such rule; this module is the
machinery it and every later rule share, factored out so there is ONE
table of jax's primitive aliases, ONE sub-jaxpr discovery convention, and ONE structured :class:`Finding` shape:

  * :func:`sub_jaxprs` / :func:`iter_eqns` — duck-typed discovery and
    recursive walking of the jaxprs hiding in eqn params (pjit bodies,
    scan/cond/while branches, shard_map, remat, custom_* rules);
  * :data:`CANONICAL` / :func:`canonical_name` — the primitive-name
    mapping (``psum``/``psum_invariant`` are one collective, traced with
    and without shard_map's vma check);
  * :func:`trace_for_lint` — one abstract trace of a python function
    into a :class:`LintContext` (closed jaxpr + flat labelled inputs +
    donation marks), the input every graph-lint rule consumes.

Nothing here runs device code: ``jax.make_jaxpr`` is abstract, so a lint
pass costs one trace, before any compile or dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax

__all__ = ["Finding", "GraphLintError", "GraphLintWarning", "CANONICAL",
           "canonical_name", "sub_jaxprs", "iter_eqns", "aval_bytes",
           "FlatInput", "LintContext",
           "trace_for_lint", "MeshInfo", "canon_spec", "spec_axes",
           "sharded_bytes", "EqnRecord", "propagate_shardings",
           "MeshLintContext", "trace_for_mesh_lint"]


class GraphLintError(RuntimeError):
    """Static-analysis findings promoted to an error (``check`` /
    ``enforce`` under ``FLAGS_graph_lint='raise'``)."""


class GraphLintWarning(UserWarning):
    """Findings surfaced under ``FLAGS_graph_lint='warn'``."""


@dataclasses.dataclass(frozen=True)
class Finding:
    """One structured lint finding.

    ``rule``: the rule id (``donation``, ``dtype-promotion``, ...);
    ``severity``: ``error`` (a perf/memory bug on the serving hot path)
    or ``warning`` (a hazard worth a look); ``path``: the eqn path
    through the jaxpr (``""`` = the traced function's top level /
    its input-output signature); ``bytes``: estimated HBM at stake,
    where the rule can size it.
    """

    rule: str
    severity: str
    path: str
    message: str
    bytes: Optional[int] = None

    def as_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"rule": self.rule, "severity": self.severity,
                             "path": self.path, "message": self.message}
        if self.bytes is not None:
            d["bytes"] = int(self.bytes)
        return d

    def __str__(self) -> str:
        b = f" [{self.bytes} bytes]" if self.bytes is not None else ""
        return (f"{self.rule}({self.severity}) "
                f"{self.path or '<signature>'}: {self.message}{b}")


# primitive name -> the canonical name schedules report (and tests pin):
# lax.psum traces as "psum_invariant" under shard_map's vma type system
# and as "psum" with check_vma=False — one collective, so analyzers match
# through this table.
CANONICAL: Dict[str, str] = {
    "psum": "psum_invariant",
    "psum_invariant": "psum_invariant",
    "all_gather_invariant": "all_gather",
}


def canonical_name(name: str) -> str:
    """Canonical primitive name (see :data:`CANONICAL`)."""
    return CANONICAL.get(name, name)


def sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """(param_name, jaxpr) pairs hiding in an eqn's params (duck-typed: a
    ClosedJaxpr exposes ``.jaxpr``, a raw Jaxpr exposes ``.eqns``)."""
    out = []
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (tuple, list)) else [v]
        for item in vals:
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                out.append((k, item.jaxpr))
            elif hasattr(item, "eqns"):          # raw Jaxpr
                out.append((k, item))
    return out


def iter_eqns(jaxpr, path: str = "") -> Iterator[Tuple[str, Any]]:
    """Yield ``(path, eqn)`` for every equation reachable from ``jaxpr``,
    descending into sub-jaxprs (pjit bodies, scan/cond/while branches,
    shard_map, remat, custom_* rules).  Path components are primitive
    names; primitives carrying a string ``name`` param (pjit, remat)
    append it as ``pjit[softmax]`` so rules can allowlist regions by the
    traced function's own name."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        name = eqn.primitive.name
        tag = eqn.params.get("name")
        comp = f"{name}[{tag}]" if isinstance(tag, str) else name
        for _, sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, f"{path}/{comp}")


def aval_bytes(aval) -> Optional[int]:
    """Byte size of an abstract value, or None when it has no static
    numeric size (extended dtypes like PRNG keys, symbolic dims)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return None
    try:
        if jax.dtypes.issubdtype(dtype, jax.dtypes.extended):
            return None
        size = 1
        for d in shape:
            size *= int(d)
        return int(size * dtype.itemsize)
    except Exception:
        return None


@dataclasses.dataclass(frozen=True)
class FlatInput:
    """One flattened input leaf of the traced call: its position in
    ``closed.in_avals``, a human label (argname + pytree keypath), its
    aval, and whether the caller donates it."""

    index: int
    label: str
    aval: Any
    donated: bool


@dataclasses.dataclass
class LintContext:
    """Everything a rule needs from ONE abstract trace."""

    closed: Any                      # ClosedJaxpr from jax.make_jaxpr
    inputs: List[FlatInput]
    out_avals: List[Any]
    fn_name: str


def _arg_names(fn, nargs: int) -> List[str]:
    """Positional parameter names of ``fn`` (labels + donate_argnames
    resolution); falls back to argN for builtins/odd signatures."""
    import inspect
    try:
        params = [p for p in inspect.signature(fn).parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY,
                                p.POSITIONAL_OR_KEYWORD)]
        names = [p.name for p in params[:nargs]]
    except (TypeError, ValueError):
        names = []
    names += [f"arg{i}" for i in range(len(names), nargs)]
    return names


def trace_for_lint(fn, *args, donate_argnums=(), donate_argnames=(),
                   **kwargs) -> LintContext:
    """One abstract trace of ``fn`` into a :class:`LintContext`.

    ``fn`` must be the PYTHON function (pre-jit) — pass a
    ``track_retraces`` wrapper's ``python_fn``, never the counted/jitted
    callable, or the lint trace itself would burn a watchdog budget.
    ``donate_argnums``/``donate_argnames`` describe what the real call
    site's ``jax.jit`` donates; they do not change the trace, only the
    donation marks rules read."""
    from jax import tree_util as jtu

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    names = _arg_names(fn, len(args))
    donated_pos = {int(i) for i in (donate_argnums or ())}
    donated_names = {str(n) for n in (donate_argnames or ())}
    for nm in donated_names:
        if nm in names:
            donated_pos.add(names.index(nm))

    leaves = jtu.tree_flatten_with_path((tuple(args), dict(kwargs)))[0]
    inputs: List[FlatInput] = []
    for idx, (kp, _leaf) in enumerate(leaves):
        if idx >= len(closed.in_avals):      # defensive: never misalign
            break
        head, rest = kp[1], kp[2:]           # kp[0] is the (args, kwargs)
        if isinstance(head, jtu.SequenceKey):  # positional arg
            nm = names[head.idx] if head.idx < len(names) \
                else f"arg{head.idx}"
            donated = head.idx in donated_pos
        else:                                  # keyword arg
            nm = str(getattr(head, "key", head))
            donated = nm in donated_names
        label = nm + jtu.keystr(tuple(rest))
        inputs.append(FlatInput(idx, label, closed.in_avals[idx], donated))

    fn_name = getattr(fn, "__qualname__", None) or getattr(
        fn, "__name__", type(fn).__name__)
    return LintContext(closed=closed, inputs=inputs,
                       out_avals=list(closed.out_avals), fn_name=fn_name)


# ---------------------------------------------------------------------------
# Mesh-aware layer (ISSUE 8): sharding specs, propagation, mesh trace
# ---------------------------------------------------------------------------
#
# A "spec" below is the canonical per-dimension sharding of one array:
# a tuple with one entry per dim, each entry the tuple of mesh axis names
# that dim is split over (() = replicated dim).  ``None`` stands for
# UNKNOWN — propagation could not prove anything — which every consumer
# must treat conservatively (replicated for byte accounting, silent for
# hazard rules).  Inputs are never unknown: an input with no declared or
# committed sharding is replicated, which is exactly what jit does with
# an unconstrained operand and exactly the waste the replication-blowup
# rule exists to flag.

Spec = Tuple[Tuple[str, ...], ...]


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    """Abstract mesh for the lint: ordered (axis, size) pairs.  No
    devices — built from a jax ``Mesh``/``AbstractMesh``, a dict, or a
    compact string like ``"mp2dp2"`` — so a pre-flight runs on a laptop
    for a topology that only exists in the cluster."""

    axes: Tuple[Tuple[str, int], ...]

    @classmethod
    def of(cls, mesh) -> "MeshInfo":
        if isinstance(mesh, MeshInfo):
            return mesh
        if isinstance(mesh, str):
            import re
            pairs = re.findall(r"([a-zA-Z_]+?)(\d+)", mesh)
            if not pairs or "".join(a + n for a, n in pairs) != mesh:
                raise ValueError(
                    f"cannot parse mesh string {mesh!r}; expected "
                    f"<axis><size> pairs like 'mp2dp2'")
            return cls(tuple((a, int(n)) for a, n in pairs))
        if isinstance(mesh, dict):
            return cls(tuple((str(a), int(n)) for a, n in mesh.items()))
        names = getattr(mesh, "axis_names", None)
        if names is not None:            # jax Mesh / AbstractMesh
            shape = mesh.shape           # mapping axis -> size
            return cls(tuple((str(a), int(shape[a])) for a in names))
        raise TypeError(f"cannot build MeshInfo from {type(mesh)}")

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(a for a, _ in self.axes)

    def size(self, name: str) -> int:
        for a, n in self.axes:
            if a == name:
                return n
        raise KeyError(name)

    def nshards(self, spec: Optional[Spec]) -> int:
        """Devices one shard of an array with this spec is divided
        over (product of the sizes of every axis the spec uses);
        unknown spec = replicated = 1."""
        if spec is None:
            return 1
        n = 1
        for entry in spec:
            for a in entry:
                n *= self.size(a)
        return n

    def as_dict(self) -> Dict[str, int]:
        return {a: n for a, n in self.axes}


def canon_spec(spec, ndim: int,
               axis_names: Optional[Tuple[str, ...]] = None
               ) -> Optional[Spec]:
    """Canonicalize a PartitionSpec / tuple into the per-dim form,
    padded with replicated dims to ``ndim`` and filtered to
    ``axis_names`` when given.  None passes through (unknown)."""
    if spec is None:
        return None
    entries = list(spec)[:ndim]
    out = []
    for e in entries:
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(str(a) for a in e
                             if axis_names is None or str(a) in axis_names))
        else:
            a = str(e)
            out.append((a,) if axis_names is None or a in axis_names
                       else ())
    out += [()] * (ndim - len(out))
    return tuple(out)


def spec_axes(spec: Optional[Spec]) -> Tuple[str, ...]:
    """Every mesh axis a spec uses, in first-appearance order."""
    if spec is None:
        return ()
    seen = []
    for entry in spec:
        for a in entry:
            if a not in seen:
                seen.append(a)
    return tuple(seen)


def sharded_bytes(aval, spec: Optional[Spec], mesh: MeshInfo
                  ) -> Optional[int]:
    """Per-device bytes of an abstract value under a sharding spec
    (replicated / unknown = the full buffer on every device)."""
    b = aval_bytes(aval)
    if b is None:
        return None
    return -(-b // mesh.nshards(spec))        # ceil division


@dataclasses.dataclass(frozen=True)
class EqnRecord:
    """One equation the propagation walker visited, with the specs it
    proved for the eqn's operands and outputs (None = unknown)."""

    path: str
    eqn: Any
    in_specs: Tuple[Optional[Spec], ...]
    out_specs: Tuple[Optional[Spec], ...]
    multiplier: int        # static trip count (scan length) enclosing it


# reduce-style primitives whose params carry the reduced dims in "axes"
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin",
})


def _prop_eqn(eqn, ins: List[Optional[Spec]], mesh: MeshInfo
              ) -> List[Optional[Spec]]:
    """Local GSPMD-style propagation: given operand specs, what can we
    prove about the outputs?  Conservative — anything not covered by a
    rule falls back to the shape-match heuristic, then to unknown."""
    name = eqn.primitive.name
    out_avals = [getattr(v, "aval", None) for v in eqn.outvars]

    if name == "sharding_constraint":
        sh = eqn.params.get("sharding")
        spec = getattr(sh, "spec", None)
        return [canon_spec(spec, out_avals[0].ndim, mesh.names)]

    if name == "transpose" and ins and ins[0] is not None:
        perm = eqn.params.get("permutation")
        if perm is not None:
            return [tuple(ins[0][int(p)] for p in perm)]

    if name == "broadcast_in_dim" and ins and ins[0] is not None:
        bdims = eqn.params.get("broadcast_dimensions", ())
        src = ins[0]
        out = [()] * out_avals[0].ndim
        for i, d in enumerate(bdims):
            if i < len(src):
                out[int(d)] = src[i]
        return [tuple(out)]

    if name in _REDUCE_PRIMS and ins and ins[0] is not None:
        axes = set(int(a) for a in eqn.params.get("axes", ()))
        kept = tuple(s for d, s in enumerate(ins[0]) if d not in axes)
        return [kept for _ in out_avals]

    if name == "squeeze" and ins and ins[0] is not None:
        dims = set(int(d) for d in eqn.params.get("dimensions", ()))
        return [tuple(s for d, s in enumerate(ins[0]) if d not in dims)]

    if name == "dot_general" and len(ins) >= 2:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        l, r = ins[0], ins[1]
        if l is not None and r is not None:
            lnd = len(l)
            rnd = len(r)
            batch = tuple(l[int(d)] for d in lb)
            lfree = tuple(l[d] for d in range(lnd)
                          if d not in set(map(int, lc))
                          and d not in set(map(int, lb)))
            rfree = tuple(r[d] for d in range(rnd)
                          if d not in set(map(int, rc))
                          and d not in set(map(int, rb)))
            return [batch + lfree + rfree]

    if name in ("dynamic_update_slice", "scatter", "scatter-add",
                "scatter-mul", "scatter-min", "scatter-max") and ins:
        return [ins[0]]

    if name == "dynamic_slice" and ins and ins[0] is not None:
        src_aval = getattr(eqn.invars[0], "aval", None)
        out = []
        for d, s in enumerate(ins[0]):
            same = (src_aval is not None
                    and out_avals[0].shape[d] == src_aval.shape[d])
            out.append(s if same else ())
        return [tuple(out)]

    if name == "concatenate" and ins and all(s is not None for s in ins):
        if len({tuple(s) for s in ins}) == 1:
            dim = int(eqn.params.get("dimension", 0))
            base = list(ins[0])
            base[dim] = ()
            return [tuple(base)]

    if name == "reshape" and ins and ins[0] is not None:
        src_aval = getattr(eqn.invars[0], "aval", None)
        if (src_aval is not None
                and tuple(src_aval.shape) == tuple(out_avals[0].shape)):
            return [ins[0]]

    # shape-match fallback: an output the same shape as a known operand
    # (elementwise chains, convert_element_type, select, where, ...)
    out: List[Optional[Spec]] = []
    for av in out_avals:
        if av is None or getattr(av, "shape", None) is None:
            out.append(None)
            continue
        if av.ndim == 0:
            out.append(())
            continue
        cands = []
        for s, v in zip(ins, eqn.invars):
            va = getattr(v, "aval", None)
            if (s is not None and va is not None
                    and tuple(getattr(va, "shape", ())) == tuple(av.shape)):
                cands.append(tuple(s))
        out.append(cands[0] if cands and len(set(cands)) == 1 else None)
    return out


# eqn params that carry descendable call bodies whose operands map 1:1
# onto the sub-jaxpr's invars (pjit, remat, custom_* forward rules)
_TRANSPARENT_CALLS = frozenset({
    "pjit", "remat", "remat2", "checkpoint", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "closed_call",
    "core_call", "xla_call",
})


def propagate_shardings(closed, in_specs: List[Optional[Spec]],
                        mesh: MeshInfo
                        ) -> Tuple[Dict[Any, Optional[Spec]],
                                   List[EqnRecord]]:
    """Walk the jaxpr forward, assigning every var the sharding spec
    propagation can prove from the input specs, the rule table above,
    and ``with_sharding_constraint`` annotations.  Returns the var->spec
    environment (top level + transparently-descended call bodies) and
    the visit records (one per eqn, with the specs at that site).

    shard_map bodies are recorded (for the collective walk) but their
    operands are per-shard values — specs inside are deliberately
    unknown; the eqn's own outputs take their specs from ``out_names``.
    Control-flow bodies (scan/while/cond) are recorded with a static
    trip-count multiplier (scan length; while = 1, a lower bound) and
    unknown internal specs."""
    env: Dict[Any, Optional[Spec]] = {}
    records: List[EqnRecord] = []

    def read(v) -> Optional[Spec]:
        if hasattr(v, "val"):            # Literal
            nd = getattr(getattr(v, "aval", None), "ndim", 0)
            return ((),) * nd
        return env.get(v)

    def walk(jaxpr, specs_in: List[Optional[Spec]], path: str,
             mult: int) -> List[Optional[Spec]]:
        for var, s in zip(jaxpr.invars, specs_in):
            env[var] = s
        for cv in jaxpr.constvars:
            nd = getattr(getattr(cv, "aval", None), "ndim", 0)
            env[cv] = ((),) * nd
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = [read(v) for v in eqn.invars]
            tag = eqn.params.get("name")
            comp = f"{name}[{tag}]" if isinstance(tag, str) else name
            outs: List[Optional[Spec]]
            if name in _TRANSPARENT_CALLS:
                subs = sub_jaxprs(eqn)
                outs = [None] * len(eqn.outvars)
                if subs:
                    _, body = subs[0]
                    n_extra = len(body.invars) - len(ins)
                    body_in = ([None] * n_extra + ins if n_extra >= 0
                               else ins[:len(body.invars)])
                    outs = walk(body, body_in, f"{path}/{comp}", mult)
                    outs = (outs + [None] * len(eqn.outvars)
                            )[:len(eqn.outvars)]
            elif name == "shard_map":
                out_names = eqn.params.get("out_names") or ()
                outs = []
                for i, v in enumerate(eqn.outvars):
                    nd = getattr(getattr(v, "aval", None), "ndim", 0)
                    try:
                        names_map = out_names[i]
                        spec = [()] * nd
                        for d, axes in dict(names_map).items():
                            spec[int(d)] = tuple(
                                a for a in axes if a in mesh.names)
                        outs.append(tuple(spec))
                    except Exception:
                        outs.append(None)
                for _, body in sub_jaxprs(eqn):
                    walk(body, [None] * len(body.invars),
                         f"{path}/{comp}", mult)
            elif name == "scan":
                length = int(eqn.params.get("length", 1) or 1)
                outs = [None] * len(eqn.outvars)
                for _, body in sub_jaxprs(eqn):
                    walk(body, [None] * len(body.invars),
                         f"{path}/{comp}", mult * max(length, 1))
            elif name in ("while", "cond"):
                outs = [None] * len(eqn.outvars)
                for _, body in sub_jaxprs(eqn):
                    walk(body, [None] * len(body.invars),
                         f"{path}/{comp}", mult)
            else:
                try:
                    outs = _prop_eqn(eqn, ins, mesh)
                except Exception:
                    outs = [None] * len(eqn.outvars)
                outs = (list(outs) + [None] * len(eqn.outvars)
                        )[:len(eqn.outvars)]
            records.append(EqnRecord(path, eqn, tuple(ins), tuple(outs),
                                     mult))
            for v, s in zip(eqn.outvars, outs):
                env[v] = s
        return [read(v) for v in jaxpr.outvars]

    walk(closed.jaxpr, list(in_specs), "", 1)
    return env, records


@dataclasses.dataclass
class MeshLintContext(LintContext):
    """A LintContext traced under an abstract mesh: per-input sharding
    specs (aligned with ``inputs``), the propagated var->spec
    environment, and the eqn visit records the mesh rules and the
    collective-cost model consume."""

    mesh: MeshInfo = None
    in_specs: List[Optional[Spec]] = dataclasses.field(
        default_factory=list)
    var_specs: Dict[Any, Optional[Spec]] = dataclasses.field(
        default_factory=dict)
    records: List[EqnRecord] = dataclasses.field(default_factory=list)
    out_specs: List[Optional[Spec]] = dataclasses.field(
        default_factory=list)

    def input_spec(self, fi: FlatInput) -> Optional[Spec]:
        return self.in_specs[fi.index]


def _declared_specs(args, kwargs, in_shardings, mesh: MeshInfo
                    ) -> List[Spec]:
    """Flatten ``in_shardings`` (a per-positional-arg sequence whose
    entries are None, a single PartitionSpec applied to every leaf of
    that arg, or a spec pytree matching the arg) — or, when None, read
    each leaf's committed NamedSharding — into one canonical spec per
    flat input leaf.  Undeclared/uncommitted leaves are REPLICATED."""
    from jax import tree_util as jtu
    from jax.sharding import PartitionSpec

    def is_spec(x):
        return x is None or isinstance(x, PartitionSpec)

    def leaf_committed(leaf):
        sh = getattr(leaf, "sharding", None)
        spec = getattr(sh, "spec", None)
        m = getattr(sh, "mesh", None)
        if spec is not None and m is not None and any(
                str(a) in mesh.names for a in getattr(m, "axis_names", ())):
            return spec
        return None

    flat: List[Spec] = []
    if in_shardings is not None:
        in_shardings = tuple(in_shardings)
        if len(in_shardings) != len(args):
            raise ValueError(
                f"in_shardings has {len(in_shardings)} entries for "
                f"{len(args)} positional args")
        for arg, sh in zip(args, in_shardings):
            leaves = jtu.tree_leaves(arg)
            if is_spec(sh):
                specs = [sh] * len(leaves)
            else:
                specs = jtu.tree_leaves(sh, is_leaf=is_spec)
                if len(specs) != len(leaves):
                    raise ValueError(
                        f"in_shardings entry with {len(specs)} specs "
                        f"does not match an arg with {len(leaves)} "
                        f"array leaves")
            for leaf, s in zip(leaves, specs):
                nd = getattr(leaf, "ndim", 0)
                flat.append(canon_spec(s, nd, mesh.names)
                            or ((),) * nd)
        for leaf in jtu.tree_leaves(dict(kwargs)):
            flat.append(((),) * getattr(leaf, "ndim", 0))
    else:
        for leaf in jtu.tree_leaves((tuple(args), dict(kwargs))):
            nd = getattr(leaf, "ndim", 0)
            flat.append(canon_spec(leaf_committed(leaf), nd, mesh.names)
                        or ((),) * nd)
    return flat


def trace_for_mesh_lint(fn, *args, mesh, in_shardings=None,
                        donate_argnums=(), donate_argnames=(),
                        **kwargs) -> MeshLintContext:
    """One abstract trace of ``fn`` under an abstract mesh: the base
    :func:`trace_for_lint` context, plus per-input sharding specs
    (declared via ``in_shardings`` or read from the args' committed
    NamedShardings) propagated through the jaxpr.  No devices are
    touched — the mesh may be a jax ``Mesh``/``AbstractMesh``, a dict,
    or a string like ``"mp2dp2"`` for hardware that isn't attached."""
    minfo = MeshInfo.of(mesh)
    base = trace_for_lint(fn, *args, donate_argnums=donate_argnums,
                          donate_argnames=donate_argnames, **kwargs)
    specs = _declared_specs(args, kwargs, in_shardings, minfo)
    specs = (specs + [((),)] * len(base.inputs))[:len(base.inputs)]
    env, records = propagate_shardings(base.closed, specs, minfo)
    out_specs = [env.get(v) for v in base.closed.jaxpr.outvars]
    return MeshLintContext(closed=base.closed, inputs=base.inputs,
                           out_avals=base.out_avals, fn_name=base.fn_name,
                           mesh=minfo, in_specs=specs, var_specs=env,
                           records=records, out_specs=out_specs)
