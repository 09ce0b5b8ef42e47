"""paddle_tpu.static_analysis — jaxpr graph lint for the serving hot path.

PAPER.md's sanitizer row ("XLA's checker + a shard_map collective-order
lint of our own") shipped its first rule as the collective-order lint in
``distributed/lint.py``; this package generalizes that one-off into a
static-analysis LAYER: one shared jaxpr walker (:mod:`.core` — the
collective lint is its first client) plus pluggable rules
(:mod:`.rules`) producing structured :class:`Finding`\\ s, each a class
of silent perf/memory bug that ONE abstract trace catches before any
device run:

  * **donation** (error) — jitted outputs whose aval matches a
    non-donated input: the serving step threads the full KV cache, so a
    missed ``donate_argnums`` double-buffers the dominant HBM consumer;
  * **dtype-promotion** (warning) — f32/f64 widenings of large
    low-precision operands (allowlist for softmax/norm accumulators);
  * **constant-capture** (error) — big arrays baked into the jaxpr as
    consts (weights closed over ⇒ HBM bloat + retrace on update);
  * **host-sync** (error) — ``pure_callback``/``io_callback``/
    ``debug_callback``/infeed/outfeed inside a step (would serialize the
    tick loop; observability hooks are allowlisted);
  * **retrace-hazard** (warning) — weak-typed scalar leaks and
    non-canonical dtypes in the call signature, the before-the-fact
    complement of the retrace watchdog's budget.

The MESH pre-flight layer (ISSUE 8, :mod:`.mesh_rules`) extends the
same one-trace framework to mesh-partitioned programs: a
sharding-propagation walker annotates operands with per-axis shardings
under an ABSTRACT mesh (``"mp2dp2"`` works on a laptop), three more
rules check the SPMD story — **replication-blowup** (error: a big
operand fully replicated along an axis it could shard),
**resharding-hazard** (warning: conflicting
``with_sharding_constraint``), **collective-deadlock** (error: the
collective-order lint folded into the rules framework;
``distributed/lint.py`` is now a shim over the shared walker) — and
two cost models report predicted per-axis collective bytes per step
(:func:`comm_report`) and donation-aware per-device peak HBM
(:func:`estimate_peak_hbm`), cross-checked against
``ServingEngine.cache_hbm_bytes`` by ``mesh_preflight``.

API mirrors the collective lint: :func:`analyze` returns findings,
:func:`check` raises :class:`GraphLintError` on any; both take
``mesh=`` / ``in_shardings=`` for the pre-flight path, and
:func:`preflight` returns findings + comm + HBM from one trace.
``FLAGS_graph_lint`` (off/warn/raise) arms the serving engines'
self-lint — every ``ServingEngine`` lints its own once-jitted step at
the first tick — and ``python -m paddle_tpu.static_analysis`` lints a
tiny-config engine step in every cache layout and prints the report
(``--mesh mp2dp2`` for the SPMD pre-flight).

A lint pass is ONE ``jax.make_jaxpr`` trace: abstract, no compile, no
device dispatch.
"""

from __future__ import annotations

import warnings
from typing import List, Optional, Sequence

from .. import flags as _flags
from . import core, mesh_rules as _mesh_rules, rules
from . import kernel_registry, kernel_rules
from .core import (Finding, GraphLintError, GraphLintWarning,
                   LintContext, MeshInfo, MeshLintContext, trace_for_lint,
                   trace_for_mesh_lint)
from .kernel_registry import (KernelSpec, KernelSpecError,
                              decode_attention_spec, flash_attention_spec,
                              int8_matmul_spec, kv_streamed_bytes,
                              gated_delta_specs, moe_experts_spec,
                              rms_norm_spec, registered_kernel_specs,
                              streamed_bytes, vmem_footprint)
from .kernel_rules import (KernelRule, KernelVmemRule, KernelBoundsRule,
                           KernelAlignRule, KernelScaleGranuleRule,
                           KernelStreamRule, analyze_kernels,
                           default_kernel_rules,
                           dispatch_agreement_findings, kernel_report)
from .mesh_rules import (CollectiveDeadlockRule, ReplicationBlowupRule,
                         ReshardingHazardRule, comm_report,
                         default_mesh_rules, estimate_peak_hbm)
from .rules import (ConstantCaptureRule, DonationRule, DtypePromotionRule,
                    HostSyncRule, RetraceHazardRule, Rule, default_rules)

__all__ = [
    "Finding", "GraphLintError", "GraphLintWarning", "LintContext",
    "MeshInfo", "MeshLintContext",
    "Rule", "DonationRule", "DtypePromotionRule", "ConstantCaptureRule",
    "HostSyncRule", "RetraceHazardRule", "default_rules",
    "ReplicationBlowupRule", "ReshardingHazardRule",
    "CollectiveDeadlockRule", "default_mesh_rules", "comm_report",
    "estimate_peak_hbm", "preflight",
    "analyze", "check", "enforce", "report", "trace_for_lint",
    "trace_for_mesh_lint",
    # kernel pre-flight (ISSUE 14)
    "KernelSpec", "KernelSpecError", "decode_attention_spec",
    "flash_attention_spec", "int8_matmul_spec", "rms_norm_spec",
    "moe_experts_spec", "gated_delta_specs",
    "registered_kernel_specs", "vmem_footprint", "streamed_bytes",
    "kv_streamed_bytes",
    "KernelRule", "KernelVmemRule", "KernelBoundsRule",
    "KernelAlignRule", "KernelScaleGranuleRule", "KernelStreamRule",
    "default_kernel_rules", "analyze_kernels", "kernel_report",
    "dispatch_agreement_findings",
]

# findings sort: errors first, then a total deterministic order so two
# runs of the same program produce byte-identical reports (the --json
# CLI contract CI diffs ride on)
_SEVERITY_ORDER = {"error": 0, "warning": 1}


def _sort_findings(findings: List[Finding]) -> List[Finding]:
    findings.sort(key=lambda f: (
        _SEVERITY_ORDER.get(f.severity, 2), f.rule, f.path,
        -1 if f.bytes is None else -int(f.bytes), f.message))
    return findings


def _unwrap(fn, donate_argnums, donate_argnames):
    """Resolve a ``track_retraces`` wrapper to its pre-jit python body
    and the donation marks of the real jit call site."""
    raw = getattr(fn, "python_fn", None)
    if raw is not None:                          # TrackedFunction
        jk = dict(getattr(fn, "jit_kwargs", None) or {})
        if donate_argnums is None:
            donate_argnums = jk.get("donate_argnums", ())
        if donate_argnames is None:
            donate_argnames = jk.get("donate_argnames", ())
        fn = raw
    return fn, (donate_argnums or ()), (donate_argnames or ())


def _trace(fn, args, kwargs, donate_argnums, donate_argnames,
           mesh, in_shardings):
    fn, dnums, dnames = _unwrap(fn, donate_argnums, donate_argnames)
    if mesh is None:
        return trace_for_lint(fn, *args, donate_argnums=dnums,
                              donate_argnames=dnames, **kwargs)
    return trace_for_mesh_lint(fn, *args, mesh=mesh,
                               in_shardings=in_shardings,
                               donate_argnums=dnums,
                               donate_argnames=dnames, **kwargs)


def analyze(fn, *args, donate_argnums=None, donate_argnames=None,
            rules: Optional[Sequence[Rule]] = None,
            mesh=None, in_shardings=None, kernels=None,
            **kwargs) -> List[Finding]:
    """Trace ``fn`` abstractly and run the graph-lint rules; returns
    findings (errors first, deterministically ordered) without raising.

    ``fn`` must be a PYTHON function (pre-jit).  A ``track_retraces``
    wrapper (observability/watchdog.py) is unwrapped automatically: its
    stored ``python_fn`` is traced — never the counted body, so a lint
    pass costs no watchdog budget — and its ``jit_kwargs`` supply
    ``donate_argnums``/``donate_argnames`` unless given explicitly, so
    ``analyze(engine._step_fn, *args)`` sees exactly what the real call
    site donates.

    ``mesh=`` selects the MESH pre-flight path (ISSUE 8): the trace is
    annotated with per-axis shardings (``in_shardings`` — per-arg specs
    — or the args' committed NamedShardings; undeclared = replicated),
    propagated through the jaxpr, and the mesh rule set
    (replication-blowup / resharding-hazard / collective-deadlock)
    runs alongside the base rules.  ``mesh`` may be a jax
    ``Mesh``/``AbstractMesh``, a ``{axis: size}`` dict, or a string
    like ``"mp2dp2"`` — no devices are needed.

    ``kernels=`` (ISSUE 14) adds the KERNEL pre-flight to the same
    pass: a sequence of :class:`KernelSpec`\\ s (usually the specs the
    traced program's dispatch would select —
    ``ServingEngine._kernel_specs``) run through the kernel rule set
    (VMEM footprint / index-map bounds / alignment / scale-granule /
    streamed-bytes); their findings merge into the same deterministic
    order."""
    ctx = _trace(fn, args, kwargs, donate_argnums, donate_argnames,
                 mesh, in_shardings)
    if rules is None:
        rules = default_rules() + (default_mesh_rules()
                                   if mesh is not None else ())
    findings: List[Finding] = []
    for rule in rules:
        findings.extend(rule.run(ctx))
    if kernels:
        findings.extend(kernel_rules.analyze_kernels(kernels))
    return _sort_findings(findings)


def report(findings: Sequence[Finding], context: str = "") -> str:
    """Human-readable multi-line report of a finding list."""
    head = (f"graph lint: {len(findings)} finding(s)"
            + (f" in {context}" if context else ""))
    return "\n".join([head] + [f"  {f}" for f in findings])


def check(fn, *args, **kwargs) -> List[Finding]:
    """Lint ``fn``; raise :class:`GraphLintError` on ANY finding, else
    return the (empty) finding list — the collective lint's
    ``check_collective_order`` contract."""
    findings = analyze(fn, *args, **kwargs)
    if findings:
        raise GraphLintError(report(findings))
    return findings


def preflight(fn, *args, mesh, in_shardings=None,
              donate_argnums=None, donate_argnames=None,
              rules: Optional[Sequence[Rule]] = None,
              kernels=None,
              **kwargs) -> dict:
    """Full mesh pre-flight of one traced program: findings (base +
    mesh rules), the per-axis collective-cost report, and the
    per-device HBM-liveness estimate — all from ONE abstract trace.
    This is the report ``ServingEngine.mesh_preflight`` wraps and the
    ``--mesh`` CLI prints; see BASELINE.md "Mesh pre-flight
    conventions" for the accounting definitions.

    ``kernels=``: optional :class:`KernelSpec` sequence to pre-flight
    alongside; their findings merge into ``"findings"`` and the
    per-spec reports ride under ``"kernels"``."""
    ctx = _trace(fn, args, kwargs, donate_argnums, donate_argnames,
                 mesh, in_shardings)
    if rules is None:
        rules = default_rules() + default_mesh_rules()
    findings: List[Finding] = []
    for rule in rules:
        findings.extend(rule.run(ctx))
    out = {"mesh": ctx.mesh.as_dict(),
           "fn": ctx.fn_name,
           "findings": findings,
           "comm": comm_report(ctx),
           "hbm": estimate_peak_hbm(ctx)}
    if kernels:
        findings.extend(kernel_rules.analyze_kernels(kernels))
        out["kernels"] = [kernel_rules.kernel_report(s) for s in kernels]
    _sort_findings(findings)
    return out


def enforce(findings: Sequence[Finding],
            context: str = "") -> Sequence[Finding]:
    """Apply ``FLAGS_graph_lint`` to a finding list: ``raise`` →
    :class:`GraphLintError`, ``warn`` → one :class:`GraphLintWarning`,
    ``off`` → pass through.  Serving engines call this on their
    first-tick self-lint."""
    if not findings:
        return findings
    action = str(_flags.flag("graph_lint"))
    if action == "off":
        return findings
    msg = report(findings, context)
    if action == "raise":
        raise GraphLintError(msg)
    warnings.warn(msg, GraphLintWarning, stacklevel=2)
    return findings
