"""Kernel pre-flight rules — static VMEM/bounds/alignment analysis of
:class:`~paddle_tpu.static_analysis.kernel_registry.KernelSpec`s.

Each rule takes one spec and returns structured
:class:`~paddle_tpu.static_analysis.core.Finding`s (the same dataclass
the graph lint and mesh pre-flight emit, so the CLI/engine/bench wiring
is shared).  Nothing here compiles or touches a device: the rules walk
the declared grid, block shapes, index maps (over integer intervals),
and scalar-prefetch value ranges.

Rules (BASELINE.md "Kernel pre-flight conventions"):

  * ``kernel-vmem`` — per-grid-step footprint (streamed operand tiles
    x2 for DMA double-buffering + scratch, a body's own copy buffers
    among it) vs
    ``FLAGS_kernel_lint_vmem_bytes`` (default 16 MiB/core);
  * ``kernel-bounds`` — interval evaluation of every index map over the
    full grid domain (for an operand the kernel body copies by hand:
    of every block its copies take): block indices within the array,
    scalar-prefetch accesses within the operand shape, and the
    ClampCheck corners — the largest and smallest table column
    dereferenced at a pinned position are the walk's last and first
    (past it = the dead tail's null (block 0) entries stream; short of
    it = live KV silently truncated);
  * ``kernel-align`` — array%block divisibility, last-dim %128 lanes,
    second-minor sublane multiples per dtype, declared 128-lane dims
    (paged block_len, flash block_kv), and the head-slice layout
    (hkv*d last dim with d not lane-aligned straddles lane tiles);
  * ``kernel-scale-granule`` — contiguous-int8 scale granule must tile
    the cache length, be 128-aligned, and agree with the KV chunk;
  * ``kernel-stream`` — the quantized KV streamed-bytes model vs the
    committed int8_serving claim (<= 0.55x the bf16-equivalent bytes).

``dispatch_agreement_findings`` is satellite 1's lint: sweep a shape
lattice and fail if ``ops.attention.decode_shape_gate`` would route a
shape to the Pallas kernel that ``decode_kernel_rejects`` refuses.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from .. import flags as _flags
from ..ops.pallas import limits as _limits
from . import core
from . import kernel_registry as _kr

__all__ = ["KernelRule", "KernelVmemRule", "KernelBoundsRule",
           "KernelAlignRule", "KernelScaleGranuleRule",
           "KernelStreamRule", "default_kernel_rules",
           "analyze_kernels", "kernel_report",
           "dispatch_agreement_findings", "STREAM_RATIO_BOUND"]

# committed int8_serving claim: quantized KV moves <= 0.55x the bytes of
# the bf16 cache for the same fetch pattern (int8 payload + f32 scale
# rows; the +0.05 covers the per-block scale overhead at block_len 128)
STREAM_RATIO_BOUND = 0.55

_SEVERITY_ORDER = {"error": 0, "warning": 1}


def _sort(findings: List[core.Finding]) -> List[core.Finding]:
    # identical key to static_analysis._sort_findings so merged
    # graph+kernel output stays deterministic under one ordering
    return sorted(findings, key=lambda f: (
        _SEVERITY_ORDER.get(f.severity, 2), f.rule, f.path,
        -1 if f.bytes is None else -int(f.bytes), f.message))


class KernelRule:
    """Base: ``name``/``severity`` class attrs + ``run(spec)``."""

    name = "kernel-rule"
    severity = "error"

    def run(self, spec: _kr.KernelSpec) -> List[core.Finding]:
        raise NotImplementedError


@dataclasses.dataclass
class KernelVmemRule(KernelRule):
    """Per-grid-step VMEM footprint must fit the per-core budget."""

    budget_bytes: Optional[int] = None
    name = "kernel-vmem"
    severity = "error"

    def run(self, spec):
        budget = self.budget_bytes
        if budget is None:
            budget = int(_flags.flag("kernel_lint_vmem_bytes"))
        total = _kr.vmem_footprint(spec)
        if total <= budget:
            return []
        return [core.Finding(
            rule=self.name, severity=self.severity, path=spec.path,
            message=(f"per-grid-step VMEM footprint {total} bytes "
                     f"exceeds the {budget}-byte per-core budget "
                     f"(FLAGS_kernel_lint_vmem_bytes); shrink block_kv "
                     f"or the q tile"),
            bytes=int(total))]


@dataclasses.dataclass
class KernelBoundsRule(KernelRule):
    """Interval-evaluate every index map over the full grid domain and
    the declared scalar ranges; run the dead-tail ClampCheck corners."""

    name = "kernel-bounds"
    severity = "error"

    def _eval(self, spec, op, pins, out: List[core.Finding],
              seen: set) -> None:
        env = _kr.ScalarEnv(spec.scalars, pins=pins)
        grid_ivs = []
        for d, g in enumerate(spec.grid):
            pin = pins.get(("grid", d)) if pins else None
            grid_ivs.append(_kr.iv(pin) if pin is not None
                            else _kr.Iv(0, max(0, int(g) - 1)))
        idx = op.index_map(tuple(grid_ivs), env)
        # every returned block index must land inside the array
        for d, (span, blk, arr) in enumerate(
                zip(idx, op.block_shape, op.array_shape)):
            span = _kr.iv(span)
            hi = max(0, arr // blk - 1)
            if span.lo < 0 or span.hi > hi:
                msg = (f"operand '{op.name}' dim {d}: index map spans "
                       f"[{span.lo}, {span.hi}] outside block range "
                       f"[0, {hi}] of array shape {op.array_shape}")
                if msg not in seen:
                    seen.add(msg)
                    out.append(core.Finding(
                        rule=self.name, severity=self.severity,
                        path=spec.path, message=msg))
        # every recorded scalar-prefetch access must be in-shape
        sc_shapes = {s.name: s.shape for s in spec.scalars}
        for sc_name, access in env.accesses:
            shape = sc_shapes[sc_name]
            for d, span in enumerate(access):
                hi = max(0, shape[d] - 1)
                if span.lo < 0 or span.hi > hi:
                    msg = (f"operand '{op.name}': scalar-prefetch "
                           f"'{sc_name}' dim {d} access "
                           f"[{span.lo}, {span.hi}] outside shape "
                           f"{shape}")
                    if msg not in seen:
                        seen.add(msg)
                        out.append(core.Finding(
                            rule=self.name, severity=self.severity,
                            path=spec.path, message=msg))

    def _clamp_corners(self, spec, op, out, seen) -> None:
        cl = op.clamp
        sc = {s.name: s for s in spec.scalars}[cl.pin_scalar]

        def flag(msg):
            if msg not in seen:
                seen.add(msg)
                out.append(core.Finding(
                    rule=self.name, severity=self.severity,
                    path=spec.path, message=msg))

        for p in {sc.lo, sc.hi}:
            for q in {0, max(0, spec.grid[cl.pin_axis] - 1)}:
                env = _kr.ScalarEnv(spec.scalars, pins={cl.pin_scalar: p})
                grid_ivs = []
                for d, g in enumerate(spec.grid):
                    grid_ivs.append(_kr.iv(q) if d == cl.pin_axis
                                    else _kr.Iv(0, max(0, int(g) - 1)))
                op.index_map(tuple(grid_ivs), env)
                cols = [a for name, a in env.accesses if name == cl.table]
                if not cols:
                    flag(f"operand '{op.name}': declared ClampCheck "
                         f"on table '{cl.table}' but the index map "
                         f"never dereferences it")
                    continue
                if cl.expected_first is not None:
                    want = int(cl.expected_first(p, q))
                    got = min(a[-1].lo for a in cols)
                    if got != want:
                        flag(f"operand '{op.name}': '{cl.table}' column "
                             f"starts at {got}, not at the walk's first "
                             f"block {want}, at pos={p}; "
                             + ("blocks no query sees are read (null or "
                                "another request's)" if got < want else
                                "live KV is silently truncated"))
                want = int(cl.expected(p, q))
                got = max(a[-1].hi for a in cols)
                if got > want:
                    flag(f"operand '{op.name}': unclamped table "
                         f"dereference — '{cl.table}' column reaches "
                         f"{got} past last live block {want} at "
                         f"pos={p}; the dead tail streams, and its "
                         f"null-filled (block 0) entries would alias "
                         f"pad data into live rows")
                elif got < want:
                    flag(f"operand '{op.name}': over-clamped table "
                         f"dereference — '{cl.table}' column tops out "
                         f"at {got} below last live block {want} at "
                         f"pos={p}; live KV is silently truncated")

    def run(self, spec):
        out: List[core.Finding] = []
        seen: set = set()
        for op in spec.operands:
            self._eval(spec, op, {}, out, seen)
            if op.clamp is not None:
                self._clamp_corners(spec, op, out, seen)
        return out


@dataclasses.dataclass
class KernelAlignRule(KernelRule):
    """Tiling lint: array%block divisibility, %128-lane last dims,
    per-dtype sublane multiples, and declared lane-critical dims."""

    name = "kernel-align"
    severity = "error"

    def run(self, spec):
        out: List[core.Finding] = []
        for op in spec.operands:
            for d, (blk, arr) in enumerate(
                    zip(op.block_shape, op.array_shape)):
                if blk <= 0 or arr % blk:
                    out.append(core.Finding(
                        rule=self.name, severity=self.severity,
                        path=spec.path,
                        message=(f"operand '{op.name}' dim {d}: block "
                                 f"{blk} does not tile array dim "
                                 f"{arr}")))
            last_b, last_a = op.block_shape[-1], op.array_shape[-1]
            if last_b % _limits.LANES and last_b != last_a:
                out.append(core.Finding(
                    rule=self.name, severity=self.severity,
                    path=spec.path,
                    message=(f"operand '{op.name}': last block dim "
                             f"{last_b} is not a multiple of "
                             f"{_limits.LANES} lanes")))
            if len(op.block_shape) >= 2 and not op.sublane_padded:
                sub_b = op.block_shape[-2]
                sub_a = op.array_shape[-2]
                sl = _limits.sublanes(op.dtype)
                # Mosaic refuses a second-minor block dim that is
                # neither the array's nor sublane-aligned — one row of
                # a taller array included
                if sub_b % sl and sub_b != sub_a:
                    out.append(core.Finding(
                        rule=self.name, severity=self.severity,
                        path=spec.path,
                        message=(f"operand '{op.name}': second-minor "
                                 f"block dim {sub_b} is not a multiple "
                                 f"of the {op.dtype} sublane tile "
                                 f"{sl}")))
        for label, v in spec.dims.get("lanes_128", ()):
            if int(v) % _limits.LANES:
                out.append(core.Finding(
                    rule=self.name, severity=self.severity,
                    path=spec.path,
                    message=(f"{label} {v} is not 128-aligned "
                             f"(lane-width DMA granularity)")))
        for label, v in spec.dims.get("sublanes_8", ()):
            if int(v) % 8:
                out.append(core.Finding(
                    rule=self.name, severity=self.severity,
                    path=spec.path,
                    message=f"{label} {v} is not a multiple of 8 rows"))
        lane_slice = spec.dims.get("lane_slice")
        if lane_slice is not None:
            d, hkv = lane_slice
            if hkv > 1 and int(d) % _limits.LANES:
                out.append(core.Finding(
                    rule=self.name, severity=self.severity,
                    path=spec.path,
                    message=(f"head_dim {d} with {hkv} kv heads folded "
                             f"into the last dim: per-head slices "
                             f"straddle {_limits.LANES}-lane tiles "
                             f"(misaligned head_dim)")))
        return out


@dataclasses.dataclass
class KernelScaleGranuleRule(KernelRule):
    """Contiguous-int8 scale layout must agree with the KV chunking:
    granule x granules == cache length, granule 128-aligned, and equal
    to the kernel's KV chunk (one scale row per streamed chunk)."""

    name = "kernel-scale-granule"
    severity = "error"

    def run(self, spec):
        gran = spec.dims.get("scale_granule")
        if gran is None:
            return []
        out: List[core.Finding] = []
        ng = int(spec.dims.get("scale_granules", 0))
        kv_len = int(spec.dims.get("kv_len", 0))
        bk = int(spec.dims.get("bk", 0))
        gran = int(gran)
        if gran * ng != kv_len:
            out.append(core.Finding(
                rule=self.name, severity=self.severity, path=spec.path,
                message=(f"int8 scale granule {gran} x {ng} granules "
                         f"!= cache length {kv_len}")))
        if gran % _limits.LANES:
            out.append(core.Finding(
                rule=self.name, severity=self.severity, path=spec.path,
                message=(f"int8 scale granule {gran} is not "
                         f"128-aligned")))
        if gran != bk:
            out.append(core.Finding(
                rule=self.name, severity=self.severity, path=spec.path,
                message=(f"int8 scale granule {gran} disagrees with "
                         f"the KV chunk {bk}: dequant would mix "
                         f"granules inside one streamed block")))
        return _sort(out)


@dataclasses.dataclass
class KernelStreamRule(KernelRule):
    """Quantized decode kernels must honour the committed int8_serving
    streamed-bytes claim: KV-side bytes <= STREAM_RATIO_BOUND x the
    bf16-equivalent bytes for the same fetch pattern."""

    max_ratio: Optional[float] = None
    name = "kernel-stream"
    severity = "error"

    def run(self, spec):
        if not spec.dims.get("quantized"):
            return []
        bound = self.max_ratio if self.max_ratio is not None \
            else STREAM_RATIO_BOUND
        kvb = int(spec.dims.get("kv_streamed_bytes", 0))
        bf16 = int(spec.dims.get("kv_streamed_bytes_bf16_equiv", 0))
        if bf16 <= 0 or kvb <= bound * bf16:
            return []
        return [core.Finding(
            rule=self.name, severity=self.severity, path=spec.path,
            message=(f"quantized KV streams {kvb} bytes = "
                     f"{kvb / bf16:.3f}x the bf16-equivalent {bf16} "
                     f"bytes, above the committed int8_serving bound "
                     f"{bound}x (scale layout too fat per token?)"),
            bytes=int(kvb))]


def default_kernel_rules() -> Tuple[KernelRule, ...]:
    return (KernelVmemRule(), KernelBoundsRule(), KernelAlignRule(),
            KernelScaleGranuleRule(), KernelStreamRule())


def analyze_kernels(specs: Sequence[_kr.KernelSpec],
                    rules: Optional[Sequence[KernelRule]] = None
                    ) -> List[core.Finding]:
    """Run every kernel rule over every spec; deterministic order."""
    if rules is None:
        rules = default_kernel_rules()
    out: List[core.Finding] = []
    for spec in specs:
        for rule in rules:
            out.extend(rule.run(spec))
    return _sort(out)


def kernel_report(spec: _kr.KernelSpec,
                  rules: Optional[Sequence[KernelRule]] = None
                  ) -> Dict[str, object]:
    """Per-kernel JSON-able report — the bench/CLI row payload."""
    findings = analyze_kernels([spec], rules=rules)
    return {
        "op": spec.op,
        "variant": spec.variant,
        "vmem_bytes": int(_kr.vmem_footprint(spec)),
        "streamed_bytes": int(_kr.streamed_bytes(spec)),
        "findings": [f.as_dict() for f in findings],
    }


# ---------------------------------------------------------------------------
# satellite 1: dispatch <-> kernel agreement
# ---------------------------------------------------------------------------

def _default_shape_lattice() -> List[Dict[str, object]]:
    # a small lattice over the dims the gates actually read: q_len
    # (decode / spec-verify / chunk / whole-prefill edge), GQA group,
    # head_dim, cache length alignment, paged block_len
    shapes: List[Dict[str, object]] = []
    for s in (1, 5, 256, _limits.MAX_Q_LEN):
        for hq, hkv in ((32, 8), (64, 1), (8, 8)):
            for d in (64, 128, _limits.MAX_HEAD_DIM):
                for kv_len in (4096, 8192):
                    shapes.append(dict(b=4, s=s, hq=hq, hkv=hkv, d=d,
                                       kv_len=kv_len))
                    shapes.append(dict(b=4, s=s, hq=hq, hkv=hkv, d=d,
                                       kv_len=kv_len,
                                       paged_block_len=128))
    return shapes


def dispatch_agreement_findings(shapes=None) -> List[core.Finding]:
    """Satellite-1 lint: for every lattice shape the dispatch gate
    routes to the Pallas kernel, the kernel spec must accept it (and
    quantized twins of the contiguous shapes with the standard
    128-token scale granule).  A disagreement is a routing bug — a
    runtime NotImplementedError waiting on the serving hot path."""
    from ..ops.attention import decode_shape_gate
    if shapes is None:
        shapes = _default_shape_lattice()
    out: List[core.Finding] = []
    for sh in shapes:
        b = int(sh.get("b", 1))
        s, hq, hkv, d = (int(sh["s"]), int(sh["hq"]), int(sh["hkv"]),
                         int(sh["d"]))
        kv_len = int(sh["kv_len"])
        pbl = sh.get("paged_block_len")
        path, why = decode_shape_gate(s, hq, hkv, d, kv_len,
                                      paged_block_len=pbl)
        quant_arms = [(False, None)]
        if kv_len % _limits.LANES == 0:
            quant_arms.append((True, kv_len // _limits.LANES))
        for quantized, ng in quant_arms:
            if pbl is not None and quantized:
                ng = None
            reject = _kr.decode_kernel_rejects(
                b, s, hq, hkv, d, kv_len, paged_block_len=pbl,
                quantized=quantized, n_granules=ng)
            if path == "pallas_decode" and reject is not None:
                out.append(core.Finding(
                    rule="kernel-dispatch", severity="error",
                    path=f"decode_attention[{sh}]",
                    message=(f"dispatch routes this shape to the Pallas "
                             f"kernel but the kernel spec rejects it: "
                             f"{reject}")))
            elif path != "pallas_decode" and reject is None and \
                    why.startswith(("GQA", "q_len", "head_dim",
                                    "q heads", "paged block_len",
                                    "max_length")):
                # shape-gate refusals only; environment refusals
                # (mesh trace, min_len, masks) are not disagreements
                out.append(core.Finding(
                    rule="kernel-dispatch", severity="error",
                    path=f"decode_attention[{sh}]",
                    message=(f"dispatch refuses a shape the kernel "
                             f"accepts ({why}): perf left on the "
                             f"floor")))
    return _sort(out)
