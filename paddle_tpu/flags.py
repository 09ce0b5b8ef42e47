"""Runtime flag registry.

TPU-native equivalent of the reference's gflags-style flag system
(upstream layout: paddle/common/flags.cc — ``PHI_DEFINE_EXPORTED_*`` macros,
surfaced to Python as ``paddle.set_flags``/``paddle.get_flags`` and ``FLAGS_*``
environment variables).  Here the registry is pure Python: flags are declared
with :func:`DEFINE`, overridable via ``FLAGS_<name>`` environment variables at
import time, and a few of them bridge onto ``jax.config`` knobs.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

__all__ = ["DEFINE", "get_flags", "set_flags", "flag"]


@dataclass
class _Flag:
    name: str
    default: Any
    value: Any
    help: str
    # optional hook run on set (e.g. to forward onto jax.config)
    on_set: Optional[Callable[[Any], None]] = None


_REGISTRY: Dict[str, _Flag] = {}


def _coerce(default: Any, raw: str) -> Any:
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def DEFINE(name: str, default: Any, help: str = "",
           on_set: Optional[Callable[[Any], None]] = None) -> None:
    """Declare a flag. ``FLAGS_<name>`` in the environment overrides the default."""
    value = default
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        value = _coerce(default, env)
    f = _Flag(name, default, value, help, on_set)
    _REGISTRY[name] = f
    if on_set is not None and value != default:
        on_set(value)


def flag(name: str) -> Any:
    """Read one flag's current value."""
    return _REGISTRY[name].value


def get_flags(names=None) -> Dict[str, Any]:
    """Mirror of ``paddle.get_flags``: dict of flag name -> value."""
    if names is None:
        return {k: f.value for k, f in _REGISTRY.items()}
    if isinstance(names, str):
        names = [names]
    return {n: _REGISTRY[n].value for n in names}


def set_flags(flags: Dict[str, Any]) -> None:
    """Mirror of ``paddle.set_flags``."""
    for name, value in flags.items():
        if name not in _REGISTRY:
            raise KeyError(f"unknown flag {name!r}; DEFINE it first")
        f = _REGISTRY[name]
        f.value = value
        if f.on_set is not None:
            f.on_set(value)


# ---------------------------------------------------------------------------
# Core flags (parity with the reference's most-used FLAGS_*)
# ---------------------------------------------------------------------------

def _set_jax_x64(v: bool) -> None:
    import jax

    jax.config.update("jax_enable_x64", bool(v))


DEFINE("check_nan_inf", False, "check outputs for nan/inf after each op (debug)")
DEFINE("call_stack_level", 1, "error-message verbosity level")
DEFINE("use_fast_math", True, "allow fastmath-style approximations in kernels")
DEFINE("enable_x64", False, "enable 64-bit types (maps onto jax_enable_x64)",
       on_set=_set_jax_x64)
DEFINE("matmul_precision", "default",
       "default|float32|tensorfloat32|highest — XLA matmul precision")
DEFINE("log_level", 0, "VLOG-style verbosity for paddle_tpu's own logging")
DEFINE("allocator_strategy", "xla",
       "parity flag: the reference exposes auto_growth; on TPU, XLA owns memory")
DEFINE("collective_lint", False,
       "lint the collective schedule of every built train step "
       "(distributed/lint.py) at its first call — raises "
       "CollectiveOrderError on rank-divergence hazards instead of "
       "deadlocking on hardware")
DEFINE("pallas_interpret", False,
       "run Pallas kernels in interpreter mode (for CPU tests)")
DEFINE("moe_dispatch", "dense",
       "MoE dispatch algorithm: 'dense' (one-hot einsum, canonical GSPMD "
       "alltoall) or 'index' (scatter/gather by slot index, O(T*k) routing "
       "metadata — the reference's global_scatter/global_gather shape)")
DEFINE("flash_attention_force", False,
       "error instead of silently falling back to the XLA reference path "
       "when the Pallas flash-attention kernel is ineligible")
# flash block defaults from a v5e sweep on the bench workload (llama3-arch
# 4L, bs2 x seq2048, head_dim 128, GQA 32/8 — full train-step MFU):
#  (bq,bkv): (256,512)=0.579  (512,512)=0.598  (512,1024)=0.611
#            (1024,1024)=0.624  (1024,2048)=VMEM OOM
# larger q tiles amortise the kv streaming; 1024x1024 is the VMEM ceiling
# reproducible: `python bench.py --op flash` re-runs the sweep and records
# it in BENCH_OPS.json (round-3 verdict #7)
DEFINE("flash_attention_block_q", 1024,
       "Pallas flash-attention q block size")
DEFINE("rms_norm_pallas_min_dim", 1 << 31,
       "route standalone rms_norm rows at least this long to the Pallas "
       "single-visit kernel.  Default disables the route: the checked-in "
       "harness (bench.py --op rms_norm, BENCH_OPS.json) measured XLA as "
       "fast or faster at EVERY shape once dispatch latency was "
       "excluded — the earlier 1.73x claim was a measurement artifact.  "
       "The kernel stays as an opt-in (set a finite threshold) reference "
       "and Mosaic testbed.")
DEFINE("flash_attention_block_kv", 1024,
       "Pallas flash-attention kv block size")
# flash-decode dispatch threshold from BENCH_DECODE.json decode rows (940M
# llama3-arch, v5e): the XLA math path sits AT the bf16 weight-stream bound
# through max_length 2048 (0.97-1.07x of bound, b=1 and b=8) — a kernel buys
# nothing there — but drops to 0.652x at b=8 max_length 8192 because it
# streams the dead cache tail; those shapes route to the split-KV Pallas
# flash-decode kernel (ops/pallas/decode_attention.py), whose live-prefix
# reads restore O(depth) per-step cost.
# reproducible: `python bench.py --op decode_attention` -> BENCH_OPS.json
DEFINE("decode_attention_min_len", 4096,
       "route cached_decode_attention to the Pallas flash-decode kernel "
       "when the cache length is at least this (Pallas backends only); "
       "below it the XLA math path already runs at the weight-stream bound")
DEFINE("decode_attention_block_kv", 512,
       "flash-decode KV chunk size (cap; the kernel picks the largest "
       "128-aligned divisor of max_length at or below it)")
# paged KV cache (serving/kv_cache.py): the serving engine's block pool
DEFINE("kv_cache_block_len", 128,
       "paged KV cache block length in tokens.  128 keeps one block == "
       "one 128-aligned flash-decode KV chunk so the Pallas kernel can "
       "dereference block tables in its index maps; non-multiples of 128 "
       "still work but pin paged attention to the XLA gather path")
DEFINE("kv_cache_num_blocks", 0,
       "paged KV pool size in blocks (plus the reserved null block).  0 "
       "derives num_slots * max_length / block_len — the contiguous "
       "cache's footprint, now shareable across slots; set lower to "
       "serve more slots than worst-case memory would allow")
# quantized KV cache (serving/kv_cache.py + models/llama.py + the
# flash-decode kernel): int8 blocks with per-block-per-kv-head scales
# halve both resident-session HBM and the per-step cache stream — the
# b=8 dead-tail regression growth_check_b8 flags
DEFINE("serving_kv_cache_dtype", "bf16",
       "KV-cache element dtype for the serving engine: 'bf16' (the "
       "model dtype), 'int8' (per-block-per-kv-head symmetric scales, "
       "quantized at scatter time, dequantized inside the flash-decode "
       "chunk loop), or 'mixed' (paged only: blocks are written bf16 "
       "and demoted to simulated-int8 when they become cold full "
       "prefix blocks at registration).  Engine constructor arg "
       "overrides")
DEFINE("serving_int8_weights", False,
       "wrap the serving engine's model with weight-only int8 "
       "quantization (models/quantized.py) so decode matmuls take the "
       "int8 Pallas path — combine with serving_kv_cache_dtype='int8' "
       "for the full int8 serving configuration")
# chunked prefill (serving/engine.py mixed steps): Sarathi-style
# iteration-level token budgeting — prompts stream into the decode step
# as fixed-size chunks instead of stalling it with whole-prompt waves
DEFINE("serving_chunked_prefill", False,
       "ServingEngine default admission mode: False = wave prefill "
       "(separate bucketed prefill programs), True = chunked prefill "
       "(prompts split into prefill_chunk-token chunks "
       "folded into the once-jitted mixed decode step, so in-flight "
       "decodes never stall behind a long prompt; engine constructor "
       "arg overrides)")
# mesh-sharded serving (serving/engine.py mesh=... + serving/router.py):
# the tensor-parallel engine step and the data-parallel replica router —
# ROADMAP item 1's multi-chip execution path
DEFINE("serving_mesh", "",
       "ServingEngine default mesh: a compact axis string like 'mp2dp2' "
       "resolved over the first matching prefix of jax.devices() at "
       "engine construction (empty = single-chip; the engine "
       "constructor's mesh argument overrides).  Params/cache are "
       "placed per models.generation.decode_mesh_specs and the "
       "once-jitted step runs under declared in_shardings with the "
       "cache operand still donated")
# graph lint (paddle_tpu/static_analysis): jaxpr static analysis of the
# serving hot path — donation, dtype widening, constant capture,
# host-sync, retrace hazards — one abstract trace, before any device run
DEFINE("graph_lint", "off",
       "serving-engine self-lint at the first scheduler tick: 'raise' "
       "(GraphLintError on any finding — the dedicated lint tests arm "
       "this), 'warn' (one GraphLintWarning; the tier-1 conftest default "
       "so every serving test lints implicitly), 'off' (no self-lint; "
       "analyze()/check() and the CLI still work explicitly)")
DEFINE("graph_lint_donation_min_bytes", 1 << 16,
       "donation rule: only outputs at least this big are matched "
       "against un-donated inputs (64 KiB default keeps (num_slots,) "
       "token vectors out while any real KV cache is in)")
DEFINE("graph_lint_widen_bytes", 1 << 16,
       "dtype-promotion rule: minimum operand size for a flagged "
       "f32/f64 widening (small scalars/stats widen for free)")
DEFINE("graph_lint_const_bytes", 1 << 20,
       "constant-capture rule: arrays baked into a jaxpr as consts at "
       "least this big are findings (weights closed over instead of "
       "passed as args cost HBM alongside the live copy and retrace on "
       "update); tiny eps/table consts stay below it")
# mesh pre-flight (paddle_tpu/static_analysis/mesh_rules.py): sharding
# propagation + collective cost + per-device HBM liveness over one
# abstract trace, before any mesh compile (BASELINE.md "Mesh pre-flight
# conventions")
DEFINE("graph_lint_replication_min_bytes", 1 << 20,
       "replication-blowup rule: a step-function operand at least this "
       "big, fully replicated along a checked mesh axis it could shard "
       "(some dimension divisible by the axis size), is an error — a "
       "KV cache or weight replicated over mp multiplies its HBM by "
       "the axis size.  dp is never checked (dp replication of params "
       "IS the data-parallel contract); rope tables are allowlisted")
DEFINE("graph_lint_reshard_min_bytes", 1 << 16,
       "resharding-hazard rule: minimum tensor size for flagging a "
       "with_sharding_constraint that conflicts with the operand's "
       "propagated sharding (an implicit cross-device reshard on the "
       "hot path); smaller tensors reshard for free")
DEFINE("graph_lint_hbm_tol", 0.02,
       "mesh pre-flight HBM cross-check tolerance: the liveness "
       "estimator's predicted per-device KV-cache bytes, scaled back "
       "by the cache's shard count, must match the engine's "
       "cache_hbm_bytes within this relative error or the pre-flight "
       "report carries an hbm-liveness error finding")
# kernel pre-flight (paddle_tpu/static_analysis/kernel_rules.py): static
# VMEM/bounds/alignment analysis of every registered Pallas KernelSpec —
# no compile, no device (BASELINE.md "Kernel pre-flight conventions")
DEFINE("kernel_lint_vmem_bytes", 16 * 1024 * 1024,
       "kernel-vmem rule budget: a kernel's per-grid-step VMEM "
       "footprint (block-shaped operand tiles with streamed operands "
       "double-buffered x2, plus scratch accumulators) must fit this "
       "per-core budget or the pre-flight carries an error finding; "
       "16 MiB is the v4/v5-generation VMEM per core")
# observability (paddle_tpu/observability): metrics registry + span tracer
DEFINE("retrace_watchdog", "warn",
       "action when a track_retraces call-site compiles past its trace "
       "budget: 'raise' (RetraceError inside the offending trace — the "
       "tier-1 conftest arms this for every test), 'warn' (one "
       "RetraceWarning per violation), 'off' (count only).  The count "
       "always lands in the jit.traces registry counter")
DEFINE("observability_spans", True,
       "record host spans (the serving tick's phases, prefill waves, "
       "RecordEvent scopes) into the default SpanTracer for "
       "Chrome-trace/Perfetto export and, as jax.profiler "
       "TraceAnnotations, into a running profiler trace; off leaves "
       "span() calls as no-ops in both")
DEFINE("trace_buffer_events", 100000,
       "span-tracer ring-buffer capacity: a long-running server keeps "
       "the most recent window of host spans and counts the rest as "
       "dropped (SpanTracer.dropped)")
DEFINE("request_log_max_requests", 4096,
       "RequestLog capacity in whole requests: the per-request "
       "lifecycle store keeps the most recent window of timelines, "
       "evicting oldest requests first and counting them "
       "(RequestLog.dropped), mirroring the span tracer's ring policy")
DEFINE("serving_slo_ttft_ms", 0.0,
       "per-request TTFT deadline in ms recorded at submit() and "
       "joined by RequestLog.slo_report(): a request whose first token "
       "lands later than this after SUBMIT (not admit) misses SLO, "
       "attributed to queue_wait or prefill by the larger segment.  "
       "0 disables the TTFT deadline")
DEFINE("serving_slo_tpot_ms", 0.0,
       "per-request TPOT deadline in ms recorded at submit(): a "
       "retired request whose mean time-per-output-token exceeds this "
       "misses SLO, attributed to decode.  0 disables the TPOT "
       "deadline")
# cost model + perf sentinel (paddle_tpu/observability/costmodel.py,
# regression.py): per-tick analytical roofline, measured-vs-predicted
# attribution, and EWMA anomaly/drift detection (BASELINE.md "Cost-model
# accounting conventions")
DEFINE("perf_model", "on",
       "per-tick roofline cost model in ServingEngine: 'on' stamps "
       "every scheduler tick with predicted_tick_ms (memoized host "
       "math), records measured/predicted into perf.tick_model_ratio "
       "histograms labelled by bound, and arms the drift/anomaly "
       "detectors behind perf_report(); 'off' skips all of it")
DEFINE("perf_model_profile", "auto",
       "hardware profile for the roofline: 'auto' picks 'v5e' on a TPU "
       "backend and 'cpu_smoke' elsewhere; any profile name registered "
       "in observability.costmodel.PROFILES overrides")
DEFINE("perf_model_tol", 3.0,
       "drift band half-width for the measured/predicted ratio EWMA: "
       "after calibration the per-bound EWMA must stay inside "
       "[base/(1+tol), base*(1+tol)] or perf_report() carries a "
       "perf-drift finding (same Finding shape as static_analysis).  "
       "The default 3.0 (a 4x band around the calibrated baseline) "
       "absorbs CPU-smoke scheduling noise — clean tier-1 replays sit "
       "within ~1.5x of calibration but CI machines spike — while a "
       "sustained slowdown past 4x still trips; TPU runs can tighten it")
# cost-model-driven control plane (serving/admission.py, router.py,
# serving/autoscaler.py, serving/fleet_sim.py): predictive SLO
# admission, priced hold queue, replica autoscaling
DEFINE("serving_admission", "queue_depth",
       "admission/placement policy for ServingEngine and ReplicaRouter: "
       "'queue_depth' (the historical reactive policy — admit whenever "
       "a slot and KV blocks are free, place on the least-loaded "
       "replica) or 'predictive' (consult CostModel.predicted_tick_ms "
       "at the hypothetical post-admission state and defer into a "
       "priced hold queue when the pooled TPOT/TTFT SLO would blow).  "
       "'predictive' silently degrades to 'queue_depth' when "
       "FLAGS_perf_model is off or the cost model carries drift "
       "findings (an uncalibrated model must not gate admission)")
DEFINE("serving_admission_slack", 1.25,
       "predictive-admission headroom multiplier: a request is deferred "
       "when predicted TPOT exceeds tpot_slo_ms * slack (or predicted "
       "queue-drain time exceeds ttft_slo_ms * slack).  >1 keeps "
       "admission conservative against model optimism; 1.0 admits "
       "right up to the SLO line")
DEFINE("serving_admission_calib", 1.0,
       "wall-ms per predicted-ms calibration multiplier applied to "
       "cost-model predictions before they are compared against "
       "wall-clock SLO deadlines.  The TPU profiles are seeded from "
       "measured BENCH rows (ratio ~1), so 1.0 is right there; the "
       "cpu_smoke profile's absolute milliseconds are NOT wall-"
       "calibrated (BASELINE.md), so CPU benches measure a warm pass "
       "and set this to measured_tick_ms/predicted_tick_ms — a fixed, "
       "deterministic input, unlike the live EWMA ratio which would "
       "make admission decisions replay-dependent.  The fleet "
       "simulator keeps 1.0: its clock IS the predicted domain")
DEFINE("serving_admission_max_defer_ticks", 64,
       "starvation bound for the predictive hold queue: a request "
       "deferred for this many consecutive scheduler ticks is force-"
       "admitted/placed regardless of the SLO prediction (aging beats "
       "pricing).  0 disables forcing")
DEFINE("serving_autoscale_min_ticks", 8,
       "ReplicaAutoscaler hysteresis: predicted-SLO pressure (or "
       "slack) must persist for this many consecutive observe() ticks "
       "before a scale-up (or drain) decision fires")
DEFINE("serving_autoscale_cooldown", 16,
       "ReplicaAutoscaler cooldown: minimum observe() ticks between "
       "two scaling actions (in either direction) — damps oscillation "
       "around the goodput target")
DEFINE("metrics_port", 0,
       "HTTP exposition port for observability.http_exposition: serve "
       "/metrics (Prometheus text), /healthz (liveness + anomaly "
       "status) and /requests (RequestLog JSON tail) on this port.  "
       "0 (default) disables the server; -1 binds an ephemeral port "
       "(tests)")
DEFINE("metrics_max_children", 64,
       "label-cardinality cap per metric family: past this many "
       "distinct label sets a family warns once and coalesces further "
       "new label sets into a single {overflow='true'} child, so "
       "per-uid or per-shape labels can never grow the registry "
       "unboundedly")
DEFINE("multihost_call_timeout_s", 5.0,
       "per-RPC-call timeout for the multi-host serving plane's socket "
       "transport (serving/multihost): a call past this deadline counts "
       "as transport loss and feeds the heartbeat/failover path")
DEFINE("multihost_call_retries", 2,
       "reconnect attempts per RPC call (deterministic exponential "
       "backoff); only idempotent methods — ping/status/result/... — "
       "are ever replayed blind after a broken connection")
DEFINE("multihost_retry_backoff_s", 0.05,
       "base of the deterministic exponential backoff between RPC "
       "reconnect attempts (base * 2**attempt seconds)")
DEFINE("multihost_heartbeat_every", 4,
       "plane scheduler ticks between heartbeat pings to every worker; "
       "counted in ticks (not wall time) so loopback replays stay "
       "byte-deterministic.  A failed ping marks the worker lost and "
       "re-admits its sessions on the survivors (recompute-from-prefix)")
DEFINE("multihost_stream_poll_s", 0.002,
       "frontend step-loop idle sleep between scheduler ticks while "
       "streaming /v1/generate responses (real-time mode only; tests "
       "drive the plane tick-by-tick instead)")
