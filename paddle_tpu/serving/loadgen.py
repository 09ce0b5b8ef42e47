"""Trace-driven load harness: seeded arrival processes + length mixes
replayed deterministically against a ServingEngine or ReplicaRouter.

Raw tok/s on a drain()-until-empty batch says nothing about production
serving, which is judged on goodput under SLO — requests finishing
within TTFT/TPOT deadlines under realistic traffic.  This module
supplies the traffic half of that judgment:

  * **arrival processes** — seeded Poisson (exponential inter-arrival
    gaps) and bursty on/off (Markov-modulated: dense arrivals inside
    ``burst_on``-tick windows separated by silent ``burst_off`` gaps),
    both in scheduler-tick time so replays are device-speed-independent;
  * **length mixes** — heavy-tail prompt/output lengths, either
    lognormal (median × e^{σZ}, clamped) or Zipf-bucketed (a fixed
    bucket ladder with rank-``a`` power-law mass — the multi-workload
    mixture shape real traces show);
  * **tenant populations** — Zipf-popular tenants, each with a shared
    prompt prefix (its "system prompt"), so prefix caching and
    prefix-affinity routing see the traffic they were built for.

``generate_load(spec, seed)`` is a pure function of its arguments —
the SAME (spec, seed) yields the SAME trace, byte for byte —  and
``replay`` drives the trace through ``submit()``/``step()`` ticks,
segmenting the process-wide RequestLog with ``mark()`` and returning
outputs, the goodput report, and the run's structural
``timeline_signature``.  Two identical-seed replays against
identically-configured engines must produce identical signatures AND
identical sampled outputs (BASELINE.md "SLO accounting conventions");
``python -m paddle_tpu.serving.loadgen --smoke`` enforces exactly that,
plus the step retrace budget, against both engine modes on CPU — the
CI hook.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import observability as _obs

__all__ = ["LoadRequest", "LoadSpec", "generate_load", "replay"]


@dataclasses.dataclass
class LoadRequest:
    """One request of a generated trace."""

    index: int                  # position in the trace (stable id)
    arrival: float              # arrival time, in scheduler ticks
    tenant: int                 # which shared-prefix population
    prompt: np.ndarray          # (plen,) int32, tenant prefix included
    max_new_tokens: int


@dataclasses.dataclass(frozen=True)
class LoadSpec:
    """Everything that shapes a trace; with the seed, it IS the trace."""

    n_requests: int = 16
    vocab: int = 256

    # arrival process (tick time)
    arrival: str = "poisson"            # "poisson" | "bursty"
    mean_gap: float = 1.0               # poisson: mean inter-arrival gap
    burst_on: float = 4.0               # bursty: window of dense arrivals
    burst_off: float = 16.0             # bursty: silent gap between windows
    burst_gap: float = 0.25             # bursty: mean gap inside a window

    # prompt length mix
    prompt_dist: str = "lognormal"      # "lognormal" | "zipf"
    prompt_median: float = 32.0         # lognormal median
    prompt_sigma: float = 0.6           # lognormal log-space sigma
    prompt_buckets: Tuple[int, ...] = (8, 16, 32, 64, 128)
    prompt_zipf_a: float = 1.2          # bucket rank exponent
    prompt_min: int = 2
    prompt_max: int = 128

    # output length mix (same knobs, own values)
    output_dist: str = "lognormal"
    output_median: float = 16.0
    output_sigma: float = 0.6
    output_buckets: Tuple[int, ...] = (4, 8, 16, 32, 64)
    output_zipf_a: float = 1.2
    output_min: int = 2
    output_max: int = 64

    # tenant population: Zipf-popular tenants sharing a prompt prefix
    tenants: int = 1
    tenant_zipf_a: float = 1.2
    shared_prefix_len: int = 0


def _lengths(rng: np.random.RandomState, n: int, dist: str,
             median: float, sigma: float, buckets: Sequence[int],
             zipf_a: float, lo: int, hi: int) -> np.ndarray:
    if dist == "lognormal":
        vals = np.exp(rng.normal(np.log(median), sigma, n))
    elif dist == "zipf":
        ranks = np.arange(1, len(buckets) + 1, dtype=np.float64)
        p = ranks ** -zipf_a
        p /= p.sum()
        vals = np.asarray(buckets)[rng.choice(len(buckets), n, p=p)]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.round(vals).astype(np.int64), lo, hi)


def _arrivals(rng: np.random.RandomState, spec: LoadSpec) -> np.ndarray:
    n = spec.n_requests
    if spec.arrival == "poisson":
        return np.cumsum(rng.exponential(spec.mean_gap, n))
    if spec.arrival != "bursty":
        raise ValueError(f"unknown arrival process {spec.arrival!r}")
    # on/off: walk burst windows, filling each with exponential gaps
    # until its ``burst_on`` budget is spent, then jump ``burst_off``
    out = np.empty((n,))
    t = window_start = 0.0
    for i in range(n):
        t += float(rng.exponential(spec.burst_gap))
        if t - window_start > spec.burst_on:
            window_start = window_start + spec.burst_on + spec.burst_off
            t = window_start + float(rng.exponential(spec.burst_gap))
        out[i] = t
    return out


def generate_load(spec: LoadSpec, seed: int = 0) -> List[LoadRequest]:
    """Materialise a trace: deterministic in (spec, seed), independent
    of any engine or device state."""
    rng = np.random.RandomState(seed)
    arrivals = _arrivals(rng, spec)
    plens = _lengths(rng, spec.n_requests, spec.prompt_dist,
                     spec.prompt_median, spec.prompt_sigma,
                     spec.prompt_buckets, spec.prompt_zipf_a,
                     spec.prompt_min, spec.prompt_max)
    olens = _lengths(rng, spec.n_requests, spec.output_dist,
                     spec.output_median, spec.output_sigma,
                     spec.output_buckets, spec.output_zipf_a,
                     spec.output_min, spec.output_max)
    ranks = np.arange(1, max(1, spec.tenants) + 1, dtype=np.float64)
    tp = ranks ** -spec.tenant_zipf_a
    tp /= tp.sum()
    tenants = rng.choice(len(ranks), spec.n_requests, p=tp)
    prefixes = rng.randint(0, spec.vocab,
                           (max(1, spec.tenants),
                            max(0, spec.shared_prefix_len))
                           ).astype(np.int32)
    load: List[LoadRequest] = []
    for i in range(spec.n_requests):
        body = rng.randint(0, spec.vocab, int(plens[i])).astype(np.int32)
        prompt = np.concatenate([prefixes[int(tenants[i])], body])
        load.append(LoadRequest(index=i, arrival=float(arrivals[i]),
                                tenant=int(tenants[i]), prompt=prompt,
                                max_new_tokens=int(olens[i])))
    return load


def replay(target, load: Sequence[LoadRequest],
           max_ticks: Optional[int] = None) -> Dict[str, Any]:
    """Drive a trace through ``target`` (ServingEngine or
    ReplicaRouter): each loop iteration submits every request whose
    arrival tick has come, then runs one ``step()``, until the trace is
    exhausted and the target is idle.  Arrival time is tick time — the
    replay schedule is identical however fast the device steps, which
    is what makes two identical-seed runs comparable event-for-event.

    Returns outputs (trace order; None = rejected), the segment's
    goodput report against the deadlines recorded at submit, the
    structural timeline signature, and per-engine step retrace counts.
    """
    log = _obs.get_request_log()
    mark = log.mark()
    engines = list(getattr(target, "engines", [target]))

    def busy() -> bool:
        # pending_held: requests parked in a router's predictive hold
        # queue (ISSUE 17) — invisible to every engine, so the replay
        # must poll the target itself or it would stop with work parked
        return bool(getattr(target, "pending_held", 0)) or any(
            e.queue_depth or e.num_active or e.num_pending
            or getattr(e, "num_preempted", 0) for e in engines)

    order = sorted(range(len(load)),
                   key=lambda i: (load[i].arrival, load[i].index))
    rids: Dict[int, int] = {}           # trace index -> target rid
    rejected = 0
    tick = 0
    nxt = 0
    t0 = time.perf_counter()
    while nxt < len(order) or busy():
        while nxt < len(order) and load[order[nxt]].arrival <= tick:
            r = load[order[nxt]]
            try:
                rids[r.index] = target.submit(
                    r.prompt, max_new_tokens=r.max_new_tokens)
            except ValueError:
                rejected += 1
            nxt += 1
        target.step()
        tick += 1
        if max_ticks is not None and tick >= max_ticks:
            break
    wall = time.perf_counter() - t0
    end_mark = log.mark()
    outputs = [target.result(rids[r.index]) if r.index in rids else None
               for r in load]
    generated = sum(len(o) for o in outputs if o)
    return {
        "requests": len(load),
        "rejected": rejected,
        "ticks": tick,
        "wall_s": wall,
        "outputs": outputs,
        "generated_tokens": generated,
        "step_traces": [int(getattr(e, "step_traces", 0))
                        for e in engines],
        "slo": log.slo_report(since_uid=mark, until_uid=end_mark,
                              wall_s=wall),
        "signature": log.timeline_signature(since_uid=mark,
                                            until_uid=end_mark),
        # predicted-vs-measured attribution per engine (ISSUE 15); the
        # predicted side is schedule-deterministic — _smoke gates its
        # perf_signature byte-stable across the A/B replays
        "perf": [e.perf_report() for e in engines
                 if hasattr(e, "perf_report")],
        # the (mark, end_mark] bracket scopes any post-hoc RequestLog
        # readout — slo_report with explicit targets, Perfetto export —
        # to exactly this run
        "mark": mark,
        "end_mark": end_mark,
    }


# -- CI smoke ----------------------------------------------------------------

def _smoke() -> int:
    """Tiny seeded load against the engine modes CI guards (wave,
    chunked, paged int8-KV, preempt-saturated), each replayed twice on
    fresh engines: non-zero exit on a step retrace past budget 1, any
    determinism drift (signature, sampled outputs, or — saturated —
    the preemption-decision signature) between the identical-seed
    runs, or any graph/kernel-lint finding."""
    import json

    import jax
    # a CPU tool: it gates counts and byte-stable replay, never a device
    # number, so it pins the CPU backend whatever JAX_PLATFORMS says
    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
    from .engine import ServingEngine

    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    spec = LoadSpec(n_requests=8, arrival="poisson", mean_gap=1.5,
                    prompt_dist="zipf", prompt_buckets=(8, 16, 32, 48),
                    prompt_zipf_a=1.1, prompt_max=48,
                    output_dist="lognormal", output_median=6.0,
                    output_sigma=0.4, output_min=3, output_max=10,
                    tenants=2, shared_prefix_len=4)
    load = generate_load(spec, seed=11)

    modes = {"wave": {}, "chunked": {"chunked": True, "prefill_chunk": 8},
             # quantized-cache drift canary (ISSUE 13): one paged int8-KV
             # replay so a regression in the quantize-at-scatter /
             # dequant-in-kernel path fails CI, not just the bench
             "int8_paged": {"paged": True, "block_len": 16,
                            "kv_cache_dtype": "int8"},
             # preemption canary (ISSUE 16): a pool too tight for the
             # trace to fit resident, so the preemptive scheduler must
             # evict mid-decode and swap back in via the host tier —
             # gated below on preemptions actually firing and on the
             # victim-decision signature replaying byte-stable
             "saturated": {"paged": True, "block_len": 8,
                           "num_blocks": 12, "preempt": "swap",
                           "host_blocks": 32}}
    failures: List[str] = []
    summary: Dict[str, Any] = {"requests": spec.n_requests}
    for mode, kw in modes.items():
        runs = []
        kernel_findings = -1
        preempt_sigs: List[str] = []
        preemptions: List[int] = []
        for _ in range(2):
            eng = ServingEngine(model, num_slots=4, max_length=128,
                                prefill_batch=2, **kw)
            if kernel_findings < 0:
                # ISSUE 14 CI gate: the kernels this mode's dispatch
                # would select must pre-flight clean (static — no
                # compile), so a kernel-lint regression fails the smoke.
                # The saturated mode runs the FULL merged lint
                # (graph rules + kernel pre-flight) — the ISSUE 16
                # contract is zero findings of either kind
                kf = (eng.lint_step() if mode == "saturated"
                      else eng.kernel_preflight()["findings"])
                kernel_findings = len(kf)
                if kf:
                    failures.append(
                        f"{mode}: pre-flight findings: "
                        + "; ".join(str(f) for f in kf))
            runs.append(replay(eng, load))
            if mode == "saturated":
                preempt_sigs.append(eng.preempt_signature())
                preemptions.append(sum(
                    eng.metrics()["preempt"]["preemptions"].values()))
        a, b = runs
        traces = max(max(r["step_traces"]) for r in runs)
        if traces > 1:
            failures.append(f"{mode}: step retraced (traces={traces})")
        if a["signature"] != b["signature"]:
            failures.append(f"{mode}: timeline signature drift between "
                            f"identical-seed runs")
        if a["outputs"] != b["outputs"]:
            failures.append(f"{mode}: sampled-output drift between "
                            f"identical-seed runs")
        # ISSUE 15 gates: the cost-model report must be clean (no drift
        # findings, no perf anomalies) on the deterministic CPU traces,
        # and its predicted side byte-stable across the A/B replays
        perf_sigs = []
        drift_findings = 0
        anomalies = 0
        for r in (a, b):
            for rep in r.get("perf", []):
                if not rep.get("enabled", False):
                    continue
                perf_sigs.append(_obs.perf_signature(rep))
                drift_findings += len(rep.get("drift", []))
                anomalies += sum(rep.get("anomalies", {}).values())
        if drift_findings:
            failures.append(f"{mode}: {drift_findings} cost-model drift "
                            f"finding(s) on a deterministic CPU trace")
        if anomalies:
            failures.append(f"{mode}: {anomalies} serving.perf_anomalies "
                            f"detection(s) on a deterministic CPU trace")
        if len(set(perf_sigs)) > 1:
            failures.append(f"{mode}: perf_report predicted-side drift "
                            f"between identical-seed runs")
        if mode == "saturated":
            # the mode only tests anything if the pool actually forced
            # eviction, and the victim decisions must replay byte-stable
            if not all(preemptions):
                failures.append(
                    "saturated: tight pool produced no preemption — "
                    "the mode is not exercising the scheduler")
            if len(set(preempt_sigs)) > 1:
                failures.append(
                    "saturated: preemption-decision signature drift "
                    "between identical-seed runs")
        summary[mode] = {
            "ticks": a["ticks"],
            "generated_tokens": a["generated_tokens"],
            "step_traces": traces,
            "goodput": a["slo"]["goodput"],
            "kernel_findings": kernel_findings,
            "perf_drift_findings": drift_findings,
            "perf_anomalies": anomalies,
            "perf_deterministic": len(set(perf_sigs)) <= 1,
            "deterministic": (a["signature"] == b["signature"]
                              and a["outputs"] == b["outputs"])}
        if mode == "saturated":
            summary[mode]["preemptions"] = preemptions
            summary[mode]["preempt_signature_stable"] = (
                len(set(preempt_sigs)) <= 1)
    summary["fleet_sim"] = _smoke_fleet_sim(model, load, failures)
    summary["multihost"] = _smoke_multihost(model, load, failures)
    summary["federated"] = _smoke_federated(model, load, failures)
    summary["spec_model"] = _smoke_spec_model(model, load, failures)
    summary["failures"] = failures
    print(json.dumps(summary, indent=2))
    return 1 if failures else 0


def _smoke_fleet_sim(model, load: Sequence[LoadRequest],
                     failures: List[str]) -> Dict[str, Any]:
    """ISSUE 17 CI gates for the device-free fleet simulator
    (serving/fleet_sim.py), two halves:

    * sim-vs-engine agreement — the SAME small trace through a real
      paged CPU engine and a SimEngine cloned from its cost model must
      produce the IDENTICAL structural schedule: equal tick counts,
      equal per-request token counts, byte-equal timeline signatures
      and equal goodput (scheduling decisions are shared code and a
      pure function of scheduler state, so the tolerance is exact;
      only the clock domains differ — BASELINE.md "Simulated-clock
      accounting conventions");

    * fleet determinism — a small multi-replica heavy-tail scenario
      replayed twice must produce byte-identical fleet signatures."""
    from . import fleet_sim as _fs
    from .engine import ServingEngine

    kw = dict(num_slots=4, max_length=128, prefill_batch=2,
              block_len=16)
    eng = ServingEngine(model, paged=True, **kw)
    spec = _fs.SimSpec.from_engine(eng)
    er = replay(eng, load)
    sr = replay(_fs.SimEngine(spec, **kw), load)
    agree = {
        "ticks": (er["ticks"], sr["ticks"]),
        "token_counts_equal": (
            [len(o) if o else 0 for o in er["outputs"]]
            == [len(o) if o else 0 for o in sr["outputs"]]),
        "signature_equal": er["signature"] == sr["signature"],
        "goodput": (er["slo"]["goodput"], sr["slo"]["goodput"]),
    }
    if er["ticks"] != sr["ticks"]:
        failures.append(
            f"fleet_sim: tick-count disagreement with the real engine "
            f"({er['ticks']} vs {sr['ticks']})")
    if not agree["token_counts_equal"]:
        failures.append(
            "fleet_sim: per-request token counts disagree with the "
            "real engine on the shared trace")
    if not agree["signature_equal"]:
        failures.append(
            "fleet_sim: structural timeline disagrees with the real "
            "engine on the shared trace")
    if er["slo"]["goodput"] != sr["slo"]["goodput"]:
        failures.append(
            f"fleet_sim: goodput disagreement with the real engine "
            f"({er['slo']['goodput']} vs {sr['slo']['goodput']})")
    sigs = [
        _fs.run_fleet(requests=300, replicas=4, num_slots=4,
                      admission="predictive", seed=5)["signature"]
        for _ in range(2)]
    if len(set(sigs)) != 1:
        failures.append("fleet_sim: fleet signature drift between "
                        "identical-seed replays")
    return dict(agree, fleet_signature_stable=len(set(sigs)) == 1)


def _smoke_multihost(model, load: Sequence[LoadRequest],
                     failures: List[str]) -> Dict[str, Any]:
    """ISSUE 18 CI gates for the multi-host plane, run entirely over
    LoopbackTransport (full RPC serialization, zero processes):

    * the trace replayed twice through frontend-grade plumbing
      (plane -> 2 engine workers) must keep the once-jitted budget
      (step_traces <= 1), lint clean, and replay byte-stable
      (timeline signature AND sampled outputs);

    * a worker killed mid-trace must NOT hang or drop work: every
      request still finishes, token-identical to the no-kill replay,
      each under its ONE original lifecycle uid."""
    from collections import OrderedDict

    from .engine import ServingEngine
    from .multihost import EngineWorker, LoopbackTransport, MultiHostRouter

    # the modes above created ~a dozen engines; their per-engine counter
    # children sit near the metrics_max_children cap, and a collapsed
    # {overflow} child would MERGE step-trace counts across engines and
    # fail the budget gate spuriously.  This leg builds everything
    # fresh, so start it on a clean registry (replay brackets the
    # request log with mark(), nothing above reads the registry later).
    _obs.reset()

    def mk_plane():
        workers = OrderedDict()
        engines = []
        for i in range(2):
            eng = ServingEngine(model, num_slots=4, max_length=128,
                                prefill_batch=2, paged=True, block_len=8)
            engines.append(eng)
            w = EngineWorker(eng, name=f"w{i}")
            workers[f"w{i}"] = LoopbackTransport(w.handle, name=f"w{i}")
        return MultiHostRouter(workers, policy="prefix"), engines

    runs = []
    lint_findings = -1
    for _ in range(2):
        plane, engines = mk_plane()
        if lint_findings < 0:
            kf = [f for e in engines for f in e.lint_step()]
            lint_findings = len(kf)
            if kf:
                failures.append("multihost: lint findings: "
                                + "; ".join(str(f) for f in kf))
        runs.append(replay(plane, load))
    a, b = runs
    traces = max(max(r["step_traces"]) for r in runs)
    if traces > 1:
        failures.append(f"multihost: step retraced (traces={traces})")
    if a["signature"] != b["signature"]:
        failures.append("multihost: timeline signature drift between "
                        "identical-seed runs")
    if a["outputs"] != b["outputs"]:
        failures.append("multihost: sampled-output drift between "
                        "identical-seed runs")

    # -- worker-kill leg: same trace, one transport killed mid-flight
    plane, _ = mk_plane()
    order = sorted(range(len(load)),
                   key=lambda i: (load[i].arrival, load[i].index))
    rids: Dict[int, int] = {}
    tick = 0
    nxt = 0
    killed = False
    while nxt < len(order) or any(not r.done
                                  for r in plane._reqs.values()):
        while nxt < len(order) and load[order[nxt]].arrival <= tick:
            r = load[order[nxt]]
            rids[r.index] = plane.submit(
                r.prompt, max_new_tokens=r.max_new_tokens)
            nxt += 1
        plane.step()
        tick += 1
        if not killed and tick >= 3:
            victim = next((plane.worker_of(rid) for rid in rids.values()
                           if plane.worker_of(rid) is not None), None)
            if victim is not None:
                plane._workers[victim].kill()
                killed = True
    if not killed:
        failures.append("multihost: kill leg never found a placed "
                        "request to orphan")
    kill_outputs = [plane.result(rids[r.index])
                    if r.index in rids else None for r in load]
    finished_all = all(o is not None and len(o) > 0 for o in kill_outputs)
    if not finished_all:
        failures.append("multihost: killed worker left unfinished "
                        "requests (failover hang)")
    if kill_outputs != a["outputs"]:
        failures.append("multihost: post-kill outputs drifted from the "
                        "no-kill replay (recompute-from-prefix broke "
                        "token identity)")
    one_timeline = all(
        _obs.get_request_log().event_names(
            plane.request_uid(rid)).count("submitted") == 1
        for rid in rids.values())
    if not one_timeline:
        failures.append("multihost: a failed-over request forked its "
                        "lifecycle timeline (uid not threaded)")
    return {
        "ticks": a["ticks"],
        "generated_tokens": a["generated_tokens"],
        "step_traces": traces,
        "lint_findings": lint_findings,
        "deterministic": (a["signature"] == b["signature"]
                          and a["outputs"] == b["outputs"]),
        "kill": {"fired": killed,
                 "lost_workers": len(plane.lost_workers),
                 "failovers": int(
                     plane.metrics()["aggregate"]["failovers"]),
                 "finished_all": finished_all,
                 "outputs_match_no_kill": kill_outputs == a["outputs"],
                 "one_timeline_per_uid": one_timeline},
    }


def _smoke_federated(model, load: Sequence[LoadRequest],
                     failures: List[str]) -> Dict[str, Any]:
    """ISSUE 19 CI gates for the federated observability layer, run
    over a 2-worker loopback plane under INJECTED deterministic clocks
    (every time source — the request log, the engines, the transports'
    server clocks — reads one virtual counter, with a fixed per-worker
    skew on the server side so the NTP-style estimator has real work):

    * federated ``/metrics`` counter totals must EXACTLY equal the sum
      of the per-worker (engine-scoped) registry series;
    * each transport's recovered clock offset must sit within the
      min-RTT error bound of its injected skew;
    * the merged timeline must be valid Perfetto JSON carrying the
      plane track, BOTH worker process tracks, rpc.call slices split
      into wire/in_worker, and per-request hop tracks;
    * the fleet-obs signature must replay byte-stable across two
      identical-seed runs;
    * one real HTTP GET each of /metrics and /fleet must serve the
      federated exposition and a healthy roster with tick-accurate
      heartbeat ages."""
    import urllib.request
    from collections import OrderedDict

    from ..observability.http_exposition import ExpositionServer
    from .engine import ServingEngine
    from .multihost import EngineWorker, LoopbackTransport, MultiHostRouter

    # same reasoning as the multihost leg: fresh engines near the
    # cardinality cap would coalesce, and a coalesced registry breaks
    # the exact federated-total equality this leg gates
    _obs.reset()
    log = _obs.get_request_log()
    skews = {"w0": 37.0, "w1": -53.0}       # ms the worker clock leads
    out: Dict[str, Any] = {"skews_ms": dict(skews)}

    def run_once(http_leg: bool) -> Dict[str, Any]:
        saved_clock, saved_t0 = log._clock, log._t0
        cell = {"t": 0.0}

        def vclock() -> float:              # virtual seconds; each read
            cell["t"] += 1e-4               # advances 0.1 ms
            return cell["t"]

        log._clock, log._t0 = vclock, 0.0
        try:
            workers = OrderedDict()
            engines = []
            for i in range(2):
                n = f"w{i}"
                eng = ServingEngine(model, num_slots=4, max_length=128,
                                    prefill_batch=2, paged=True,
                                    block_len=8)
                eng._clock = vclock         # SLO stamps off the wall too
                engines.append(eng)
                w = EngineWorker(eng, name=n)
                workers[n] = LoopbackTransport(
                    w.handle, name=n,
                    server_clock=(lambda s=skews[n]: log.now_ms() + s))
            plane = MultiHostRouter(workers, policy="prefix")
            rep = replay(plane, load)
            r: Dict[str, Any] = {"ticks": rep["ticks"]}

            # federated totals == sum of the per-worker registry series
            fed = plane.federation()
            merged = fed.merged()
            eids = {str(e._eid) for e in engines}
            proc = _obs.snapshot()
            bad = []
            n_counters = 0
            for name, fam in merged.items():
                if name in ("schema_version", "workers") \
                        or fam["type"] != "counter":
                    continue
                n_counters += 1
                want = sum(float(row["value"])
                           for row in proc[name]["series"]
                           if str(row["labels"].get("engine", ""))
                           in eids)
                got = float(fam["pooled"]["value"])
                if got != want:
                    bad.append(f"{name}: federated {got} != sum of "
                               f"worker registries {want}")
            if not n_counters:
                bad.append("no counter families federated at all")
            if bad:
                failures.append("federated: " + "; ".join(bad))
            r["counter_families"] = n_counters
            r["counter_totals_equal"] = not bad

            # recovered offsets within the min-RTT bound of the skew
            offs = {}
            for n, t in plane._workers.items():
                est = t.stitch.estimator
                err = abs(est.offset_ms - skews[n])
                offs[n] = {"offset_ms": round(est.offset_ms, 6),
                           "error_ms": round(err, 6),
                           "bound_ms": round(est.error_bound_ms, 6)}
                if not est.ready or err > est.error_bound_ms + 1e-9:
                    failures.append(
                        f"federated: {n} recovered offset "
                        f"{est.offset_ms} is outside the min-RTT bound "
                        f"of the injected skew {skews[n]}")
            r["offsets"] = offs

            # one merged, valid Perfetto timeline with every track kind
            trace = plane.export_merged_perfetto(
                since_uid=rep["mark"], until_uid=rep["end_mark"])
            import json as _json
            _json.dumps(trace)              # valid Perfetto JSON
            evs = trace["traceEvents"]
            procs = {e["args"]["name"] for e in evs
                     if e.get("name") == "process_name"}
            structure = {
                "worker_tracks": {"paddle_tpu worker w0",
                                  "paddle_tpu worker w1"} <= procs,
                "plane_track": "paddle_tpu plane" in procs,
                "rpc_split": (
                    any(str(e.get("name", "")).startswith("rpc.call:")
                        for e in evs)
                    and any(e.get("name") == "wire" for e in evs)
                    and any(e.get("name") == "in_worker" for e in evs)),
                "request_tracks": any(
                    str(e.get("name", "")).startswith("on w")
                    for e in evs)}
            if not all(structure.values()):
                failures.append(
                    f"federated: merged timeline is missing tracks: "
                    f"{[k for k, v in structure.items() if not v]}")
            r["merged_timeline"] = structure

            # tick-accurate heartbeat ages + live roster
            fleet = plane.fleet_report()
            hb = plane._hb_every
            exp_age = plane._ticks - hb * ((plane._ticks - 1) // hb)
            ages = {n: w["heartbeat_age_ticks"]
                    for n, w in fleet["workers"].items()}
            if not all(w["alive"] for w in fleet["workers"].values()):
                failures.append("federated: a loopback worker reported "
                                "dead on a clean run")
            if any(a != exp_age for a in ages.values()):
                failures.append(
                    f"federated: heartbeat ages {ages} are not tick-"
                    f"accurate (expected {exp_age} after "
                    f"{plane._ticks} ticks, heartbeat_every={hb})")
            r["heartbeat_age_ticks"] = ages

            r["signature"] = plane.fleet_obs_signature(
                since_uid=rep["mark"], until_uid=rep["end_mark"])

            if http_leg:
                with ExpositionServer(port=-1, engines=[plane]) as srv:
                    base = f"http://127.0.0.1:{srv.port}"
                    text = urllib.request.urlopen(
                        base + "/metrics", timeout=10).read().decode()
                    fl = _json.loads(urllib.request.urlopen(
                        base + "/fleet", timeout=10).read().decode())
                http_ok = {
                    "metrics_has_fleet_prefix":
                        "paddle_tpu_fleet_" in text,
                    "metrics_has_worker_labels":
                        'worker="w0"' in text and 'worker="w1"' in text,
                    "fleet_reports_both_workers": all(
                        fl["workers"].get(n, {}).get("alive")
                        for n in ("w0", "w1"))}
                if not all(http_ok.values()):
                    failures.append(
                        f"federated: HTTP exposition gaps: "
                        f"{[k for k, v in http_ok.items() if not v]}")
                r["http"] = http_ok
            return r
        finally:
            log._clock, log._t0 = saved_clock, saved_t0

    a = run_once(http_leg=True)
    b = run_once(http_leg=False)
    if a["signature"] != b["signature"]:
        failures.append("federated: fleet-obs signature drift between "
                        "identical-seed replays")
    out.update(a)
    out["signature_stable"] = a["signature"] == b["signature"]
    return out


def _smoke_spec_model(model, load: Sequence[LoadRequest],
                      failures: List[str]) -> Dict[str, Any]:
    """ISSUE 20 CI gates for draft-model speculation: the trace replayed
    twice through a 2-replica loopback plane running MIXED drafters
    (replica w0 a truncated draft model, w1 the n-gram drafter) must
    keep BOTH once-jitted budgets (verify step and draft step, 1 trace
    each), replay byte-stable (timeline signature and sampled outputs),
    lint clean, and the per-shard kernel geometry a model-parallel
    engine would pre-flight (heads/mp, the ``mpN-shard`` variant) must
    be finding-free — all device-free except the tiny CPU replay."""
    from collections import OrderedDict

    from .. import static_analysis as _sa
    from ..models.llama import draft_model_from
    from .engine import ServingEngine
    from .multihost import EngineWorker, LoopbackTransport, MultiHostRouter

    # fresh registry: this leg builds its own engines and reads their
    # trace budgets; collapsed {overflow} children from the modes above
    # would merge counters across engines (same reasoning as multihost)
    _obs.reset()
    dm, dparams = draft_model_from(model, num_layers=1)

    def mk_plane():
        workers = OrderedDict()
        engines = []
        for name, kw in (("w0", {"drafter": "model",
                                 "draft_model": (dm, dparams)}),
                         ("w1", {"drafter": "ngram"})):
            eng = ServingEngine(model, num_slots=4, max_length=128,
                                prefill_batch=2, spec_decode=True,
                                spec_k=3, **kw)
            engines.append(eng)
            w = EngineWorker(eng, name=name)
            workers[name] = LoopbackTransport(w.handle, name=name)
        return MultiHostRouter(workers, policy="prefix"), engines

    runs = []
    lint_findings = -1
    draft_traces = 0
    drafted: Dict[str, int] = {}
    for _ in range(2):
        plane, engines = mk_plane()
        if lint_findings < 0:
            kf = [f for e in engines for f in e.lint_step()]
            lint_findings = len(kf)
            if kf:
                failures.append("spec_model: lint findings: "
                                + "; ".join(str(f) for f in kf))
        runs.append(replay(plane, load))
        for e in engines:
            by = e.metrics().get("spec", {}).get("by_drafter", {})
            for kind, m in by.items():
                drafted[kind] = (drafted.get(kind, 0)
                                 + m["drafted_tokens"])
            d = e._drafter
            if getattr(d, "uses_device", False):
                draft_traces = max(draft_traces, d.draft_traces)
    a, b = runs
    traces = max(max(r["step_traces"]) for r in runs)
    if traces > 1:
        failures.append(f"spec_model: verify step retraced "
                        f"(traces={traces})")
    if draft_traces > 1:
        failures.append(f"spec_model: draft step retraced "
                        f"(traces={draft_traces})")
    if a["signature"] != b["signature"]:
        failures.append("spec_model: timeline signature drift between "
                        "identical-seed runs")
    if a["outputs"] != b["outputs"]:
        failures.append("spec_model: sampled-output drift between "
                        "identical-seed runs")
    if drafted.get("model", 0) <= 0:
        failures.append("spec_model: the draft-model replica proposed "
                        "nothing — the mode is not exercising the "
                        "drafter")
    # per-shard pre-flight: the exact geometry a mesh (mp=2) engine's
    # _kernel_specs projects — heads/mp, head_dim and cache length
    # rounded to kernel tiles — must lint clean (static, no devices)
    c = model.config
    mp = 2
    hq = max(int(c.num_attention_heads) // mp, 1)
    hkv = max(int(c.num_key_value_heads) // mp, 1)
    shard_spec = _sa.decode_attention_spec(
        4, 4, hq, hkv, 128, kv_len=4096,
        variant=f"contiguous,spec_verify,s=4,mp{mp}-shard")
    shard_findings = _sa.analyze_kernels([shard_spec])
    if shard_findings:
        failures.append("spec_model: per-shard kernel pre-flight "
                        "findings: "
                        + "; ".join(str(f) for f in shard_findings))
    return {
        "ticks": a["ticks"],
        "generated_tokens": a["generated_tokens"],
        "step_traces": traces,
        "draft_step_traces": draft_traces,
        "lint_findings": lint_findings,
        "kernel_findings": len(shard_findings),
        "drafted_tokens_by_kind": dict(sorted(drafted.items())),
        "deterministic": (a["signature"] == b["signature"]
                          and a["outputs"] == b["outputs"]),
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving.loadgen",
        description="trace-driven serving load harness")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny seeded load against both engine modes on "
                         "CPU; exits non-zero on retrace-budget or "
                         "determinism drift (the CI hook)")
    args = ap.parse_args(argv)
    if args.smoke:
        return _smoke()
    ap.print_help()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
