"""Device-free fleet simulator: the REAL control plane on a cost-model
clock (ISSUE 17 tentpole c).

``SimEngine`` is a :class:`~paddle_tpu.serving.engine.ServingEngine`
with the device removed and NOTHING else replaced: the same ``submit``
/ ``step`` / ``drain`` scheduler, the same paged admission
(``_admit``, ``_prefill_wave``), the same :class:`~paddle_tpu.serving.kv_cache.
BlockManager` pool (prefix trie, COW, reservations, host tier), the
same preemption machinery and the same predictive-admission gate — but
every jitted dispatch is replaced by the roofline cost model's
prediction for that tick, and the engine's ``_clock`` indirection (the
one time source every SLO stamp reads through) returns a simulated
clock that those predictions advance.  Tokens are synthesized by a
deterministic hash, so a trace replays byte-identically however fast
the host runs it.

``FleetSim`` puts N SimEngines behind the REAL
:class:`~paddle_tpu.serving.router.ReplicaRouter` — predictive
admission, the priced hold queue and elastic add/drain/retire all
execute the production code paths — which is what lets a ≥100k-request,
≥16-replica heavy-tail scenario replay in seconds of CPU wall and
answer capacity questions (replica counts, admission policies, SLO
settings) without a device.

What the simulator deliberately does NOT model (BASELINE.md
"Simulated-clock accounting conventions"): compile/retrace time,
host-swap wall jitter, and any measured/predicted residual — measured
IS predicted here, so the perf layer sees ratio 1.0 everywhere and the
drift detectors stay quiet by construction.  Sim milliseconds are the
cost model's domain; never compare them against wall milliseconds
without the FLAGS_serving_admission_calib bridge.

Unsupported engine modes raise at construction: chunked prefill,
speculative decoding and meshes change the dispatch structure the
simulator replaces, and quantized caches only change device bytes the
sim spec already captures in ``kv_token_bytes``.

CLI::

    python -m paddle_tpu.serving.fleet_sim --requests 100000 \
        --replicas 16 --admission predictive

runs the heavy-tail scale scenario twice and gates the two runs'
signatures byte-identical (the determinism contract the bench row and
the loadgen ``fleet_sim`` smoke mode also enforce).
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .. import flags as _flags
from ..models.parts import ServingTraits
from ..observability import costmodel as _cm
from ..observability import tracing as _obs
from . import loadgen as _loadgen
from .engine import ServingEngine
from .router import ReplicaRouter

__all__ = ["SimSpec", "SimEngine", "FleetSim", "fleet_load_spec",
           "run_fleet", "fleet_signature", "main"]

#: synthesized-token alphabet (any fixed size works; matching a real
#: tokenizer's vocab keeps prompt/output token ids in a familiar range)
_SIM_VOCAB = 50257


@dataclasses.dataclass(frozen=True)
class SimSpec:
    """The simulated model: exactly the static byte/FLOP inputs the
    roofline :class:`~paddle_tpu.observability.costmodel.CostModel`
    needs — nothing else about the model matters to the scheduler."""

    name: str
    weight_bytes: int           # params footprint streamed per tick
    n_params: int               # dense FLOP model: 2*N per token
    kv_token_bytes: float       # HBM bytes one live context token costs

    @classmethod
    def default(cls) -> "SimSpec":
        """A ~940M-param bf16 decoder (the committed llama_940m bench
        shape): 24 layers x 2 (K+V) x 4 kv-heads x 64 head-dim = 12288
        cache elements per token at 2 bytes each."""
        return cls(name="sim_940m", weight_bytes=1_880_000_000,
                   n_params=940_000_000,
                   kv_token_bytes=float(24 * 2 * 4 * 64 * 2))

    @classmethod
    def from_engine(cls, engine: ServingEngine) -> "SimSpec":
        """Clone a live engine's cost-model inputs, so a SimEngine
        predicts exactly what the real engine's perf layer predicts —
        the sim-vs-engine agreement gate builds its twin this way."""
        if engine._perf is None:
            raise ValueError(
                "SimSpec.from_engine needs the engine's cost model: "
                "construct the engine with FLAGS_perf_model='on'")
        m = engine._perf.model
        return cls(name=f"from_engine_{engine._eid}",
                   weight_bytes=m.weight_bytes, n_params=m.n_params,
                   kv_token_bytes=m.kv_token_bytes)


class SimEngine(ServingEngine):
    """ServingEngine minus the device (see module docstring).

    The constructor deliberately does NOT chain to
    ``ServingEngine.__init__`` — there is no model, no params, no
    jitted program — but it builds the identical host-side state
    catalog, so every inherited scheduler method (``submit``, ``step``,
    ``_admit``, the tick, preemption, cancel, metrics, the predictive
    admission gate) runs unmodified.  Only four methods are overridden:
    the two device seams (``_device_step``, ``_device_prefill``) swap
    upload, dispatch and readback for a cost-model prediction +
    simulated-clock advance, and the two host-tier hooks account swap
    bytes without moving payloads."""

    def __init__(self, spec: SimSpec, *, num_slots: int = 8,
                 max_length: int = 1024, prefill_batch: int = 4,
                 seed: int = 0, block_len: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 preempt: str = "off",
                 host_blocks: int = 0,
                 eos_token_id: Optional[int] = None,
                 pad_token_id: int = 0,
                 profile: Optional[_cm.HardwareProfile] = None):
        self.sim_spec = spec
        self.model = None
        self.config = None
        self.num_slots = int(num_slots)
        self.max_length = int(max_length)
        self.eos_token_id = eos_token_id
        self.pad_token_id = int(pad_token_id)
        self.prefill_batch = int(prefill_batch)
        self._int8_weights = False
        # no model: no expert layers' load, no window layers' dead
        # positions, no per-slot state, no kernel whose block walk the
        # spans would count
        self._bind_traits(ServingTraits())
        self._kv_walk_geom = None
        # the simulator is paged-only: the BlockManager IS the part of
        # the memory system worth simulating (admission blocking,
        # prefix hits, preemption, the host tier)
        self.paged = True
        self.kv_dtype = "bf16"
        self.quantized = False
        if bool(_flags.flag("serving_chunked_prefill")):
            raise NotImplementedError(
                "SimEngine does not model chunked prefill (the mixed "
                "step's chunk cursor is a dispatch-structure feature)")
        # (nor speculative decoding: accept rates depend on real logits)
        self.chunked = False
        self.prefill_chunk = 256
        self._chunk_policy = "prefill"
        self.spec = False
        self.spec_k = 4
        self._init_preempt(preempt, host_blocks)
        self.mesh = None
        self._init_metrics()
        self._init_scheduler_state()
        _, bl = self._init_pool(block_len, num_blocks, prefix_cache)
        self._sim_block_nbytes = int(round(spec.kv_token_bytes * bl))
        self.kv.set_block_nbytes({"bf16": self._sim_block_nbytes})
        self._params = None
        self._cache = None               # the pool has no device twin
        # COW privatisation is pool bookkeeping here; the device copy
        # the real engine dispatches has no simulated cost of its own
        # (it rides inside the tick the cost model already prices)
        self._cow_fn = lambda cache, src, dst: cache
        if self._host_blocks > 0:
            self.kv.on_swap_out = self._host_swap_out
            self.kv.on_swap_in = self._host_swap_in
        self._base_key = None            # tokens are hash-synthesized
        self._seed = int(seed)
        # the simulated clock: every SLO stamp reads _clock(), and the
        # overridden device seams advance _now_s by the model's
        # prediction — sim seconds ARE predicted milliseconds / 1e3
        self._now_s = 0.0
        self._clock = lambda: self._now_s
        self._step_fn = None
        self._prefill_fn = None
        self._linted = True              # no jitted program to lint
        self._cost = _cm.CostModel(
            profile or _cm.resolve_profile(),
            weight_bytes=spec.weight_bytes, n_params=spec.n_params,
            kv_token_bytes=spec.kv_token_bytes,
            num_slots=self.num_slots)
        self._perf = (_cm.TickAttribution(self._cost,
                                          engine_id=self._eid)
                      if _flags.flag("perf_model") == "on" else None)

    # -- simulated time ----------------------------------------------------

    @property
    def sim_time_s(self) -> float:
        """This replica's simulated clock (cost-model seconds)."""
        return self._now_s

    def _sim_token(self, rid: int, pos: int) -> int:
        """Deterministic token synthesis: a pure hash of (request id,
        position, seed), steered off the EOS id so the trace's
        max_new_tokens — not sampling luck — decides every length."""
        tok = (rid * 1_000_003 + pos * 10_007
               + self._seed * 7_919) % _SIM_VOCAB
        if self.eos_token_id is not None and tok == self.eos_token_id:
            tok = (tok + 1) % _SIM_VOCAB
        return tok

    # -- the device seams --------------------------------------------------

    def _device_step(self, own, chunk) -> List[np.ndarray]:
        """The step program replaced by the cost model's prediction for
        this tick: the simulated clock advances by the predicted
        milliseconds, so ``_perf_tick`` records measured == predicted
        (same memo key: ratio 1.0, no drift, byte-stable perf signature),
        and every busy row's token is synthesized."""
        # inactive rows hold position 0 (_clear_slot), so the full sum
        # IS the live-token depth — no boolean-mask temporary
        pred = self._cost.predicted_tick_ms(
            self.num_active, int(self._positions.sum()),
            swap_bytes=self._tick_swap_bytes)
        self._now_s += pred / 1e3
        nxt = np.full((self.num_slots,), self.pad_token_id, np.int32)
        for i, slot in enumerate(self._slots):
            if slot is not None:
                nxt[i] = self._sim_token(slot.rid, int(self._positions[i]))
        return [nxt]

    def _device_prefill(self, wave, bucket) -> List[int]:
        """The prefill program replaced by its modeled cost — one tick
        whose chunk term carries the computed suffix tokens (prefix hits
        ride free, exactly like the real wave's suffix-only compute) —
        and synthesized first tokens."""
        suffix = sum(int(req.prompt.size) - int(m) for req, _, m in wave)
        pred = self._cost.predicted_tick_ms(len(wave), suffix,
                                            chunk_tokens=suffix)
        self._now_s += pred / 1e3
        return [self._sim_token(req.request_id, int(req.prompt.size))
                for req, _, _ in wave]

    # -- host-tier hooks (byte accounting only) ----------------------------

    def _host_swap_out(self, pairs):
        tier = self.kv.host_tier
        for bid, hid in pairs:
            tier.put(hid, None)          # the payload is virtual
            self._tick_swap_bytes += self._sim_block_nbytes
            self._m_swap_out_bytes.inc(self._sim_block_nbytes)

    def _host_swap_in(self, pairs):
        for hid, bid in pairs:
            self._tick_swap_bytes += self._sim_block_nbytes
            self._m_swap_in_bytes.inc(self._sim_block_nbytes)

    # -- device-only surfaces ----------------------------------------------

    def lint_step(self):
        """No jitted program, nothing to lint."""
        return []

    def kernel_preflight(self):
        raise NotImplementedError(
            "SimEngine has no device programs to preflight")


class FleetSim:
    """N SimEngine replicas behind the real ReplicaRouter (same
    ``submit``/``step``/``drain``/``result`` surface, so
    ``loadgen.replay`` drives it unchanged).  Per-replica simulated
    clocks advance independently — replicas tick in lockstep but a
    loaded replica's tick costs more — and the fleet's simulated wall
    is the slowest replica's clock."""

    def __init__(self, num_replicas: int = 16,
                 spec: Optional[SimSpec] = None, *,
                 policy: str = "prefix", seed: int = 0,
                 **engine_kwargs: Any):
        self.spec = spec or SimSpec.default()
        self.engines = [SimEngine(self.spec, seed=seed + i,
                                  **engine_kwargs)
                        for i in range(int(num_replicas))]
        self.router = ReplicaRouter(engines=self.engines, policy=policy)

    # the router surface loadgen.replay expects
    def submit(self, *a: Any, **kw: Any) -> int:
        return self.router.submit(*a, **kw)

    def step(self) -> List[int]:
        return self.router.step()

    def drain(self):
        return self.router.drain()

    def result(self, rid: int) -> List[int]:
        return self.router.result(rid)

    @property
    def pending_held(self) -> int:
        return self.router.pending_held

    @property
    def sim_wall_s(self) -> float:
        """Fleet simulated wall: the slowest replica's clock."""
        return max(e.sim_time_s for e in self.engines)

    def worker_clocks(self) -> Dict[str, float]:
        """Per-replica simulated clocks in ms, keyed ``replica<i>`` —
        the fleet-sim analogue of the multihost plane's stitched
        per-worker clocks.  No wire time is modelled, so these ARE the
        exact offsets a plane-side estimator would recover (BASELINE.md
        "Fleet observability conventions")."""
        return {f"replica{i}": round(e.sim_time_s * 1e3, 6)
                for i, e in enumerate(self.engines)}

    def slo_by_worker(self, slo: Dict[str, Any]) -> Dict[str, Any]:
        """A replay report's ``by_worker`` SLO attribution re-keyed
        from per-process ``engine:<id>`` onto run-stable ``replica<i>``
        names — the same federated attribution the multihost plane
        reports keyed by worker name, proving the one slo_report code
        path serves both clock domains."""
        eid_to_replica = {f"engine:{e._eid}": f"replica{i}"
                          for i, e in enumerate(self.engines)}
        byw = slo.get("by_worker") or {}
        return {eid_to_replica.get(k, k): v
                for k, v in sorted(byw.items())}

    def report(self) -> Dict[str, Any]:
        return {
            "spec": dataclasses.asdict(self.spec),
            "replicas": len(self.engines),
            "sim_wall_s": round(self.sim_wall_s, 6),
            "per_replica": [
                {"ticks": e._ticks,
                 "sim_time_s": round(e.sim_time_s, 6),
                 "requests_finished": int(e._m_finished.value()),
                 "tokens_generated": int(e._m_tokens.value())}
                for e in self.engines],
            "router": self.router.metrics()["aggregate"]["control_plane"],
        }


def fleet_signature(fleet: FleetSim,
                    replay_report: Dict[str, Any]) -> str:
    """sha256 over the deterministic state of one fleet replay: the
    structural request timeline, every replica's scheduler counters +
    simulated clock + preemption log + perf signature, and the sampled
    outputs.  Engine/router ids and host wall-clock fields are
    excluded, so two identical-seed runs in one process (fresh engines,
    new ids) must produce byte-identical signatures."""
    body = {
        "timeline": replay_report["signature"],
        "outputs": [o if o is None else list(map(int, o))
                    for o in replay_report["outputs"]],
        "per_replica": [
            {"ticks": e._ticks,
             "clock_ms": round(e.sim_time_s * 1e3, 6),
             "preempt": e.preempt_signature(),
             "perf": (_cm.perf_signature(e._perf.report())
                      if e._perf is not None else None)}
            for e in fleet.engines],
        "decisions": fleet.router.metrics()["aggregate"]["control_plane"][
            "decisions"],
    }
    blob = json.dumps(body, sort_keys=True, separators=(",", ":"),
                      default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


def fleet_load_spec(requests: int, *, seed_gap: float = 0.13,
                    replicas: int = 16,
                    num_slots: int = 8) -> _loadgen.LoadSpec:
    """The heavy-tail scale scenario: Zipf prompt/output lengths (many
    short requests, a long tail out to 8x the median), Poisson arrivals
    tuned just under the fleet's token service rate so queues stay
    loaded but bounded, and a Zipf tenant mix sharing prompt prefixes
    (the prefix trie sees realistic hit rates at scale)."""
    # service ~= replicas*num_slots tokens per fleet tick; the mean
    # Zipf output is ~14 tokens, so gap = 0.13 ticks lands near 85%
    # decode utilization before prefill waves claim their ticks
    gap = seed_gap * (16 * 8) / max(1, replicas * num_slots)
    return _loadgen.LoadSpec(
        n_requests=int(requests), vocab=256,
        arrival="poisson", mean_gap=gap,
        prompt_dist="zipf", prompt_buckets=(8, 16, 32, 64, 224),
        prompt_zipf_a=1.1, prompt_max=224,
        output_dist="zipf", output_buckets=(4, 8, 16, 32, 64),
        output_zipf_a=1.1, output_max=64,
        tenants=8, tenant_zipf_a=1.2, shared_prefix_len=8)


def run_fleet(*, requests: int = 100_000, replicas: int = 16,
              num_slots: int = 8, max_length: int = 512,
              admission: str = "predictive", policy: str = "least_loaded",
              preempt: str = "off", host_blocks: int = 0,
              seed: int = 0, spec: Optional[SimSpec] = None,
              profile: str = "v5e",
              max_ticks: Optional[int] = None) -> Dict[str, Any]:
    """One deterministic fleet replay of the heavy-tail scenario.
    Returns the loadgen replay report plus the fleet report and the
    run's :func:`fleet_signature`.  Flags are scoped to the run and
    restored on exit."""
    saved = {k: _flags.flag(k) for k in
             ("serving_admission", "perf_model", "request_log_max_requests",
              "serving_chunked_prefill")}
    # keep the scale run's memory bounded: the rolling request-log
    # window covers the trace tail, plenty for the structural signature
    _flags.set_flags({
        "serving_admission": admission,
        "perf_model": "on",
        "serving_chunked_prefill": False,
        "request_log_max_requests": min(8192, max(4096, requests // 8))})
    tracer = _obs.get_tracer()
    saved_trace = tracer.enabled
    # span tracing at 100k-request scale is pure host overhead (the
    # run's artifact is the fleet signature, not a trace); the request
    # log keeps its structural timeline either way
    tracer.enabled = False
    try:
        fleet = FleetSim(replicas, spec, policy=policy, seed=seed,
                         num_slots=num_slots, max_length=max_length,
                         preempt=preempt, host_blocks=host_blocks,
                         profile=_cm.PROFILES[profile])
        load = _loadgen.generate_load(
            fleet_load_spec(requests, replicas=replicas,
                            num_slots=num_slots), seed=seed)
        t0 = time.perf_counter()
        rep = _loadgen.replay(fleet, load, max_ticks=max_ticks)
        wall = time.perf_counter() - t0
        out = {
            "requests": requests,
            "replicas": replicas,
            "admission": admission,
            "ticks": rep["ticks"],
            "generated_tokens": rep["generated_tokens"],
            "rejected": rep["rejected"],
            "host_wall_s": round(wall, 3),
            "sim_wall_s": round(fleet.sim_wall_s, 3),
            "sim_tok_per_s": round(
                rep["generated_tokens"] / max(fleet.sim_wall_s, 1e-9), 3),
            "goodput": rep["slo"].get("goodput"),
            # federated attribution under simulated clocks (ISSUE 19):
            # the same slo_report by_worker join the multihost plane
            # uses, re-keyed onto run-stable replica names
            "slo_by_worker": fleet.slo_by_worker(rep["slo"]),
            "worker_clocks_ms": fleet.worker_clocks(),
            "fleet": fleet.report(),
            "signature": fleet_signature(fleet, rep),
        }
        return out
    finally:
        tracer.enabled = saved_trace
        _flags.set_flags(saved)


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="device-free serving fleet simulator (cost-model "
                    "clock; see module docstring)")
    p.add_argument("--requests", type=int, default=100_000)
    p.add_argument("--replicas", type=int, default=16)
    p.add_argument("--num-slots", type=int, default=8)
    p.add_argument("--max-length", type=int, default=512)
    p.add_argument("--admission", default="predictive",
                   choices=("queue_depth", "predictive"))
    p.add_argument("--policy", default="least_loaded",
                   choices=("prefix", "least_loaded", "round_robin"))
    p.add_argument("--preempt", default="off",
                   choices=("off", "swap", "recompute"))
    p.add_argument("--host-blocks", type=int, default=0)
    p.add_argument("--profile", default="v5e",
                   choices=sorted(_cm.PROFILES),
                   help="roofline profile the simulated replicas run "
                        "on (the sim clock's time domain)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--runs", type=int, default=2,
                   help="replays to run; >1 gates byte-stable "
                        "signatures across runs")
    args = p.parse_args(argv)
    sigs: List[str] = []
    for run in range(max(1, args.runs)):
        rep = run_fleet(requests=args.requests, replicas=args.replicas,
                        num_slots=args.num_slots,
                        max_length=args.max_length,
                        admission=args.admission, policy=args.policy,
                        preempt=args.preempt, profile=args.profile,
                        host_blocks=args.host_blocks, seed=args.seed)
        sigs.append(rep["signature"])
        slim = {k: v for k, v in rep.items() if k != "fleet"}
        print(json.dumps({"run": run, **slim}, indent=2, default=str))
    if len(set(sigs)) != 1:
        print("FLEET SIM NON-DETERMINISTIC: signatures differ across "
              "identical-seed runs")
        return 1
    print(f"signature stable across {len(sigs)} run(s): {sigs[0][:16]}…")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
