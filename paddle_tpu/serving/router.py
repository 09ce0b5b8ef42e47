"""Data-parallel replica router — N ServingEngines behind one submit().

The horizontal half of ROADMAP item 1's mesh-sharded serving: the
tensor-parallel engine step (``ServingEngine(mesh=...)``) makes ONE
model instance span chips; this router scales *throughput* by running N
independent engine replicas — each with its own KV cache / block pool /
scheduler, optionally each mesh-sharded — and placing requests across
them.  Aggregate tok/s is the sum of per-replica committed tokens
(BASELINE.md multi-replica accounting), and the placement policy is
what keeps that sum high:

  * **prefix-affinity** (default, ``policy="prefix"``): paged
    replicas expose a READ-ONLY trie probe
    (:meth:`~paddle_tpu.serving.kv_cache.BlockManager.prefix_probe`);
    the router sends a prompt to the replica holding its longest
    already-cached full-block prefix — a shared system prompt is
    computed once on ONE replica and every later tenant request lands
    on the warm trie instead of recomputing it cold elsewhere.  With no
    full-block match anywhere (cold start, empty trie, contiguous
    engines) placement falls back to **least-loaded** — queue depth +
    pending prefill chunks (the BASELINE.md capacity signal) + busy
    slots;
  * **session affinity** overrides every policy: the first request of a
    ``session`` pins the session to its replica and every later request
    reuses it, so a conversation's decode (and its incremental prefix
    blocks) never migrates — even across chunked-prefill ticks while an
    earlier turn is still streaming in;
  * **failover**: ``submit()`` tries replicas in placement order — a
    replica whose admission rejects the request outright (pool too
    small for the worst case) is skipped and the next candidate takes
    it, counted in ``router.submit_failovers``.  Only when EVERY
    replica rejects does the error propagate.

**Predictive admission** (control plane, FLAGS_serving_admission
``'predictive'``): before placing, each candidate is priced against its
cost model (:func:`~paddle_tpu.serving.admission.place_verdict` over
:meth:`~paddle_tpu.serving.engine.ServingEngine.admission_probe`) —
"would this placement blow the pooled TPOT/TTFT SLO?".  The first
candidate that fits takes the request; when NONE fits, the request is
parked in a priced :class:`~paddle_tpu.serving.admission.HoldQueue`
instead of being blindly rejected, and ``step()`` retries placement
each tick (priority classes outrank pricing; entries older than
FLAGS_serving_admission_max_defer_ticks are force-placed — the queue
never starves).  The gate degrades to today's reactive policy whenever
FLAGS_perf_model is off or any live replica's model carries a drift
finding.  Decisions land in ``router.admission_decision{verdict=
admit|defer|reject}`` counters and ``router.predicted_tpot_ms``
per-replica gauges on the shared /metrics registry.

**Elasticity** (the autoscaler's surface): :meth:`add_replica` grows
the fleet mid-flight, :meth:`drain_replica` excludes a replica from
new placements (pinned sessions keep landing — sessions never
migrate), and :meth:`retire_replica` removes an EMPTY drained replica
from the tick loop (its index stays allocated so router rids remain
stable; session pins to it are dropped and re-pin cold).

Scheduling is a round-robin tick loop: ``step()`` services the hold
queue, then ticks every live replica once (an idle replica's tick
returns immediately without device work), ``drain()`` loops until all
replicas are empty AND the hold queue is drained.  There are no router
threads — on TPU each replica's step is an async dispatch, so one host
thread keeps N devices busy; the loop form also keeps tests and traces
deterministic.

Telemetry rides the shared registry with per-replica labels
(``router.requests{replica=..., route=...}``); :meth:`metrics` returns
the per-replica engine snapshots plus the pooled aggregates (summed
tokens, pooled prefix hit rate) the bench rows commit.
"""

from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from .. import flags as _flags
from .. import observability as _obs
from .admission import HoldQueue, place_verdict
from .engine import SamplingParams, ServingEngine

__all__ = ["ReplicaRouter"]

_ROUTER_IDS = itertools.count()


class ReplicaRouter:
    """N data-parallel ServingEngine replicas behind one ``submit()``.

    ``ReplicaRouter(model, num_replicas=4)`` builds the replicas (the
    model's host-side params are shared; each replica owns its cache
    and scheduler; ``engine_kwargs`` — ``paged``, ``chunked``,
    ``mesh``, ... — are forwarded to every one).  Pass ``engines=[...]``
    instead to route over pre-built, possibly heterogeneous engines.
    """

    def __init__(self, model=None, num_replicas: int = 1,
                 *, engines: Optional[List[ServingEngine]] = None,
                 policy: str = "prefix", **engine_kwargs):
        self.policy = str(policy)
        if self.policy not in ("prefix", "least_loaded", "round_robin"):
            raise ValueError(
                f"policy must be 'prefix', 'least_loaded' or "
                f"'round_robin', got {self.policy!r}")
        self._factory: Optional[Callable[[], ServingEngine]] = None
        if engines is not None:
            if model is not None or engine_kwargs:
                raise ValueError(
                    "pass either engines=[...] or a model (+kwargs), "
                    "not both")
            self.engines = list(engines)
        else:
            if model is None:
                raise ValueError("a model (or engines=[...]) is required")
            n = int(num_replicas)
            if n < 1:
                raise ValueError(f"num_replicas must be >= 1, got {n}")
            self._factory = lambda: ServingEngine(model, **engine_kwargs)
            self.engines = [self._factory() for _ in range(n)]
        if not self.engines:
            raise ValueError("at least one replica is required")
        self._rid = itertools.count()
        # router rid -> (replica index, engine rid); _order is arrival
        # order (drain() returns it — held requests keep their arrival
        # slot even though they enter _placed late)
        self._placed: Dict[int, Tuple[int, int]] = {}
        self._order: List[int] = []
        # replica index -> {engine rid -> router rid}: the O(1) reverse
        # map step() resolves finished ids through (the fleet simulator
        # replays 100k+ requests — a linear scan of _placed per tick
        # would be quadratic in trace length)
        self._by_engine: Dict[int, Dict[int, int]] = {
            i: {} for i in range(len(self.engines))}
        self._affinity: Dict[object, int] = {}      # session -> replica
        self._rr = 0                                # round-robin cursor
        # control plane: the priced deferral queue + elastic state
        self._hold = HoldQueue()
        self._draining: Set[int] = set()
        self._retired: Set[int] = set()
        reg = _obs.default_registry()
        self._router_id = str(next(_ROUTER_IDS))
        self._rlog = _obs.get_request_log()
        self._uids: Dict[int, int] = {}     # router rid -> lifecycle uid
        lbl = {"router": self._router_id}
        self._m_requests = reg.counter(
            "router.requests",
            "requests placed, by replica and route (prefix = warm-trie "
            "match, affinity = session pin, least_loaded / round_robin "
            "= the fallbacks)")
        self._m_failovers = reg.counter(
            "router.submit_failovers",
            "submissions retried on another replica after the chosen "
            "one rejected admission outright").labels(**lbl)
        self._m_prefix_tokens = reg.counter(
            "router.prefix_routed_tokens",
            "prompt tokens the placement probe found already cached on "
            "the chosen replica at submit time").labels(**lbl)
        self._f_admission = reg.counter(
            "router.admission_decision",
            "control-plane placement decisions by verdict: admit (a "
            "replica took the request), defer (every candidate priced "
            "over the SLO — parked in the hold queue), reject (a "
            "replica's admission refused outright)")
        self._f_pred_tpot = reg.gauge(
            "router.predicted_tpot_ms",
            "last cost-model predicted post-admission TPOT per replica "
            "(calibrated wall ms), refreshed at every predictive "
            "placement probe")
        self._g_held = reg.gauge(
            "router.held_requests",
            "requests currently parked in the predictive hold "
            "queue").labels(**lbl)

    # -- placement ---------------------------------------------------------

    @property
    def num_replicas(self) -> int:
        return len(self.engines)

    @property
    def live_replicas(self) -> List[int]:
        """Indices still in the tick loop (not retired)."""
        return [i for i in range(len(self.engines))
                if i not in self._retired]

    @property
    def pending_held(self) -> int:
        """Requests parked in the predictive hold queue — loadgen's
        ``busy()`` must count these or replay would stop early."""
        return len(self._hold)

    @staticmethod
    def _load(eng: ServingEngine) -> Tuple[int, int]:
        """Replica load for the least-loaded fallback: queued requests
        plus pending prefill chunks (the BASELINE.md capacity signal)
        first, busy slots as the tie-breaker."""
        return (eng.queue_depth + eng.num_pending + eng.pending_chunks,
                eng.num_active)

    def _probe(self, eng: ServingEngine, prompt: np.ndarray) -> int:
        """Cached prefix tokens ``eng`` already holds for ``prompt``
        (0 for contiguous / prefix-cache-off replicas)."""
        if not eng.paged:
            return 0
        return int(eng.kv.prefix_probe(prompt))

    def _placement_order(self, prompt: np.ndarray,
                         session) -> List[Tuple[int, str, int]]:
        """Candidate replicas, best first, as ``(index, route, warm)``
        triples.  Failover walks this list in order.  Retired replicas
        never appear; draining replicas only appear for their pinned
        sessions (sessions never migrate, but no NEW work lands)."""
        idx = [i for i in range(len(self.engines))
               if i not in self._retired and i not in self._draining]
        if session is not None and session in self._affinity:
            pin = self._affinity[session]
            if pin in self._retired:
                # the pinned replica is gone — drop the pin, the
                # session re-pins cold on whatever takes this request
                del self._affinity[session]
            else:
                # the session's replica first (draining or not); the
                # rest by load as failover
                rest = sorted((i for i in idx if i != pin),
                              key=lambda i: self._load(self.engines[i]))
                return ([(pin, "affinity",
                          self._probe(self.engines[pin], prompt))]
                        + [(i, "least_loaded", 0) for i in rest])
        if not idx:
            # every live replica is draining: placement must still make
            # progress (the autoscaler never drains the whole fleet,
            # but a user can) — fall back to the live set
            idx = self.live_replicas
        if self.policy == "round_robin":
            r = self._rr % len(idx)
            order = idx[r:] + idx[:r]
            self._rr = (self._rr + 1) % len(idx)
            return [(i, "round_robin", 0) for i in order]
        loads = {i: self._load(self.engines[i]) for i in idx}
        by_load = sorted(idx, key=lambda i: loads[i])
        if self.policy == "least_loaded":
            return [(i, "least_loaded", 0) for i in by_load]
        # prefix policy: longest warm trie match wins (load breaks
        # ties); replicas with no full-block match rank by load behind
        # every warm one — the empty-trie cold start degenerates to
        # pure least-loaded
        warm = {i: self._probe(self.engines[i], prompt) for i in idx}
        order = sorted(idx, key=lambda i: (-warm[i], loads[i]))
        return [(i, "prefix" if warm[i] else "least_loaded", warm[i])
                for i in order]

    def _predictive_armed(self) -> bool:
        """The control-plane gate arms only when EVERY live replica's
        model is trustworthy: one drifting replica means predictions
        can no longer rank candidates — fall back conservative."""
        if str(_flags.flag("serving_admission")) != "predictive":
            return False
        live = [self.engines[i] for i in self.live_replicas]
        return bool(live) and all(e.admission_armed() for e in live)

    def _register(self, i: int, route: str, warm: int, session,
                  uid: int, erid: int, rid: Optional[int] = None) -> int:
        """Book one successful placement (fresh or from the hold
        queue): rid maps, reverse map, lifecycle event, telemetry."""
        if rid is None:
            rid = next(self._rid)
            self._order.append(rid)
        self._placed[rid] = (i, erid)
        self._by_engine[i][erid] = rid
        self._uids[rid] = uid
        self._rlog.event(uid, "placed", router=self._router_id,
                         replica=str(i), route=route,
                         warm_tokens=int(warm))
        if session is not None:
            self._affinity.setdefault(session, i)
        self._m_requests.labels(router=self._router_id,
                                replica=str(i), route=route).inc()
        if warm:
            self._m_prefix_tokens.inc(int(warm))
        self._f_admission.labels(router=self._router_id,
                                 verdict="admit").inc()
        return rid

    def _try_place(self, prompt: np.ndarray, max_new_tokens: int,
                   sampling: Optional[SamplingParams], session,
                   priority: int, uid: int, *,
                   slo_ttft: float, slo_tpot: float,
                   rid: Optional[int] = None,
                   gate: bool = True) -> Tuple[Optional[int],
                                               Optional[Exception],
                                               float, int]:
        """One walk of the placement order.  With ``gate`` (and the
        control plane armed) each candidate is priced first and
        over-SLO candidates are skipped.  ``slo_ttft`` / ``slo_tpot``
        are the request's deadlines captured at ROUTER submit — they
        price the placement AND stamp the engine-side request, so a
        hold-queue retry ticks later still carries the class deadlines
        it arrived with.  Returns ``(rid, last_err, hold_price,
        deferrals)`` — rid None means nothing placed."""
        armed = gate and self._predictive_armed()
        last_err: Optional[Exception] = None
        price = 0.0
        deferrals = 0
        for i, route, warm in self._placement_order(prompt, session):
            if armed:
                v = place_verdict(self.engines[i], int(prompt.size),
                                  ttft_slo_ms=slo_ttft,
                                  tpot_slo_ms=slo_tpot)
                self._f_pred_tpot.labels(
                    router=self._router_id,
                    replica=str(i)).set(v.predicted_tpot_ms)
                if v.verdict != "admit":
                    deferrals += 1
                    price = min(price, v.price) if deferrals > 1 \
                        else v.price
                    continue
            try:
                erid = self.engines[i].submit(
                    prompt, max_new_tokens=max_new_tokens,
                    sampling=sampling, request_uid=uid,
                    priority=priority, ttft_slo_ms=slo_ttft,
                    tpot_slo_ms=slo_tpot)
            except ValueError as e:
                # admission rejected the request outright (e.g. the
                # replica's pool cannot cover its worst case) — the
                # failover clause: try the next candidate
                last_err = e
                self._m_failovers.inc()
                self._f_admission.labels(router=self._router_id,
                                         verdict="reject").inc()
                continue
            return (self._register(i, route, warm, session, uid, erid,
                                   rid=rid), None, 0.0, deferrals)
        return (None, last_err, price, deferrals)

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               sampling: Optional[SamplingParams] = None,
               session=None, priority: int = 0) -> int:
        """Place and enqueue a request; returns the ROUTER request id.
        ``session`` (any hashable) pins this and every later request of
        the session to one replica — decode never migrates.
        ``priority`` rides through to the replica's preemptive scheduler
        (higher wins a victim slot under saturation) AND through the
        predictive hold queue (priority classes outrank pricing).

        Under predictive admission a request every candidate prices
        over the SLO is PARKED, not rejected: the returned rid is
        valid immediately, placement happens on a later ``step()``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        # the lifecycle uid is minted HERE, before placement, and the
        # same uid rides through every replica attempt — on failover the
        # rejecting replica's "rejected" and the accepting replica's
        # "admitted" land on one timeline
        uid = self._rlog.new_uid()
        slo_ttft = float(_flags.flag("serving_slo_ttft_ms"))
        slo_tpot = float(_flags.flag("serving_slo_tpot_ms"))
        self._rlog.event(
            uid, "submitted", router=self._router_id,
            prompt_len=int(prompt.size),
            max_new_tokens=int(max_new_tokens),
            ttft_slo_ms=slo_ttft, tpot_slo_ms=slo_tpot)
        rid, last_err, price, deferrals = self._try_place(
            prompt, max_new_tokens, sampling, session, priority, uid,
            slo_ttft=slo_ttft, slo_tpot=slo_tpot)
        if rid is not None:
            return rid
        if deferrals == 0:
            # every candidate REJECTED (infeasible everywhere) — the
            # legacy contract: propagate, nothing to hold
            raise last_err if last_err is not None else RuntimeError(
                "no replica accepted the request")
        # at least one candidate merely priced over the SLO: park it
        rid = next(self._rid)
        self._order.append(rid)
        self._uids[rid] = uid
        self._hold.push(
            {"rid": rid, "uid": uid, "prompt": prompt,
             "max_new_tokens": int(max_new_tokens), "sampling": sampling,
             "session": session, "priority": int(priority),
             "slo_ttft": slo_ttft, "slo_tpot": slo_tpot},
            priority=priority, price=price)
        self._g_held.set(len(self._hold))
        self._f_admission.labels(router=self._router_id,
                                 verdict="defer").inc()
        self._rlog.event(uid, "held", router=self._router_id,
                         price_ms=round(price, 6),
                         priority=int(priority))
        return rid

    def _service_hold(self) -> None:
        """Retry placement for every held request, best-first (aged →
        priority → price → arrival).  Aged entries bypass the gate —
        the starvation bound force-places at the legacy best candidate.
        Entries that still do not fit are re-priced in place."""
        if not len(self._hold):
            return
        for e in self._hold.ordered():
            p = e.payload
            rid, _, price, deferrals = self._try_place(
                p["prompt"], p["max_new_tokens"], p["sampling"],
                p["session"], p["priority"], p["uid"], rid=p["rid"],
                slo_ttft=p["slo_ttft"], slo_tpot=p["slo_tpot"],
                gate=not self._hold.aged(e))
            if rid is not None:
                self._hold.remove(e)
            elif deferrals:
                e.price = price
            else:
                # zero deferrals and nothing placed: every live replica
                # rejected outright.  Engine-side rejection is STATIC
                # infeasibility (prompt past max_length, pool too small
                # for the worst case) — retrying forever would wedge
                # drain().  Surface the same terminal verdict submit()
                # would have raised, as a lifecycle event
                self._hold.remove(e)
                self._order.remove(p["rid"])
                self._rlog.event(p["uid"], "rejected",
                                 router=self._router_id, stage="held")
                self._f_admission.labels(router=self._router_id,
                                         verdict="reject").inc()
        self._g_held.set(len(self._hold))

    def request_uid(self, rid: int) -> int:
        """The lifecycle uid behind router request ``rid`` — one key
        into the request log across every replica the request touched."""
        return self._uids[rid]

    def cancel(self, rid: int) -> bool:
        """Cancel router request ``rid`` wherever its replica holds it
        (held pre-placement, queued, mid-prefill, decoding, or awaiting
        resume after a preemption).  Delegates to the owning replica's
        :meth:`ServingEngine.cancel`; returns ``False`` once the
        request already finished (its tokens stay retrievable via
        :meth:`result`)."""
        if rid not in self._placed:
            for e in self._hold:
                if e.payload["rid"] == rid:
                    self._hold.remove(e)
                    self._order.remove(rid)
                    self._rlog.event(self._uids[rid], "cancelled",
                                     router=self._router_id,
                                     stage="held")
                    self._g_held.set(len(self._hold))
                    return True
            raise KeyError(f"unknown router request id {rid}")
        i, erid = self._placed[rid]
        return self.engines[i].cancel(erid)

    # -- elasticity (the autoscaler's surface) -----------------------------

    def add_replica(self,
                    engine: Optional[ServingEngine] = None) -> int:
        """Grow the fleet by one replica mid-flight; returns its index.
        Routers built from a model construct the engine themselves;
        routers built over pre-built engines must be handed one."""
        if engine is None:
            if self._factory is None:
                raise ValueError(
                    "router was built over pre-built engines — pass "
                    "engine= to add_replica")
            engine = self._factory()
        i = len(self.engines)
        self.engines.append(engine)
        self._by_engine[i] = {}
        self._rlog.event(self._rlog.new_uid(), "replica_added",
                         router=self._router_id, replica=str(i))
        return i

    def drain_replica(self, i: int) -> None:
        """Exclude replica ``i`` from NEW placements.  Its queue keeps
        draining and pinned sessions keep landing (sessions never
        migrate); once empty it can be retired."""
        if i in self._retired or not 0 <= i < len(self.engines):
            raise ValueError(f"replica {i} is not live")
        self._draining.add(i)

    def undrain_replica(self, i: int) -> None:
        """Return a draining (not yet retired) replica to service."""
        if i in self._retired:
            raise ValueError(f"replica {i} is already retired")
        self._draining.discard(i)

    def replica_empty(self, i: int) -> bool:
        eng = self.engines[i]
        return not (eng.queue_depth or eng.num_active or eng.num_pending
                    or eng.num_preempted)

    def retire_replica(self, i: int) -> None:
        """Remove an EMPTY replica from the tick loop.  Indices stay
        allocated (router rids remain stable); session pins to the
        retired replica are dropped and re-pin cold on their next
        request.  Raises if the replica still holds work — drain
        first, retire only when empty (sessions never migrate)."""
        if i in self._retired:
            return
        if not 0 <= i < len(self.engines):
            raise ValueError(f"replica {i} does not exist")
        if not self.replica_empty(i):
            raise RuntimeError(
                f"replica {i} still holds work — drain_replica() and "
                f"tick until empty before retiring")
        if len(self.live_replicas) <= 1:
            raise RuntimeError("cannot retire the last live replica")
        self._retired.add(i)
        self._draining.discard(i)
        for s in [s for s, ri in self._affinity.items() if ri == i]:
            del self._affinity[s]
        self._rlog.event(self._rlog.new_uid(), "replica_retired",
                         router=self._router_id, replica=str(i))

    # -- scheduling --------------------------------------------------------

    def step(self) -> List[int]:
        """One round-robin tick: service the hold queue, then tick
        every live replica (idle replicas return immediately).  Returns
        router rids finished this tick."""
        self._service_hold()
        finished: List[int] = []
        for i, eng in enumerate(self.engines):
            if i in self._retired:
                continue
            done = eng.step()
            if done:
                emap = self._by_engine[i]
                finished.extend(sorted(
                    emap.pop(erid) for erid in done if erid in emap))
        if len(self._hold):
            self._hold.tick()
        return finished

    def drain(self) -> List[Tuple[int, List[int]]]:
        """Tick until every live replica is empty and the hold queue
        has drained; returns ``[(router_rid, tokens)]`` in arrival
        order."""
        while (len(self._hold)
               or any(not self.replica_empty(i)
                      for i in self.live_replicas)):
            self.step()
        return [(rid, self.result(rid)) for rid in self._order]

    def result(self, rid: int) -> List[int]:
        i, erid = self._placed[rid]
        return self.engines[i].result(erid)

    def replica_of(self, rid: int) -> int:
        """Which replica serves router request ``rid`` (affinity probes
        in tests; a session's requests all map to one value)."""
        return self._placed[rid][0]

    # -- telemetry ---------------------------------------------------------

    def metrics(self) -> Dict[str, object]:
        """Per-replica engine snapshots plus the pooled aggregates
        (BASELINE.md multi-replica accounting): aggregate tok/s derives
        from ``tokens_generated`` summed over replicas; the pooled
        prefix hit rate re-divides summed hit tokens by summed admitted
        prompt tokens (NOT the mean of per-replica rates)."""
        per = [eng.metrics() for eng in self.engines]
        agg: Dict[str, object] = {
            "replicas": len(self.engines),
            "policy": self.policy,
            "tokens_generated": sum(m["tokens_generated"] for m in per),
            "requests_submitted": sum(m["requests_submitted"]
                                      for m in per),
            "requests_finished": sum(m["requests_finished"] for m in per),
            "submit_failovers": int(self._m_failovers.value()),
            "prefix_routed_tokens": int(self._m_prefix_tokens.value())}
        agg["control_plane"] = {
            "admission": str(_flags.flag("serving_admission")),
            "predictive_armed": self._predictive_armed(),
            "held_requests": len(self._hold),
            "draining": sorted(self._draining),
            "retired": sorted(self._retired),
            "live_replicas": len(self.live_replicas),
            "decisions": {
                str(c.labels["verdict"]): int(c.value())
                for c in self._f_admission.children()
                if c.labels.get("router") == self._router_id}}
        if all(eng.paged for eng in self.engines):
            hits = sum(eng.kv.stats["prefix_hit_tokens"]
                       for eng in self.engines)
            total = sum(eng.prefill_tokens_total for eng in self.engines)
            agg["prefix_hit_rate_pooled"] = (round(hits / total, 3)
                                             if total else 0.0)
            agg["prefix_hit_rate_per_replica"] = [
                m["kv_cache"]["prefix_hit_rate"] for m in per]
        return {"aggregate": agg, "per_replica": per}
