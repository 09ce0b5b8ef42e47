"""Per-request lifecycle tracing (paddle_tpu/observability/request_log).

Two layers under test: the RequestLog store itself (timelines, mark
bracketing, structural signatures, Perfetto per-request tracks, the
bounded ring, the SLO goodput join with its violation-cause
attribution), and the serving integration — a uid minted at submit()
must thread engine → slot (and router → replica on failover) so every
lifecycle event of one request, on whichever replica served it, lands
on one correlated timeline in the asserted order.
"""

import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags as fl
from paddle_tpu import observability as obs
from paddle_tpu.observability import RequestLog

MAXLEN = 128


# -- RequestLog store --------------------------------------------------------

def test_event_timeline_order_and_mark_bracketing():
    log = RequestLog(max_requests=16)
    u1 = log.new_uid()
    log.event(u1, "submitted", prompt_len=4)
    log.event(u1, "admitted", slot=0)
    mark = log.mark()
    u2 = log.new_uid()
    log.event(u2, "submitted", prompt_len=8)
    end = log.mark()
    u3 = log.new_uid()
    log.event(u3, "submitted", prompt_len=2)
    assert log.event_names(u1) == ["submitted", "admitted"]
    # (mark, end] brackets exactly the middle request
    recs = log.records(since_uid=mark, until_uid=end)
    assert list(recs) == [u2]
    assert len(log.records()) == 3
    tl = log.timeline(u1)
    assert tl[0]["attrs"] == {"prompt_len": 4}
    assert tl[0]["t_ms"] <= tl[1]["t_ms"]


def test_signature_strips_ids_and_timings():
    """Two runs that differ only in per-process ids and wall-clock
    measurements must sign identically; a structural difference (an
    extra event, a changed token count) must not."""
    def run(engine_id, qw):
        log = RequestLog(max_requests=8)
        u = log.new_uid()
        log.event(u, "submitted", engine=engine_id, prompt_len=4)
        log.event(u, "admitted", engine=engine_id, slot=1,
                  queue_wait_ms=qw)
        log.event(u, "retired", engine=engine_id, reason="eos", tokens=3,
                  violation="none")
        return log.timeline_signature()

    assert run("0", 1.25) == run("7", 99.0)
    other = RequestLog(max_requests=8)
    u = other.new_uid()
    other.event(u, "submitted", engine="0", prompt_len=4)
    other.event(u, "admitted", engine="0", slot=1, queue_wait_ms=1.25)
    other.event(u, "retired", engine="0", reason="eos", tokens=4,
                violation="none")
    assert other.timeline_signature() != run("0", 1.25)


def test_events_stay_out_of_the_span_ring_and_share_its_clock():
    """A request event is stored once, in the log (it used to be copied
    into the span ring as a ``request.*`` instant nobody read); the log's
    ``t_ms`` and a span's ``ts`` run on one origin, so the two timelines
    line up by conversion, not by copying."""
    log = obs.get_request_log()
    u = log.new_uid()
    with obs.span("around"):
        log.event(u, "submitted", prompt_len=4)
    evs = obs.get_tracer().events()
    assert not [e for e in evs if e["name"].startswith("request.")]
    sp = [e for e in evs if e["name"] == "around"][-1]
    t_ev = obs.clock.event_ms_to_perf_counter(log.timeline(u)[0]["t_ms"])
    t0 = obs.clock.span_ts_to_perf_counter(sp["ts"])
    t1 = obs.clock.span_ts_to_perf_counter(sp["ts"] + sp["dur"])
    assert t0 <= t_ev <= t1


def test_bounded_store_drops_oldest_whole_requests():
    log = RequestLog(max_requests=3)
    uids = []
    for _ in range(5):
        u = log.new_uid()
        uids.append(u)
        log.event(u, "submitted")
        log.event(u, "retired")
    assert log.dropped == 2
    assert list(log.records()) == uids[2:]      # oldest evicted first
    assert log.event_names(uids[0]) == []


def test_perfetto_export_one_named_track_per_request(tmp_path):
    log = RequestLog(max_requests=8)
    for _ in range(2):
        u = log.new_uid()
        log.event(u, "submitted", prompt_len=4)
        log.event(u, "admitted", slot=0)
        log.event(u, "first_token", ttft_ms=1.0)
        log.event(u, "retired", reason="eos", tokens=3)
    path = tmp_path / "requests.json"
    trace = log.export_perfetto(str(path))
    with open(path) as f:
        assert json.load(f)["traceEvents"]       # valid JSON on disk
    evs = trace["traceEvents"]
    tracks = {e["tid"]: e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    uids = sorted(log.records())
    assert tracks == {u: f"request {u}" for u in uids}
    for u in uids:
        mine = [e for e in evs if e["ph"] != "M" and e["tid"] == u]
        names = [e["name"] for e in mine]
        assert names[:4] == ["submitted", "admitted", "first_token",
                             "retired"]
        # phase slices reconstructed from the instants
        slices = {e["name"]: e for e in mine if e["ph"] == "X"}
        assert set(slices) == {"queued", "prefill", "decode"}
        assert slices["queued"]["ts"] + slices["queued"]["dur"] <= \
            slices["prefill"]["ts"] + 1e-6


# -- SLO goodput join --------------------------------------------------------

def _timeline(log, *, qw=1.0, ttft=2.0, tpot=1.0, tokens=5,
              slo=(0.0, 0.0), reject=False, retire=True):
    u = log.new_uid()
    log.event(u, "submitted", prompt_len=4, max_new_tokens=tokens,
              ttft_slo_ms=slo[0], tpot_slo_ms=slo[1])
    if reject:
        log.event(u, "rejected", reason="too_long")
        return u
    log.event(u, "admitted", slot=0, queue_wait_ms=qw)
    log.event(u, "first_token", ttft_ms=ttft)
    if retire:
        log.event(u, "retired", reason="eos", tokens=tokens,
                  ttft_ms=ttft, tpot_ms=tpot, violation="none")
    return u


def test_slo_report_attained_and_goodput_tok_s():
    log = RequestLog(max_requests=16)
    for _ in range(4):
        _timeline(log, ttft=2.0, tpot=1.0, tokens=5, slo=(10.0, 5.0))
    rep = log.slo_report(wall_s=2.0)
    assert rep["requests"] == rep["attained"] == 4
    assert rep["goodput"] == 1.0
    assert rep["attained_tokens"] == 20
    assert rep["goodput_tok_s"] == 10.0
    assert rep["targets_ms"] == {"ttft": 10.0, "tpot": 5.0}
    assert all(v == 0 for v in rep["violations"].values())


def test_slo_violation_attribution_by_cause():
    """One cause per violating request: a missed TTFT splits by the
    larger segment (queue_wait vs prefill), a missed TPOT is decode,
    a rejection counts in the denominator, in-flight is incomplete."""
    log = RequestLog(max_requests=16)
    slo = (10.0, 5.0)
    _timeline(log, qw=9.0, ttft=12.0, slo=slo)            # queue-bound
    _timeline(log, qw=1.0, ttft=12.0, slo=slo)            # prefill-bound
    _timeline(log, ttft=2.0, tpot=50.0, slo=slo)          # decode-bound
    _timeline(log, reject=True, slo=slo)
    _timeline(log, retire=False, slo=slo)                 # still in flight
    _timeline(log, ttft=2.0, tpot=1.0, tokens=7, slo=slo)  # attained
    rep = log.slo_report()
    assert rep["requests"] == 6                # rejected included
    assert rep["violations"] == {"rejected": 1, "cancelled": 0,
                                 "queue_wait": 1, "prefill": 1,
                                 "decode": 1, "incomplete": 1}
    assert rep["attained"] == 1 and rep["goodput"] == round(1 / 6, 4)
    assert rep["attained_tokens"] == 7


def test_slo_report_explicit_targets_override_recorded():
    log = RequestLog(max_requests=16)
    # recorded with deadlines DISABLED: attained by default...
    _timeline(log, ttft=20.0, tpot=9.0, slo=(0.0, 0.0))
    assert log.slo_report()["attained"] == 1
    # ...but an explicit post-hoc ruler re-judges the same timelines
    rep = log.slo_report(ttft_ms=10.0, tpot_ms=5.0)
    assert rep["attained"] == 0
    assert rep["violations"]["prefill"] == 1
    assert rep["targets_ms"] == {"ttft": 10.0, "tpot": 5.0}


# -- serving integration -----------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    return model


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def test_chunked_engine_event_order_per_request(lm):
    """Staggered chunked trace: every request's timeline reads
    submitted → admitted → prefill_chunk+ → first_token → retired, with
    the chunk cursor strictly rising to the prompt length."""
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN,
                        chunked=True, prefill_chunk=8)
    rids = [eng.submit(_prompt(20, 1), max_new_tokens=3),
            eng.submit(_prompt(11, 2), max_new_tokens=4)]
    eng.step()
    rids.append(eng.submit(_prompt(5, 3), max_new_tokens=3))
    eng.drain()
    log = obs.get_request_log()
    for rid, plen in zip(rids, (20, 11, 5)):
        tl = log.timeline(eng.request_uid(rid))
        names = [e["name"] for e in tl]
        n_chunks = -(-plen // 8)
        assert names == (["submitted", "admitted"]
                         + ["prefill_chunk"] * n_chunks
                         + ["first_token", "retired"])
        cursors = [e["attrs"]["cursor"] for e in tl
                   if e["name"] == "prefill_chunk"]
        assert cursors == sorted(cursors) and cursors[-1] == plen
        sub = tl[0]["attrs"]
        assert sub["prompt_len"] == plen
        ret = tl[-1]["attrs"]
        assert ret["reason"] == "max_new_tokens"
        assert ret["tpot_ms"] is not None and ret["violation"] == "none"


def test_wave_engine_event_order_and_queue_wait(lm):
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN,
                        prefill_batch=2)
    # 3 requests into 2 slots: the third queues behind a full batch
    rids = [eng.submit(_prompt(8, s), max_new_tokens=3) for s in range(3)]
    eng.drain()
    log = obs.get_request_log()
    for rid in rids:
        tl = log.timeline(eng.request_uid(rid))
        assert [e["name"] for e in tl] == \
            ["submitted", "admitted", "prefill", "first_token", "retired"]
        adm = [e for e in tl if e["name"] == "admitted"][0]["attrs"]
        assert adm["queue_wait_ms"] >= 0.0
        ttfts = [e["attrs"]["ttft_ms"] for e in tl
                 if e["name"] == "first_token"]
        assert ttfts[0] >= adm["queue_wait_ms"]  # TTFT measured from submit


def test_rejected_admission_records_and_counts(lm):
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(lm, num_slots=2, max_length=32)
    log = obs.get_request_log()
    mark = log.mark()
    with pytest.raises(ValueError, match="exceeds the engine's"):
        eng.submit(_prompt(40, 0), max_new_tokens=4)
    recs = log.records(since_uid=mark)
    assert len(recs) == 1
    (tl,) = recs.values()
    assert [e["name"] for e in tl] == ["submitted", "rejected"]
    assert tl[1]["attrs"]["reason"] == "too_long"
    assert eng.metrics()["slo_violations"] == {"rejected": 1}
    rep = log.slo_report(since_uid=mark)
    assert rep["requests"] == 1 and rep["goodput"] == 0.0
    assert rep["violations"]["rejected"] == 1


def test_router_failover_carries_one_uid_across_replicas(lm):
    """A replica that rejects admission outright and the replica that
    then serves the request write to the SAME timeline: the uid is
    minted at the router and threaded through both submit attempts."""
    from paddle_tpu.serving import ReplicaRouter, ServingEngine

    router = ReplicaRouter(
        engines=[ServingEngine(lm, num_slots=2, max_length=32),
                 ServingEngine(lm, num_slots=2, max_length=MAXLEN)],
        policy="least_loaded")
    rid = router.submit(_prompt(40, 0), max_new_tokens=3)
    assert router.replica_of(rid) == 1
    router.drain()
    uid = router.request_uid(rid)
    tl = obs.get_request_log().timeline(uid)
    names = [e["name"] for e in tl]
    assert names == ["submitted", "rejected", "placed", "admitted",
                     "prefill", "first_token", "retired"]
    assert tl[0]["attrs"]["router"] == router._router_id
    assert tl[1]["attrs"]["reason"] == "too_long"
    assert tl[2]["attrs"]["replica"] == "1"
    # the rejecting and serving replicas are different engines, one uid
    assert tl[1]["attrs"]["engine"] != tl[3]["attrs"]["engine"]
    # the engine-side uid accessor agrees with the router-side one
    assert router.engines[1].request_uid(router._placed[rid][1]) == uid


def test_live_slo_flags_attribute_decode_violation(lm):
    """Deadlines from FLAGS at submit time: an impossibly tight TPOT
    target marks the retirement as a decode violation in both the
    lifecycle record and the serving.slo_violations counter."""
    from paddle_tpu.serving import ServingEngine

    old = (fl.flag("serving_slo_ttft_ms"), fl.flag("serving_slo_tpot_ms"))
    fl.set_flags({"serving_slo_ttft_ms": 1e9, "serving_slo_tpot_ms": 1e-6})
    try:
        eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
        rid = eng.submit(_prompt(8, 0), max_new_tokens=4)
        eng.drain()
    finally:
        fl.set_flags({"serving_slo_ttft_ms": old[0],
                      "serving_slo_tpot_ms": old[1]})
    log = obs.get_request_log()
    tl = log.timeline(eng.request_uid(rid))
    ret = tl[-1]["attrs"]
    assert ret["violation"] == "decode"
    assert eng.metrics()["slo_violations"] == {"decode": 1}
    rep = log.slo_report()
    assert rep["violations"]["decode"] == 1
    assert rep["targets_ms"] == {"ttft": 1e9, "tpot": 1e-6}
