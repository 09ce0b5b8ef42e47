"""Roofline cost model (paddle_tpu/observability/costmodel, ISSUE 15).

Pure host-math layer: hardware-profile resolution, the per-tick
prediction arithmetic against a hand-computable profile, the four bound
verdicts, depth-bucketed memoization, the dtype-aware per-token KV cost
(cross-checked against the committed int8 streamed-bytes ratio in
BENCH_DECODE.json), perf-signature determinism, and reset() isolation.
No engines, no compiles.
"""

import json

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import tiny_llama_config
from paddle_tpu.observability import costmodel as cm
from paddle_tpu.observability.metrics import MetricsRegistry



# -- profiles ----------------------------------------------------------------

def test_profiles_and_resolution():
    assert {"v5e", "cpu_smoke"} <= set(cm.PROFILES)
    v5e = cm.resolve_profile("v5e")
    assert v5e.peak_bf16_flops == 197e12
    assert v5e.hbm_bps == 819.0 * 1e9      # published, not the July chain
    # the test backend is CPU, so 'auto' (and the flag default) must
    # pick the smoke profile — tier-1 never pretends to be a v5e
    assert cm.resolve_profile("auto").name == "cpu_smoke"
    assert cm.resolve_profile().name == "cpu_smoke"
    with pytest.raises(ValueError, match="unknown hardware profile"):
        cm.resolve_profile("v9000")

    class Dev:                      # a jax device, as far as the table looks
        device_kind = "TPU v5 lite"

    assert cm.profile_for_device(Dev) is v5e
    Dev.device_kind = "TPU v9000"
    with pytest.raises(ValueError, match="no published peaks.*v9000"):
        cm.profile_for_device(Dev)


def test_profile_as_dict_round_trips():
    d = cm.PROFILES["v5e"].as_dict()
    assert d == {"name": "v5e", "peak_bf16_flops": 197e12,
                 "hbm_gbps": 819.0, "ici_gbps": 200.0,
                 "host_gbps": 16.0}
    assert cm.HardwareProfile(**d) == cm.PROFILES["v5e"]


# -- prediction arithmetic ---------------------------------------------------

def _model(**kw):
    """1 GB/s HBM + ICI, 1 GFLOP/s: every term is hand-computable."""
    prof = cm.HardwareProfile("unit", peak_bf16_flops=1e9,
                              hbm_gbps=1.0, ici_gbps=1.0)
    kw.setdefault("weight_bytes", 1_000_000)
    kw.setdefault("n_params", 1_000)
    kw.setdefault("kv_token_bytes", 100.0)
    kw.setdefault("num_slots", 4)
    return cm.CostModel(prof, **kw)


def test_predict_term_arithmetic():
    p = _model().predict(occ=4, live_tokens=64)
    # 1e6 bytes over 1 GB/s = 1.0 ms, streamed once per tick
    assert p["weight_stream_ms"] == pytest.approx(1.0)
    # KV scales with the (bucketed) live depth
    assert p["kv_stream_ms"] == pytest.approx(64 * 100.0 / 1e9 * 1e3)
    # dense decode GEMMs run over all num_slots rows (masked, not
    # skipped): 2*N FLOPs per row
    assert p["compute_ms"] == pytest.approx(2 * 1_000 * 4 / 1e9 * 1e3)
    assert p["comm_ms"] == 0.0                 # unmeshed
    # HBM terms share the stream: predicted = weight + kv
    assert p["predicted_ms"] == pytest.approx(
        p["weight_stream_ms"] + p["kv_stream_ms"])
    assert p["bound"] == "weight-stream"


def test_chunk_and_window_grow_the_compute_term():
    m = _model()
    base = m.predict(2, 16)["compute_ms"]
    chunked = m.predict(2, 16, chunk_tokens=32)["compute_ms"]
    spec = m.predict(2, 16, window=5)["compute_ms"]
    # chunk adds its prompt tokens; a spec window multiplies the rows
    assert chunked == pytest.approx(base * (4 + 32) / 4)
    assert spec == pytest.approx(base * 5)


def test_bound_verdicts_cover_all_four():
    assert _model().predict(1, 0)["bound"] == "weight-stream"
    assert _model(kv_token_bytes=1e6).predict(4, 1024)["bound"] \
        == "kv-stream"
    assert _model(n_params=10**9).predict(4, 16)["bound"] == "compute"
    big_comm = _model(comm_bytes_fn=lambda: 10**10)
    assert big_comm.predict(4, 16)["bound"] == "comm"
    assert big_comm.comm_bytes_per_step == 10**10


def test_comm_bytes_fn_is_lazy_and_memoized():
    calls = []
    m = _model(comm_bytes_fn=lambda: calls.append(1) or 4096)
    assert not calls                       # construction never traces
    m.predict(1, 8)
    m.predict(2, 8)
    assert calls == [1]                    # one comm_report, memoized
    m.clear()
    m.predict(1, 8)
    assert calls == [1, 1]                 # clear() re-arms the lazy fn


def test_depth_bucketing_and_memoization():
    m = _model()
    a = m.predict(2, 33)
    b = m.predict(2, 64)
    # 33 and 64 share the next-pow2 bucket: one memo entry, same dict
    assert a is b
    assert a["live_tokens_bucket"] == 64
    assert m.predict(2, 65)["live_tokens_bucket"] == 128
    assert m.memo_size() == 2
    m.clear()
    assert m.memo_size() == 0


# -- dtype-aware KV cost -----------------------------------------------------

def test_kv_bytes_per_token_matches_pool_accounting_int8_ratio():
    """The model's per-token KV cost must reproduce the pool accounting's
    int8/full streamed-bytes ratio exactly — 0.254 on the f32 tiny config
    at block_len 16: a quarter the payload plus one f32 scale per kv head
    per block (ISSUE 15 acceptance)."""
    c = tiny_llama_config()
    full = cm.kv_bytes_per_token(c, "bf16")
    int8 = cm.kv_bytes_per_token(c, "int8", block_len=16)
    tok = c.num_hidden_layers * 2 * c.num_key_value_heads * c.head_dim
    assert full == tok * 4                 # f32 itemsize on the CPU lane
    scales = c.num_hidden_layers * 2 * c.num_key_value_heads * 4
    assert int8 == pytest.approx(tok + scales / 16)
    assert int8 < full
    # 'mixed' keeps the device pool at native precision
    assert cm.kv_bytes_per_token(c, "mixed") == full
    assert round(int8 / full, 3) == round(0.25 + 4 / (16 * c.head_dim * 4),
                                          3) == 0.254


# -- attribution: signature determinism + reset ------------------------------

def _drive(measured):
    att = cm.TickAttribution(_model(), engine_id="sig",
                             registry=MetricsRegistry())
    for i, ms in enumerate(measured):
        att.on_tick(ms, occ=2, live_tokens=8 + i)
    return att.report()


def test_perf_signature_is_schedule_deterministic():
    """Same tick schedule, different wall clock: the signature (the
    loadgen --smoke A/B stability gate) must be byte-identical, while
    the wall-clock side of the report differs."""
    a = _drive([1.0] * 12)
    b = _drive([5.0, 2.0] * 6)
    assert cm.perf_signature(a) == cm.perf_signature(b)
    assert a["ratio"] != b["ratio"]
    assert a["measured_ms_sum"] != b["measured_ms_sum"]
    # and it is canonical JSON
    sig = json.loads(cm.perf_signature(a))
    assert sig["ticks_modeled"] == 12
    assert sig["profile"] == "unit"
    assert sig["drift"] == 0


def test_report_bounds_partition_the_ticks():
    rep = _drive([1.0] * 10)
    assert rep["ticks_modeled"] == 10
    assert sum(b["ticks"] for b in rep["bounds"].values()) == 10
    assert sum(b["share"] for b in rep["bounds"].values()) \
        == pytest.approx(1.0)
    assert rep["ratio"]["count"] == 10
    assert rep["anomalies"] == {"ratio": 0, "tick_ms": 0,
                                "tpot": 0, "ttft": 0}


def test_observability_reset_clears_attribution_state():
    att = cm.TickAttribution(_model(), engine_id="rst",
                             registry=MetricsRegistry())
    att.on_tick(1.0, occ=1, live_tokens=8)
    assert att.report()["ticks_modeled"] == 1
    assert att.model.memo_size() == 1
    obs.reset()                            # the test-isolation hook
    assert att.report()["ticks_modeled"] == 0
    assert att.model.memo_size() == 0
    assert att.report()["drift"] == []
