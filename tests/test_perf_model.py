"""Engine-level cost-model attribution + drift sentinel (ISSUE 15).

The acceptance sweep: every clean cache layout (the same 8 the
static-analysis CLI lints) drains a small trace with ZERO drift
findings; a scripted-clock engine whose ticks are artificially slowed
after calibration produces a structured perf-drift Finding and trips
the anomaly counters; every modeled tick is one
``perf.tick_model_ratio`` sample (and no span-ring write); and the
metrics registry's label-cardinality guard coalesces offender families
into an overflow child.
"""

import json
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags, observability as obs
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import ServingEngine

MAXLEN = 64
BL = 8


@pytest.fixture(scope="module")
def lm():
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    return model


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


# the 8 layouts the static-analysis CLI sweeps (__main__.py variants)
LAYOUTS = [
    ("contiguous", {}),
    ("paged", dict(paged=True, block_len=BL)),
    ("contiguous+chunked", dict(chunked=True, prefill_chunk=8)),
    ("paged+chunked", dict(paged=True, block_len=BL, chunked=True,
                           prefill_chunk=8)),
    ("contiguous+spec", dict(spec_decode=True, spec_k=4)),
    ("paged+spec", dict(paged=True, block_len=BL, spec_decode=True,
                        spec_k=4)),
    ("paged+chunked+spec", dict(paged=True, block_len=BL, chunked=True,
                                prefill_chunk=8, spec_decode=True,
                                spec_k=4)),
    ("contiguous+chunked+spec", dict(chunked=True, prefill_chunk=8,
                                     spec_decode=True, spec_k=4)),
]


@pytest.mark.parametrize("name,kw", LAYOUTS, ids=[n for n, _ in LAYOUTS])
def test_clean_layouts_produce_no_drift(lm, name, kw):
    """Every clean layout models its ticks and reports zero drift —
    the negative half of the drift acceptance criterion."""
    eng = ServingEngine(lm, num_slots=3, max_length=MAXLEN, **kw)
    for i, n in enumerate((5, 9)):
        eng.submit(_prompt(n, seed=40 + i), max_new_tokens=16)
    eng.drain()
    rep = eng.perf_report()
    assert rep["enabled"]
    assert rep["ticks_modeled"] > 0
    assert rep["drift"] == []
    assert sum(b["ticks"] for b in rep["bounds"].values()) \
        == rep["ticks_modeled"]
    assert sum(b["share"] for b in rep["bounds"].values()) \
        == pytest.approx(1.0)
    assert rep["model_inputs"]["weight_bytes"] > 0
    assert rep["memo_entries"] >= 1


def test_int8_kv_shrinks_the_modeled_kv_term(lm):
    """The engine-built model inherits the pool's dtype: the int8
    engine's per-token KV cost shrinks by the committed ratio without
    running a single tick."""
    full = ServingEngine(lm, num_slots=2, max_length=MAXLEN, paged=True,
                         block_len=BL)
    int8 = ServingEngine(lm, num_slots=2, max_length=MAXLEN, paged=True,
                         block_len=BL, kv_cache_dtype="int8")
    kf = full.perf_report()["model_inputs"]["kv_bytes_per_token"]
    k8 = int8.perf_report()["model_inputs"]["kv_bytes_per_token"]
    assert k8 < kf
    # paged int8 amortizes one f32 scale row per block_len tokens
    c = lm.config
    scales = c.num_hidden_layers * 2 * c.num_key_value_heads * 4
    assert k8 == pytest.approx(kf / 4 + scales / BL)


def test_perf_model_off_flag_disables_the_layer(lm):
    old = flags.flag("perf_model")
    flags.set_flags({"perf_model": "off"})
    try:
        eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
        eng.submit(_prompt(5, seed=44), max_new_tokens=4)
        eng.drain()
        assert eng.perf_report() == {"enabled": False}
    finally:
        flags.set_flags({"perf_model": old})


# -- the scripted-clock drift proof ------------------------------------------

class _ScriptedClock:
    """Deterministic stand-in for the engine's ``time`` module: every
    ``perf_counter`` call advances a fixed dt, so a tick 'costs' the
    number of clock reads it spans; inflating dt mid-run fakes a
    sustained slowdown without sleeping."""

    def __init__(self, dt=1e-4):
        self.t = 0.0
        self.dt = dt

    def perf_counter(self):
        self.t += self.dt
        return self.t


def test_scripted_slow_tick_produces_drift_finding(lm, monkeypatch):
    """The positive half of the drift criterion: after the EWMA
    calibrates on honest ticks, a sustained artificial slowdown pushes
    measured/predicted out of the band and perf_report carries a
    structured perf-drift finding (plus tripped anomaly counters)."""
    from paddle_tpu.serving import engine as engine_mod
    clk = _ScriptedClock()
    monkeypatch.setattr(engine_mod, "time", clk)
    eng = ServingEngine(lm, num_slots=1, max_length=MAXLEN)
    eng.submit(_prompt(6, seed=50), max_new_tokens=40)
    for _ in range(16):                 # SKIP + WARMUP honest ticks
        eng.step()
    clk.dt *= 400.0                     # every later tick reads 400x slower
    eng.drain()
    rep = eng.perf_report()
    assert rep["drift"], "slowed ticks produced no drift finding"
    d = rep["drift"][0]
    assert d["rule"] == "perf-drift"
    assert d["severity"] == "warning"
    assert "bound=" in d["path"]
    assert "left the calibrated band" in d["message"]
    # the sentinel counters fired too (tick_ms is one-sided upward)
    assert rep["anomalies"]["tick_ms"] >= 1
    assert rep["anomalies"]["ratio"] >= 1
    # sticky: the finding survives further reporting, and reset clears it
    assert eng.perf_report()["drift"]
    obs.reset()
    assert eng.perf_report()["drift"] == []


# -- one sample per modeled tick ---------------------------------------------

def test_one_ratio_sample_per_modeled_tick_and_none_in_the_span_ring(
        lm, tmp_path):
    """Every modeled tick lands once in ``perf.tick_model_ratio`` (what
    ``perf_report()`` and the Prometheus text read); the span ring gets
    no per-tick counter sample beside the ``serving.step`` spans, and the
    Chrome-trace export stays loadable."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
    eng.submit(_prompt(5, seed=60), max_new_tokens=6)
    eng.drain()
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    assert [e for e in events
            if e.get("ph") == "X" and e["name"] == "serving.step"]
    assert not [e for e in events if e.get("ph") == "C"]
    fam = obs.snapshot()["perf.tick_model_ratio"]["series"]
    samples = sum(row["count"] for row in fam
                  if row["labels"]["engine"] == eng._eid)
    assert samples == eng.perf_report()["ticks_modeled"] > 0


# -- metrics label-cardinality guard -----------------------------------------

def test_cardinality_guard_coalesces_into_overflow_child():
    old = flags.flag("metrics_max_children")
    flags.set_flags({"metrics_max_children": 4})
    try:
        reg = MetricsRegistry()
        fam = reg.counter("t.card", "cardinality guard under test")
        for i in range(4):
            fam.labels(uid=str(i)).inc()
        with pytest.warns(RuntimeWarning, match="label-cardinality cap"):
            fam.labels(uid="intruder-a").inc()
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # warns once per family
            fam.labels(uid="intruder-b").inc(2)
            # existing children keep resolving normally past the cap
            fam.labels(uid="2").inc()
        assert fam.coalesced == 2
        assert fam.value(overflow="true") == 3.0
        assert fam.value(uid="2") == 2.0
        # the overflow child is visible in the exposition
        assert 'overflow="true"' in reg.prometheus_text()
    finally:
        flags.set_flags({"metrics_max_children": old})
