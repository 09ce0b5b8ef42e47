"""``correct`` for the LFM2-MoE cell has to be able to come out false:
both controls (the reference with int8-rounded weights, and the reference
without history, each in the program's place) fail it, an engine that
zeroes a state row mid-request fails it, and the new per-layer readers read
the run's record.  Tiny sizes, CPU, float32 program; the readings on the
chip at the cell's own size are in PERF.md."""

import time

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import serve_lfm2
from tiny_lfm2 import TINY, TINY_MIX, tiny_cell


def _run(**kw):
    return serve_lfm2.run(tiny_cell(), TINY, TINY_MIX, seed=2**31 + 5,
                          seconds=1.5, t_start=time.perf_counter(),
                          say=lambda w, f: None, **kw)


@pytest.fixture(scope="module")
def run():
    return _run(control_bits=8)


def test_sound_run_is_correct_and_both_controls_are_not(run):
    rows = {c["name"]: c for c in run["checks"]}
    assert run["correct"], run["checks"]
    assert rows["served_gap_max"]["value"] <= 1e-5
    control = {c["name"]: c for c in run["control"]}
    assert set(control) == {"served_gap_max", "served_gap_mean",
                            "no_history.served_gap_max",
                            "no_history.served_gap_mean"}
    assert not control["served_gap_max"]["ok"]
    assert not control["no_history.served_gap_max"]["ok"]
    assert not control["no_history.served_gap_mean"]["ok"]
    assert run["failed"] == 0 and run["attempted"] > 0


def test_an_engine_that_zeroes_a_state_row_mid_request_is_not_correct(
        monkeypatch):
    """The fault the no-history control stands for, made in the program:
    every 7th tick the engine's convolution state is wiped.  The tokens it
    then serves are another model's, and ``correct`` says so."""
    from paddle_tpu.serving import ServingEngine
    real = ServingEngine.step

    def faulty(self):
        if self._ticks % 7 == 6:
            self._cache = dict(self._cache, conv=self._cache["conv"] * 0)
        return real(self)
    monkeypatch.setattr(ServingEngine, "step", faulty)
    broken = _run()
    rows = {c["name"]: c for c in broken["checks"]}
    assert not broken["correct"]
    assert not rows["served_gap_max"]["ok"]
    assert rows["step_traces"]["ok"]


def test_state_rows_and_their_reader(run):
    state = run["counters"]["state"]
    assert state and all(total == 5 for _, total in state)   # 4 slots + null
    assert all(0 <= live <= 4 for live, _ in state)
    got = mf.load_metric("cache.state_live_pct").read(run)
    assert got == pytest.approx(
        100 * np.mean([live / 5 for live, _ in state]))
    assert 20.0 < got <= 80.0
    assert mf.load_metric("cache.state_live_pct").read(
        dict(run, counters=None)) is None
    assert mf.load_metric("cache.state_live_pct").read(
        dict(run, counters={"state": None})) is None
    # the experts' counters come out as for the other expert model
    win = run["counters"]["window"]
    assert win["pairs"].shape == (4, 4)          # expert layers x held
    assert mf.load_metric("moe.pairs_per_expert_mean").read(run) > 0
    assert run["cache"]["state_bytes"] == 4 * 5 * 2 * 64 * 4  # f32 here
    assert mf.load_metric("cache.window_dead_kv_pct").read(run) in (None,
                                                                    0.0)


def test_kv_layers_roofline_reader_on_a_recorded_shape_of_trace():
    """Against a hand-made reduced trace with the kernels' names as the
    program gives them: K and V once a K/V layer over the decode rows'
    kernel seconds; the chunk's kernel and other ops are not taken in; a
    trace without the kernel reads as nothing."""
    from benchmark.harness import flops_bytes_lfm2, peaks
    cfg = dict(TINY, dtype="bfloat16")
    ticks = [(0.0, 1.0, 3, 120), (1.0, 2.0, 3, 123), (2.0, 3.0, 0, 0),
             (9.0, 10.0, 3, 500)]
    rec = {"config": cfg, "peaks": peaks.peaks_for("TPU v5 lite"),
           "ticks": ticks, "trace_slice": (0.5, 3.5),
           "trace": {"ops": {
               "pallas:_step_impl_decode_rows_flash_decode:bf16[3,2,8,16]":
                   (1e-6, 10),
               "pallas:_step_impl_prompt_chunk_flash_decode:bf16[1,2,64,16]":
                   (5.0, 10),
               "pallas:_step_impl_decode_rows_moe_experts:bf16[128,32]":
                   (2e-6, 12),
               "fusion:bf16[3,64]": (9.0, 99)}}}
    want = sum(flops_bytes_lfm2.decode_rows_attention(cfg, 3, d)[1]
               for d in (120, 123)) / 819e9 / 1e-6
    reader = mf.load_metric("kernel.kv_layers_decode_attn_roofline")
    assert reader.read(rec) == pytest.approx(100 * want, rel=1e-3)
    assert reader.read(
        dict(rec, trace={"ops": {"fusion:bf16[3,64]": (9.0, 99)}})) is None
    assert reader.read(dict(rec, trace_slice=None)) is None
