"""``correct`` for the Olmo-Hybrid cell has to be able to come out false:
both controls (the reference with int8-rounded weights, and the reference
without history, each in the program's place) fail it, an engine that
zeroes a slot's matrix state mid-request fails it, and the new per-layer
readers read the run's record.  Tiny sizes, CPU, float32 program; the
readings on the chip at the cell's own size are in PERF.md."""

import time

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import serve_olmo_hybrid
from tiny_olmo_hybrid import TINY, TINY_MIX, tiny_cell


def _run(**kw):
    return serve_olmo_hybrid.run(
        tiny_cell(), TINY, TINY_MIX, seed=2**31 + 5, seconds=1.5,
        t_start=time.perf_counter(), say=lambda w, f: None, **kw)


@pytest.fixture(scope="module")
def run():
    return _run(control_bits=8)


def test_sound_run_is_correct_and_both_controls_are_not(run):
    rows = {c["name"]: c for c in run["checks"]}
    assert run["correct"], run["checks"]
    assert rows["served_gap_max"]["value"] <= 1e-4
    control = {c["name"]: c for c in run["control"]}
    assert set(control) == {"served_gap_max", "served_gap_mean",
                            "no_history.served_gap_max",
                            "no_history.served_gap_mean",
                            "bf16_state.served_gap_max",
                            "bf16_state.served_gap_mean"}
    # a bfloat16 state is further from the reference than the float32
    # program is (whether it passes the tiny cell's limits is not asked)
    assert (control["bf16_state.served_gap_mean"]["value"]
            > rows["served_gap_mean"]["value"])
    assert not control["served_gap_max"]["ok"]
    assert not control["no_history.served_gap_max"]["ok"]
    assert not control["no_history.served_gap_mean"]["ok"]
    assert run["failed"] == 0 and run["attempted"] > 0


@pytest.mark.parametrize("leaf", ["delta", "conv"])
def test_an_engine_that_zeroes_a_state_leaf_mid_request_is_not_correct(
        monkeypatch, leaf):
    """The fault the no-history control stands for, made in the program:
    every 7th tick one of the engine's two state leaves is wiped.  The
    tokens it then serves are another model's, and ``correct`` says so."""
    from paddle_tpu.serving import ServingEngine
    real = ServingEngine.step

    def faulty(self):
        if self._ticks % 7 == 6:
            self._cache = dict(self._cache,
                               **{leaf: self._cache[leaf] * 0})
        return real(self)
    monkeypatch.setattr(ServingEngine, "step", faulty)
    broken = _run()
    rows = {c["name"]: c for c in broken["checks"]}
    assert not broken["correct"]
    assert not rows["served_gap_max"]["ok"]
    assert rows["step_traces"]["ok"]


def test_state_rows_bytes_and_chunk_tokens(run):
    state = run["counters"]["state"]
    assert state and all(total == 5 for _, total in state)   # 4 slots + null
    got = mf.load_metric("cache.state_live_pct").read(run)
    assert got == pytest.approx(
        100 * np.mean([live / 5 for live, _ in state]))
    # 4 linear layers x (4 heads x 16 x 32 float32 + 3 x 256 channels f32)
    per_slot = 4 * (4 * 16 * 32 * 4 + 3 * 256 * 4)
    assert run["cache"]["state_bytes_per_slot"] == per_slot
    assert run["cache"]["state_bytes"] == 5 * per_slot
    # no trace was taken: no traced ticks' chunk tokens
    assert run["counters"]["chunk_tokens"] is None


def test_chunk_tokens_are_the_programs_counter():
    """Tick by tick, the chunk part's real tokens add up to the prompts'."""
    from paddle_tpu.serving import ServingEngine
    model, _ = serve_olmo_hybrid.build_model(TINY, 3, 128)
    eng = serve_olmo_hybrid.Counted(
        ServingEngine(model, seed=0, **tiny_cell()["engine"]))
    rng = np.random.default_rng(0)
    lens = (5, 19, 8)
    for n in lens:
        eng.submit(rng.integers(1, 256, n).astype(np.int32),
                   max_new_tokens=3)
    while not all(len(eng.result(r)) == 3 for r in range(len(lens))):
        eng.step()
    assert sum(eng.chunk) == sum(lens)
    assert max(eng.chunk) == 8 and 0 in eng.chunk
    ticks = [(i, i + 0.5, 0, 0) for i in range(len(eng.chunk))]
    assert serve_olmo_hybrid.chunk_tokens_between(
        eng.chunk, ticks, 0.0, 2.9) == eng.chunk[:3]


def _trace_record():
    from benchmark.harness import peaks
    cfg = dict(TINY, dtype="bfloat16")
    ticks = [(0.0, 1.0, 3, 120), (1.0, 2.0, 2, 123), (2.0, 3.0, 0, 0),
             (9.0, 10.0, 3, 500)]
    return {"config": cfg, "peaks": peaks.peaks_for("TPU v5 lite"),
            "ticks": ticks, "trace_slice": (0.5, 3.5),
            "counters": {"chunk_tokens": [8, 0, 5]},
            "trace": {"busy_s": 4e-5, "ops": {
                "pallas:_step_impl_decode_rows_gated_delta_step:f32[3,1,128]":
                    (1e-6, 8),
                "pallas:_step_impl_prompt_chunk_gated_delta_chunk:"
                "f32[1,8,128]": (3e-6, 12),
                "pallas:_step_impl_decode_rows_flash_decode:bf16[3,4,8,16]":
                    (5.0, 10),
                "fusion:bf16[3,64]": (9.0, 99)}}}


def test_the_three_new_readers_on_a_recorded_shape_of_trace():
    """Against a hand-made reduced trace with the kernels' names as the
    program gives them: the occupied rows' state once a linear layer over
    the step kernel's seconds, the real chunk tokens' walk over the chunk
    kernel's, both kernels over the busy time; other kernels are not taken
    in; a trace without the kernels reads as nothing."""
    from benchmark.harness import flops_bytes_olmo_hybrid as fo
    rec = _trace_record()
    cfg = rec["config"]
    want = sum(fo.gated_delta_step(cfg, rows)[1] for rows in (3, 2)) / 819e9
    step = mf.load_metric("kernel.gdn_step_roofline")
    assert step.read(rec) == pytest.approx(100 * want / 1e-6, rel=1e-3)
    chunk = mf.load_metric("kernel.gdn_chunk_roofline")
    want = sum(max(f / 197e12, b / 819e9) for f, b in
               (fo.gated_delta_chunk(cfg, n) for n in (8, 5)))
    assert chunk.read(rec) == pytest.approx(100 * want / 3e-6, rel=1e-3)
    share = mf.load_metric("step.gdn_share_pct")
    assert share.read(rec) == pytest.approx(100 * 4e-6 / 4e-5)
    bare = dict(rec, trace={"busy_s": 1.0,
                            "ops": {"fusion:bf16[3,64]": (9.0, 99)}})
    assert all(r.read(bare) is None for r in (step, chunk, share))
    assert step.read(dict(rec, trace_slice=None)) is None
    assert chunk.read(dict(rec, counters={"chunk_tokens": None})) is None
    assert chunk.read(dict(rec, counters={"chunk_tokens": [0, 0]})) is None
