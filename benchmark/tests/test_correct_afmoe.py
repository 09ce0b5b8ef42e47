"""``correct`` for the AFMoE cell has to be able to come out false: both
controls (the reference with int8-rounded weights, and the reference
without its window, each in the program's place) fail it, and the new
per-layer readers read the run's record.  Tiny sizes, CPU, float32
program; the readings on the chip at the cell's own size are in PERF.md."""

import time

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import serve_afmoe
from tiny_afmoe import TINY, TINY_MIX, tiny_cell


@pytest.fixture(scope="module")
def run():
    return serve_afmoe.run(tiny_cell(), TINY, TINY_MIX, seed=2**31 + 5,
                           seconds=1.5, t_start=time.perf_counter(),
                           say=lambda w, f: None, control_bits=8)


def test_sound_run_is_correct_and_both_controls_are_not(run):
    rows = {c["name"]: c for c in run["checks"]}
    assert run["correct"], run["checks"]
    assert rows["served_gap_max"]["value"] <= 1e-5
    control = {c["name"]: c for c in run["control"]}
    assert set(control) == {"served_gap_max", "served_gap_mean",
                            "no_window.served_gap_max",
                            "no_window.served_gap_mean"}
    # the sample holds requests past the window of 16: without the window
    # the first token moves at most of their positions
    assert not control["served_gap_max"]["ok"]
    assert not control["no_window.served_gap_max"]["ok"]
    assert not control["no_window.served_gap_mean"]["ok"]
    assert run["failed"] == 0 and run["attempted"] > 0


def test_counters_and_their_readers(run):
    win = run["counters"]["window"]
    assert win["pairs"].shape == (4, 2)          # expert layers x held
    # every routed pair went to a held expert or elsewhere
    assert win["pairs"].sum() + win["pairs_elsewhere"] > 0
    assert 0 < win["experts_touched"] <= 2 * win["layer_calls"]
    pairs = mf.load_metric("moe.pairs_per_expert_mean").read(run)
    assert pairs == pytest.approx(
        win["pairs"].sum() / (2 * win["layer_calls"]))
    assert mf.load_metric("moe.load_max_over_mean").read(run) >= 1.0
    dead = mf.load_metric("cache.window_dead_kv_pct").read(run)
    assert 0.0 < dead < 80.0                     # 4 of 5 layers have windows


def test_roofline_readers_on_a_recorded_shape_of_trace(run):
    """The two device-trace readers against a hand-made reduced trace with
    the kernels' names as the program gives them: shares come out of the
    counters' bytes over the named kernels' seconds, other kernels of the
    step program are not taken in, and a program without the counters or
    the kernels reads as nothing."""
    from benchmark.harness import flops_bytes_afmoe, peaks
    cfg = dict(TINY, dtype="bfloat16")
    ticks = [(0.0, 1.0, 3, 120), (1.0, 2.0, 3, 123)]
    counted = {"pairs": np.array([[5, 7], [6, 6], [4, 8], [9, 3]]),
               "experts_touched": 16, "layer_calls": 16,
               "dead_by_tick": [(ticks[0], 40), (ticks[1], 44)]}
    rec = {"config": cfg, "peaks": peaks.peaks_for("TPU v5 lite"),
           "counters": {"trace": counted, "window": counted},
           "trace": {"ops": {
               "pallas:_step_impl_decode_rows_moe_experts:bf16[128,32]":
                   (2e-6, 12),
               "pallas:_step_impl_prompt_chunk_moe_experts:bf16[128,32]":
                   (2e-6, 12),
               "pallas:_step_impl_decode_rows_flash_decode:bf16[3,2,8,16]":
                   (1e-6, 10),
               "pallas:_step_impl_prompt_chunk_flash_decode:bf16[1,2,64,16]":
                   (5.0, 10),
               "fusion:bf16[3,64]": (9.0, 99)}}}
    _, nbytes = flops_bytes_afmoe.grouped_product(cfg, 48, 16)
    moe = mf.load_metric("kernel.moe_experts_roofline").read(rec)
    assert moe == pytest.approx(100 * nbytes / 819e9 / 4e-6, rel=1e-3)
    attn = mf.load_metric("kernel.window_decode_attn_roofline").read(rec)
    want = sum(flops_bytes_afmoe.decode_rows_attention(cfg, 3, d, x)[1]
               for d, x in ((120, 40), (123, 44))) / 819e9 / 1e-6
    assert attn == pytest.approx(100 * want, rel=1e-3)
    # a window layer reads min(depth, window): fewer bytes than no window
    assert flops_bytes_afmoe.decode_rows_attention(cfg, 3, 120, 40)[1] < \
        flops_bytes_afmoe.decode_rows_attention(cfg, 3, 120, 0)[1]
    for name in ("kernel.moe_experts_roofline",
                 "kernel.window_decode_attn_roofline"):
        assert mf.load_metric(name).read(dict(rec, counters=None)) is None
        assert mf.load_metric(name).read(
            dict(rec, trace={"ops": {"fusion:bf16[3,64]": (9.0, 99)}})) \
            is None
    for name in ("moe.pairs_per_expert_mean", "moe.load_max_over_mean",
                 "cache.window_dead_kv_pct"):
        assert mf.load_metric(name).read(dict(rec, counters=None)) is None


def test_only_a_request_that_could_have_no_token_yet_is_left_out():
    """Against a backlog the window's end finds the chunked engine's one
    cursor mid-prompt: that request is not judged, on the stamps' evidence
    alone (fewer ticks in its slot than its prompt has chunks).  One that
    had its ticks and has no token stays failed, and so does any request of
    an engine that does not chunk."""
    from types import SimpleNamespace as NS

    def rec(index, prompt_len, slot, times):
        return NS(req=NS(index=index, prompt=[1] * prompt_len), slot=slot,
                  times=times, due=0.0)
    ticks = [(t - 0.1, float(t), 2, 0) for t in range(1, 11)]
    stamps = {"window": (2.0, 10.0), "t_zero": 0.0, "ticks": ticks}
    served = rec(0, 16, 3.0, [4.0, 5.0])
    streaming = rec(1, 40, 8.0, [])        # 5 chunks of 8, 3 ticks had
    stalled = rec(2, 16, 5.0, [])          # 2 chunks, 6 ticks had
    m = {"judged": [served, streaming, stalled], "failed": 2,
         "ttft_ms": [4000.0, 8000.0, 8000.0], "queue_wait_ms": [0, 0, 0]}
    cell = {"engine": {"chunked": True, "prefill_chunk": 8}}
    kept, out = serve_afmoe.mid_prefill_at_end(m, stamps, cell)
    assert [r.req.index for r in kept["judged"]] == [0, 2]
    assert kept["failed"] == 1 and kept["ttft_ms"] == [4000.0, 8000.0]
    assert out == [{"index": 1, "prompt_tokens": 40, "chunks_needed": 5,
                    "ticks_had": 3, "admitted_before_end_s": 2.0}]
    same, none = serve_afmoe.mid_prefill_at_end(m, stamps, {"engine": {}})
    assert same is m and none == []
