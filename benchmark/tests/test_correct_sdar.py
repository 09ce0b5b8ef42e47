"""``correct`` for the SDAR-MoE cell has to be able to come out false: the
three controls (the reference with int8-rounded weights, under a causal
mask, and with every block's K/V as its last denoising forward left them,
each in the program's place) fail it, an engine that skips the commit
forward fails it, and the new per-layer readers read the run's record.
Tiny sizes, CPU, float32 program; the readings on the chip at the cell's
own size are in PERF.md."""

import time

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import serve_sdar
from tiny_sdar import TINY, TINY_MIX, tiny_cell


def _run(**kw):
    return serve_sdar.run(tiny_cell(), TINY, TINY_MIX, seed=2**31 + 5,
                          seconds=1.5, t_start=time.perf_counter(),
                          say=lambda w, f: None, **kw)


@pytest.fixture(scope="module")
def run():
    return _run(control_bits=8)


def test_sound_run_is_correct_and_every_control_is_not(run):
    rows = {c["name"]: c for c in run["checks"]}
    assert run["correct"], run["checks"]
    assert rows["served_gap_max"]["value"] <= 1e-5
    assert rows["served_pick_gap_mean"]["value"] <= 1e-6
    control = {c["name"]: c for c in run["control"]}
    assert set(control) == {
        pre + n for pre in ("", "causal.", "no_commit.")
        for n in serve_sdar.NUMBERS}
    for pre in ("", "causal.", "no_commit."):
        assert not all(control[pre + n]["ok"] for n in serve_sdar.NUMBERS)
    assert not control["no_commit.served_gap_mean"]["ok"]
    assert not control["causal.served_gap_mean"]["ok"]
    assert run["failed"] == 0 and run["attempted"] > 0


def test_an_engine_that_skips_the_commit_forward_is_not_correct(monkeypatch):
    """The fault the third control stands for, made in the program: a row
    whose block came back mask-free moves on at once — its K/V stay as the
    last denoising forward wrote them.  The tokens it then serves are
    another model's, and ``correct`` says so."""
    from paddle_tpu.serving import ServingEngine
    real = ServingEngine._advance_block

    def no_commit(self, new, deliver, now):
        finished = real(self, new, deliver, now)
        for i in deliver:
            if self._slots[i] is not None:
                self._positions[i] += self._block
                self._open_block(i)
        return finished
    monkeypatch.setattr(ServingEngine, "_advance_block", no_commit)
    broken = _run()
    rows = {c["name"]: c for c in broken["checks"]}
    assert not broken["correct"]
    assert not rows["served_gap_mean"]["ok"]
    assert rows["step_traces"]["ok"]


def test_prompts_never_hold_the_mask_token():
    from benchmark.harness import traffic
    cfg = dict(TINY, vocab_size=8, mask_token_id=5)
    reqs = serve_sdar.without_mask_token(
        traffic.generate(TINY_MIX, 8, 3, 1.0), cfg)
    assert reqs and all((r.prompt != 5).all() for r in reqs)


def test_the_block_ticks_readers(run):
    per_forward = mf.load_metric("diffusion.tokens_per_forward").read(run)
    commits = mf.load_metric("diffusion.commit_share_pct").read(run)
    # under seeded weights no confidence passes 0.9: a block of four takes
    # four denoising forwards and (unless the request ends in it) a commit
    assert 0.5 < per_forward <= 1.0
    assert 10.0 < commits <= 20.0
    for name in ("diffusion.tokens_per_forward",
                 "diffusion.commit_share_pct"):
        assert mf.load_metric(name).read(dict(run, window=(1e12, 2e12))) \
            is None
    # the accepted readers the cell lists read this record unedited
    for name in ("step.weight_passes", "step.pass_fill_pct",
                 "kernel.decode_walk_live_pct", "sample.sort_skipped_pct",
                 "moe.pairs_per_expert_mean", "moe.load_max_over_mean",
                 "sched.tick_p50_ms", "sched.occupancy_mean",
                 "cache.live_kv_pct.saturated",
                 "entry.ttft_p50_ms.saturated"):
        assert mf.load_metric(name).read(run) is not None, name
    assert mf.load_metric("step.weight_passes").read(run) == 1
    assert mf.load_metric("sample.sort_skipped_pct").read(run) == 100.0
    win = run["counters"]["window"]
    assert win["pairs"].shape == (3, 4)          # expert layers x held


def test_block_rows_roofline_reader_on_a_recorded_shape_of_trace():
    """Against a hand-made reduced trace with the kernels' names as the
    program gives them: K and V of the rows' depths and of each row's block
    once a layer over the block rows' kernel seconds; the chunk's kernel
    and other ops are not taken in; a trace without the kernel reads as
    nothing."""
    from benchmark.harness import flops_bytes_sdar, peaks
    cfg = dict(TINY, dtype="bfloat16")
    ticks = [(0.0, 1.0, 3, 120), (1.0, 2.0, 3, 123), (2.0, 3.0, 0, 0),
             (9.0, 10.0, 3, 500)]
    rec = {"config": cfg, "peaks": peaks.peaks_for("TPU v5 lite"),
           "ticks": ticks, "trace_slice": (0.5, 3.5),
           "trace": {"ops": {
               "pallas:_step_impl_block_rows_flash_decode:bf16[3,2,8,16]":
                   (1e-6, 10),
               "pallas:_step_impl_prompt_chunk_flash_decode:bf16[1,2,64,16]":
                   (5.0, 10),
               "pallas:_step_impl_token_pass_moe_experts:bf16[128,32]":
                   (2e-6, 12),
               "fusion:bf16[3,64]": (9.0, 99)}}}
    want = sum(flops_bytes_sdar.block_rows_attention(cfg, 3, d)[1]
               for d in (120, 123)) / 819e9 / 1e-6
    reader = mf.load_metric("kernel.block_decode_attn_roofline")
    assert reader.read(rec) == pytest.approx(100 * want, rel=1e-3)
    assert reader.read(
        dict(rec, trace={"ops": {"fusion:bf16[3,64]": (9.0, 99)}})) is None
    assert reader.read(dict(rec, trace_slice=None)) is None


def test_bytes_of_the_block_rows_attention():
    from benchmark.harness import flops_bytes_sdar
    cfg = {"num_hidden_layers": 48, "num_attention_heads": 32,
           "num_key_value_heads": 4, "head_dim": 128, "block_length": 4,
           "dtype": "bfloat16"}
    assert flops_bytes_sdar.kv_bytes_per_position(cfg) == 96 * 1024
    flops, nbytes = flops_bytes_sdar.block_rows_attention(cfg, 2, 1000)
    keys = 1000 + 2 * 4
    assert flops == 4 * 4096 * 4 * keys * 48
    assert nbytes == 96 * 1024 * keys + 2 * 4096 * 4 * 2 * 48 * 2
    # memory-bound: 32 FLOPs a byte of K and V against the chip's 240
    assert flops / nbytes < 240
