"""The readers of the engine's own spans (``harness/engine_spans.py``):
interval arithmetic by hand, one small trace recorded on a TPU v5e with the
engine's spans in it (``record_engine_trace.py``; a wave engine and a
chunked one, 11 ticks; gzipped, because a trace carries its programs' HLO)
kept beside this file, and the ring and request-log readers against a tiny
engine run here on the CPU."""

import gzip
import os
import re
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.harness import engine_spans as es
from benchmark.harness import manifest as mf
from benchmark.harness import trace_reduce as tr
from benchmark.tests.conftest import TINY

TRACE = os.path.join(os.path.dirname(__file__), "engine_trace.xplane.pb.gz")


def test_a_known_split_is_attributed_exactly():
    spans = [("serving.step", 0, 100), ("serving.admit", 0, 10),
             ("serving.prefill", 2, 9), ("serving.dispatch", 3, 5),
             ("serving.decode", 12, 90), ("serving.grow", 12, 20),
             ("serving.readback", 30, 90), ("serving.advance", 91, 100),
             ("serving.step", 150, 160)]
    segments = es.flatten(spans, es.PARTS)
    # disjoint, sorted, and the innermost kept span names each stretch;
    # serving.prefill and serving.decode are transparent
    assert segments == [
        (0, 3, "serving.admit"), (3, 5, "serving.dispatch"),
        (5, 10, "serving.admit"), (10, 12, "serving.step"),
        (12, 20, "serving.grow"), (20, 30, "serving.step"),
        (30, 90, "serving.readback"), (90, 91, "serving.step"),
        (91, 100, "serving.advance"), (150, 160, "serving.step")]
    got = es.attribute([(1, 4), (8, 35), (95, 155)], segments)
    want = {"serving.admit": 4, "serving.dispatch": 1, "serving.step": 17,
            "serving.grow": 8, "serving.readback": 5, "serving.advance": 5,
            es.OUTSIDE: 50}
    assert {k: round(v * 1e9) for k, v in got.items()} == want
    assert sum(want.values()) == (4 - 1) + (35 - 8) + (155 - 95)


def test_the_skew_is_bounded_from_both_sides():
    # host: dispatch opens at 1000 k, readback closes 900 later; the device
    # runs 100 later for 700 and stamps everything 40 ahead
    ticks = [(1000 * k, 1000 * k + 900) for k in range(1, 6)]
    true = 40
    programs = [(d + 100 + true, d + 800 + true) for d, _ in ticks]
    low, high, pairs = es.skew_bounds(programs, ticks)
    assert pairs == 5 and low <= true <= high
    assert (low, high) == (true - 100, true + 100)
    # one tick with a prompt launch tightens the upper bound, one with a
    # prompt wake-up the lower
    programs[2] = (ticks[2][0] + 10 + true, programs[2][1])
    programs[3] = (programs[3][0], ticks[3][1] - 5 + true)
    assert es.skew_bounds(programs, ticks)[:2] == (true - 5, true + 10)
    assert es.skew_bounds([], ticks) is None


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with gzip.open(TRACE) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    return es.split_profile(profile), tr.reduce_profile(profile, top=100)


def test_the_recorded_parts_sum_to_the_idle_share(recorded):
    split, reduced = recorded
    assert split["window_s"] == reduced["window_s"]
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(split["idle_s"].values()) == pytest.approx(idle, rel=1e-9)
    assert sum(sec for _, sec in reduced["idle_gaps"]) == pytest.approx(
        idle, rel=1e-9)
    # 11 ticks with 5 ms of sleep after each: most idle time is the
    # harness's, and every part the engine has is there
    assert split["idle_s"][es.OUTSIDE] > 11 * 0.005
    assert all(sec > 0 for sec in split["idle_s"].values())


def test_the_recorded_skew_lies_inside_its_own_bounds(recorded):
    split, _ = recorded
    low, high = split["skew_ns"]
    assert low <= split["skew_applied_ns"] <= high
    # one engine program a dispatch: 11 steps and the wave's prefill
    assert split["pairs"] == 12
    assert 0 < high - low < 2e6


def test_the_recorded_kernels_are_told_apart_by_name(recorded):
    _, reduced = recorded
    pallas = sorted(k.split(":")[1] for k in reduced["ops"]
                    if k.startswith("pallas:"))
    assert pallas == ["_prefill_impl_wave_rows_flash_decode",
                      "_step_impl_decode_rows_flash_decode",
                      "_step_impl_prompt_chunk_flash_decode"]
    # the two readers PR 23 wrote go on reading: the step programs'
    # kernels by the instruction's name, the programs by the module's
    old = re.compile(r"^pallas:_\w*step_impl\w*:")
    assert sorted(k.split(":")[1] for k in reduced["ops"] if old.match(k)) \
        == pallas[1:]
    steps = [n for n in reduced["modules"]
             if re.match(r"^jit_.*step_impl", n)]
    assert sorted(steps) == ["jit__mixed_step_impl_paged",
                             "jit__step_impl_paged"]


# -- the ring and the request log, on the CPU --------------------------------

@pytest.fixture(scope="module")
def tiny_run():
    """A tiny paged wave engine driven the way ``serve.drive`` does, with
    the harness's stamps: four requests due 10 ms before they are
    submitted, one more refused."""
    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving import ServingEngine

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**TINY))
    model.eval()
    obs.reset()
    eng = ServingEngine(model, num_slots=4, max_length=128, paged=True,
                        block_len=8)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(1, 256, 5).astype(np.int32), max_new_tokens=2)
    eng.drain()                                  # the warm-up request
    t_zero = time.perf_counter()
    w0 = t_zero
    order, prompts = [], (5, 11, 20, 7)
    for n in prompts:
        rec = SimpleNamespace(due=time.perf_counter() - 0.010 - t_zero,
                              slot=None)
        eng.submit(rng.integers(1, 256, n).astype(np.int32),
                   max_new_tokens=4)
        order.append(rec)
    with pytest.raises(ValueError):
        eng.submit(np.ones(200, np.int32), max_new_tokens=4)
    while eng.num_active or eng.queue_depth:
        eng.step()
        for rec in order:
            rec.slot = rec.slot or time.perf_counter()
    run = {"window": (w0, time.perf_counter()), "t_zero": t_zero,
           "order": order, "judged": order, "prompts": prompts,
           "seconds": time.perf_counter() - w0,
           "prefill_batch": eng.prefill_batch}
    return run


def test_wave_metrics_read_the_ring(tiny_run):
    waves = es.ring_spans(tiny_run, "serving.prefill")
    assert [a["rows"] for _, a in waves] == [4]  # the warm-up's is outside
    (sec, args), = waves
    assert args["padded_rows"] == tiny_run["prefill_batch"] == 4
    assert args["tokens"] == sum(tiny_run["prompts"]) and args["bucket"] == 32
    pad = mf.load_metric("sched.wave_pad_pct").read(tiny_run)
    assert pad == pytest.approx(100 * (1 - 43 / (4 * 32)))
    share = mf.load_metric("sched.prefill_share_pct").read(tiny_run)
    assert share == pytest.approx(100 * sec / tiny_run["seconds"])
    assert 0 < share < 100


def test_request_waits_join_the_log_by_order_of_submission(tiny_run):
    waits = es.request_waits(tiny_run)
    assert len(waits) == 4                # not the warm-up, not the refused
    for (wait, prefill), rec in zip(waits, tiny_run["order"]):
        # due 10 ms before its submit; admitted in the first tick after
        assert wait > 10.0
        assert wait < (rec.slot - (rec.due + tiny_run["t_zero"])) * 1e3
        assert prefill > 0
    p95 = mf.load_metric("sched.admit_wait_p95_ms").read(tiny_run)
    assert min(w for w, _ in waits) <= p95 <= max(w for w, _ in waits)
    assert mf.load_metric("sched.prefill_p95_ms").read(tiny_run) > 0


def test_the_decode_rows_roofline_is_the_accepted_reader_narrowed():
    from benchmark.harness import peaks
    cfg = dict(TINY)
    ops = {"pallas:_step_impl_decode_rows_flash_decode:bf16[4,2,8,128]":
           [3e-3, 8],
           "pallas:_step_impl_prompt_chunk_flash_decode:bf16[1,2,512,128]":
           [1e-3, 8],
           "fusion:bf16[4,64]": [5e-3, 8]}
    run = {"trace": {"ops": ops}, "trace_slice": (0.0, 10.0), "config": cfg,
           "ticks": [(0.0, 1.0, 4, 400), (1.0, 2.0, 4, 404)],
           "peaks": peaks.peaks_for("TPU v5 lite")}
    both = mf.load_metric("kernel.decode_attn_roofline").read(run)
    rows = mf.load_metric("kernel.decode_rows_attn_roofline").read(run)
    assert rows == pytest.approx(both * 4e-3 / 3e-3)
    # a program without the names (the parent of the PR that added them)
    run["trace"]["ops"] = {"pallas:_mixed_step_impl_paged:bf16[4,2,8,128]":
                           [4e-3, 16]}
    assert mf.load_metric("kernel.decode_rows_attn_roofline").read(run) is None
    assert mf.load_metric("kernel.decode_attn_roofline").read(run) == \
        pytest.approx(both)
