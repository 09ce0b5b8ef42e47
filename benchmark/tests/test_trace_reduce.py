"""The reduction from a trace to numbers: interval arithmetic by hand, and
one small trace recorded on a TPU v5e (``record_trace.py``; six executions
of one matmul program, a 20 ms sleep after each) kept beside this file."""

import os

import pytest

from benchmark.harness import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "small_trace.xplane.pb")


def test_union_clip_and_gaps_by_hand():
    busy = tr.union([(5, 9), (0, 3), (2, 4), (8, 12), (20, 21)])
    assert busy == [(0, 4), (5, 12), (20, 21)]
    assert tr.clip(busy, 3, 20) == [(3, 4), (5, 12)]
    assert tr.gaps(tr.clip(busy, 3, 22), 3, 22) == [(4, 5), (12, 20),
                                                    (21, 22)]
    assert tr.gaps([], 0, 7) == [(0, 7)]


def test_op_key_names_what_the_trace_prints():
    pallas = ('%_step_impl.16 = bf16[24,8,8,128]{3,2,1,0:T(8,128)(2,1)} '
              'custom-call(s32[24]{0:T(128)} %positions.1), '
              'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.op_key(pallas) == "pallas:_step_impl:bf16[24,8,8,128]"
    fusion = ('%fusion.755 = (bf16[1,1,24,4096]{3,2,1,0:T(8,128)(2,1)}, '
              'bf16[1,1,24,4096]{3,2,1,0}) fusion(bf16[16,2]{1,0} %p), '
              'kind=kLoop')
    assert tr.op_key(fusion) == "fusion:(bf16[1,1,24,4096],bf16[1,1,24,4096])"
    assert tr.op_key("%squeeze.37 = bf16[192,512]{1,0} reshape(bf16[1] %x)") \
        == "reshape:squeeze:bf16[192,512]"
    assert tr.op_key("while.3") == "while"


def test_the_recorded_trace_gives_known_totals():
    r = tr.reduce_file(TRACE)
    steps = r["modules"]["jit_small_step"]
    # six were run; the device's clock reads about 1.1 ms ahead of the
    # host's spans, so the first lies 0.93 ms before ``bench.window`` opens
    # and is clipped away: five executions of ~47 us each
    assert len(steps) == 5 and all(46e-6 < t < 49e-6 for t in steps)
    assert r["window_s"] == pytest.approx(0.130451, rel=1e-4)
    assert r["busy_s"] == pytest.approx(236.4e-6, rel=1e-3)
    assert r["busy_s"] == pytest.approx(sum(steps), rel=0.01)
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)]]
    assert r["ops"]["fusion:convolution_tanh_fusion:bf16[512,2048]"][1] == 5
    assert [k for k, _ in r["device_ops"][:2]] == [
        "fusion:convolution_tanh_fusion:bf16[512,2048]",
        "fusion:bf16[512,2048]"]
