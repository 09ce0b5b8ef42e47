"""``step.weight_passes`` and ``step.pass_fill_pct`` against the tracer's
ring as a tiny chunked engine leaves it on the CPU: one pass of the weights a
tick, the real tokens over the pass's padded rows, and a ring whose spans
carry no such args (a program from before them)."""

import time

import pytest

from benchmark.harness import engine_spans as es
from benchmark.harness import manifest as mf
from benchmark.tests.test_sample_sort_skipped import drive, engine  # noqa: F401

PASSES = mf.load_metric("step.weight_passes").read
FILL = mf.load_metric("step.pass_fill_pct").read


def test_both_are_read_from_the_ring(engine):  # noqa: F811
    from paddle_tpu import observability as obs

    obs.reset()
    run = drive(engine, [None, None], new_tokens=12)
    ticks = es.ring_spans(run, "serving.decode")
    assert len(ticks) >= 12
    assert PASSES(run) == 1.0
    rows = engine.num_slots + engine.prefill_chunk
    assert {a["pass_rows"] for _, a in ticks} == {rows}
    # two 9-token prompts, a chunk each, then 12 tokens a request less the
    # one its last chunk sampled: every real token of the window, once
    real = sum(a["pass_tokens"] for _, a in ticks)
    assert real == 2 * 9 + 2 * 11
    assert FILL(run) == pytest.approx(100.0 * real / (rows * len(ticks)))
    assert 0 < FILL(run) < 100
    # each window reads its own ticks only
    again = drive(engine, [None], new_tokens=3)
    assert FILL(again) != FILL(run)


def test_none_where_the_spans_carry_no_such_args():
    """The parent's ``serving.decode`` spans have ``slots``, ``sample_path``
    and the walk's args; a window with no tick at all reads None too."""
    from paddle_tpu import observability as obs

    obs.reset()
    w0 = time.perf_counter()
    empty = {"window": (w0, time.perf_counter())}
    assert PASSES(empty) is None and FILL(empty) is None
    for _ in range(3):
        with obs.get_tracer().span("serving.decode", slots=2,
                                   sample_path="greedy", kv_blocks=4,
                                   kv_walk=8):
            time.sleep(0.001)
    run = {"window": (w0, time.perf_counter())}
    assert len(es.ring_spans(run, "serving.decode")) == 3
    assert PASSES(run) is None and FILL(run) is None
