"""``sample.sort_skipped_pct`` against the tracer's ring as a tiny engine
leaves it on the CPU: all-greedy ticks, then ticks in which a sampling row
truncates, and a ring whose spans carry no ``sample_path`` (a program from
before the arg)."""

import time

import numpy as np
import pytest

from benchmark.harness import engine_spans as es
from benchmark.harness import manifest as mf
from benchmark.tests.conftest import TINY


@pytest.fixture(scope="module")
def engine():
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving import ServingEngine

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**TINY))
    model.eval()
    return ServingEngine(model, num_slots=4, max_length=128, paged=True,
                         chunked=True, prefill_chunk=16, block_len=8)


def drive(engine, samplings, new_tokens=6):
    """Serve one request a sampling given; the window is the drive."""
    rng = np.random.default_rng(1)
    w0 = time.perf_counter()
    for sampling in samplings:
        engine.submit(rng.integers(1, 256, 9).astype(np.int32),
                      max_new_tokens=new_tokens, sampling=sampling)
    engine.drain()
    w1 = time.perf_counter()
    return {"window": (w0, w1), "seconds": w1 - w0}


def test_the_share_is_read_from_the_ring(engine):
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import SamplingParams

    read = mf.load_metric("sample.sort_skipped_pct").read
    obs.reset()
    greedy = drive(engine, [None, SamplingParams()])
    ticks = es.ring_spans(greedy, "serving.decode")
    assert len(ticks) >= 6
    assert {a["sample_path"] for _, a in ticks} == {"greedy"}
    assert read(greedy) == 100.0
    # a temperature alone draws without a sort: still skipped
    warm = drive(engine, [SamplingParams(temperature=0.7), None])
    assert {a["sample_path"] for _, a in es.ring_spans(
        warm, "serving.decode")} == {"categorical", "greedy"}
    assert read(warm) == 100.0
    # a window over a truncating request's ticks, which sort, and a greedy
    # one's after it
    first = drive(engine, [SamplingParams(temperature=0.7, top_p=0.5)],
                  new_tokens=3)
    second = drive(engine, [None], new_tokens=3)
    mixed = {"window": (first["window"][0], second["window"][1])}
    paths = [a["sample_path"] for _, a in es.ring_spans(
        mixed, "serving.decode")]
    assert "truncated" in paths and "greedy" in paths
    assert read(mixed) == pytest.approx(
        100.0 * sum(p != "truncated" for p in paths) / len(paths))
    assert 0 < read(mixed) < 100
    # each window reads its own ticks only
    assert read(greedy) == 100.0


def test_none_where_the_spans_carry_no_such_arg():
    """The parent's ``serving.decode`` spans have ``slots`` and nothing else;
    a window with no tick at all reads None too."""
    from paddle_tpu import observability as obs

    read = mf.load_metric("sample.sort_skipped_pct").read
    obs.reset()
    w0 = time.perf_counter()
    empty = {"window": (w0, time.perf_counter())}
    assert read(empty) is None
    for _ in range(3):
        with obs.get_tracer().span("serving.decode", slots=2):
            time.sleep(0.001)
    run = {"window": (w0, time.perf_counter())}
    assert len(es.ring_spans(run, "serving.decode")) == 3
    assert read(run) is None
