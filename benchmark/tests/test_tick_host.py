"""The host's part of a tick (``harness/tick_host.py``): the self-time
split and the stall sums on hand-made ring events, the None-against-a-parent
rule, the trace recorded on a TPU v5e before the engine had a
``serving.upload`` (``engine_trace.xplane.pb.gz``: 11 ticks of a program that
is a parent to the split), and the seven metric files against a tiny engine
run here on the CPU."""

import gzip
import time

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import tick_host as th
from benchmark.harness import trace_reduce as tr
from benchmark.tests.conftest import TINY
from benchmark.tests.test_engine_spans import TRACE

METRICS = ("host.schedule_ms", "host.assemble_ms", "host.upload_ms",
           "host.enqueue_ms", "host.account_ms", "host.stall_ms",
           "host.stall_readback_ms")


def _tick(at, readback=60, wave=False):
    """One tick's spans from ``at``: admit 10 (with a wave inside it:
    account 1, then build 3 of which upload 2, dispatch 1, readback 2),
    2 of the step's own, build 10 of which upload 4 and account 3, grow 2
    inside the rows span, dispatch 5, ``readback``, 1 of the step's own,
    advance 8 of which account 2."""
    s = [("serving.admit", at, at + 10)]
    if wave:
        s += [("serving.account", at + 1, at + 2),
              ("serving.prefill", at + 2, at + 8),
              ("serving.build_inputs", at + 2, at + 5),
              ("serving.upload", at + 3, at + 5),
              ("serving.dispatch", at + 5, at + 6),
              ("serving.readback", at + 6, at + 8)]
    t = at + 12
    s += [("serving.build_inputs", t, t + 10),
          ("serving.upload", t + 1, t + 5),
          ("serving.account", t + 6, t + 9),
          ("serving.decode", t + 10, t + 17 + readback),
          ("serving.grow", t + 10, t + 12),
          ("serving.dispatch", t + 12, t + 17),
          ("serving.readback", t + 17, t + 17 + readback)]
    t += 17 + readback + 1
    s += [("serving.advance", t, t + 8), ("serving.account", t + 1, t + 3),
          ("serving.step", at, t + 8)]
    return s, t + 8


def test_every_microsecond_goes_to_the_innermost_name():
    plain, end = _tick(1000)
    waved, end2 = _tick(end + 50, wave=True)
    # a tick that returns before the device seam, and one outside the window
    early = [("serving.step", end2 + 10, end2 + 14),
             ("serving.admit", end2 + 10, end2 + 13)]
    late, _ = _tick(end2 + 100)
    ticks = th.split_ticks(plain + waved + early + late, 0, end2 + 50)
    assert len(ticks) == 2
    assert ticks[0] == {
        "serving.admit": 10, "serving.step": 3, "serving.build_inputs": 3,
        "serving.upload": 4, "serving.account": 5, "serving.grow": 2,
        "serving.dispatch": 5, "serving.readback": 60, "serving.advance": 6}
    assert sum(ticks[0].values()) == end - 1000
    # the wave's own phases count under their names, not under admit
    assert ticks[1] == {
        "serving.admit": 3, "serving.step": 3, "serving.build_inputs": 4,
        "serving.upload": 6, "serving.account": 6, "serving.grow": 2,
        "serving.dispatch": 6, "serving.readback": 62, "serving.advance": 6}
    assert sum(ticks[1].values()) == end2 - (end + 50)


def test_a_stall_is_summed_over_the_median_and_placed():
    at, spans = 0, []
    for k in range(9):
        # two ticks of nine stall (the unit is the caller's: milliseconds
        # to the readers), one inside its readback, 3,000 over, and one
        # outside every phase, 400 over
        one, at = _tick(at, readback={4: 3060}.get(k, 60))
        if k == 7:
            (name, s, e), = [sp for sp in one if sp[0] == "serving.step"]
            one = [sp for sp in one if sp[0] != "serving.step"] \
                + [(name, s, e + 400)]
            at += 400
        spans += one
    ticks = th.split_ticks(spans, 0, at)
    assert len(ticks) == 9
    stall, in_readback = th.stall_sums(ticks)
    assert (stall, in_readback) == (3000 + 400, 3000)
    # and a part's mean is over the seven that did not
    assert th.calm(ticks) == [t for k, t in enumerate(ticks)
                              if k not in (4, 7)]
    assert all(t["serving.readback"] == 60 for t in th.calm(ticks))
    # under the threshold nothing is a stall
    calm = th.split_ticks([sp for k in range(5)
                           for sp in _tick(200 * k, readback=60 + 40 * k)[0]],
                          0, 2000)
    assert th.stall_sums(calm) == (0, 0)


def test_the_recorded_trace_is_a_parent_to_the_split():
    """The chip-recorded trace is of a program without ``serving.upload``:
    its ticks split into the six phases and the step's own time, which sum
    to each ``serving.step``; it has none of the two costs."""
    from jax.profiler import ProfileData
    with gzip.open(TRACE) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    spans = tr.host_spans(profile, prefix="serving.")
    steps = sorted((s, e) for n, s, e in spans if n == th.STEP)
    ticks = th.split_ticks(spans, steps[0][0], steps[-1][1])
    assert len(ticks) == len(steps) == 11
    for (s, e), parts in zip(steps, ticks):
        assert sum(parts.values()) == e - s
        assert not {th.UPLOAD, th.ACCOUNT} & set(parts)
        assert parts["serving.readback"] > parts["serving.dispatch"] > 0
    # a wave tick (the first) spends in its phases what a plain one does not
    assert ticks[0]["serving.admit"] > ticks[1]["serving.admit"]


@pytest.fixture(scope="module")
def tiny_run():
    """A tiny paged chunked engine driven the way ``serve.drive`` does."""
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
                        block_len=8, chunked=True, prefill_chunk=16)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(1, 256, 5).astype(np.int32), max_new_tokens=2)
    eng.drain()                                  # the warm-up request
    w0 = time.perf_counter()
    for n in (5, 40, 20):
        eng.submit(rng.integers(1, 256, n).astype(np.int32),
                   max_new_tokens=6)
    ticks = []
    while eng.num_active or eng.queue_depth or eng.num_pending:
        t_a = time.perf_counter()
        eng.step()
        ticks.append((t_a, time.perf_counter(), eng.last_occupancy, 0))
    eng.step()                                   # idle: returns at once
    return {"window": (w0, time.perf_counter()), "ticks": ticks,
            "operands": len(eng._step_table)}


def test_the_seven_metrics_read_the_ring(tiny_run):
    got = {name: mf.load_metric(name).read(tiny_run) for name in METRICS}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["host.stall_ms"] == got["host.stall_readback_ms"] == 0.0
    assert all(got[name] > 0 for name in METRICS[:5])
    note = th.summary(tiny_run)
    assert note["ticks"] == len(tiny_run["ticks"]) == note["harness_ticks"]
    assert note["stalled_ticks"] == 0
    assert note["ring_tick_ms"] == note["ring_tick_all_ms"]
    # the five parts and the readback are the ring's tick; the harness's
    # clock around step() holds the span's own open and close besides
    assert sum(got[name] for name in METRICS[:5]) + note["readback_ms"] \
        == pytest.approx(note["ring_tick_ms"], rel=1e-9)
    assert note["ring_tick_ms"] < note["harness_tick_ms"]
    assert note["uploads_a_tick"] == 1
    assert note["operands_a_tick"] == tiny_run["operands"]
    assert note["ring_dropped"] == 0 and note["events_a_tick"] <= 14


def test_against_a_parent_every_reader_is_none(tiny_run):
    from paddle_tpu import observability as obs
    tracer = obs.get_tracer()
    kept = tracer.events()
    parent = {k: v for k, v in tiny_run.items() if k != "tick_host"}
    tracer.clear()
    try:
        for ev in kept:         # the ring of a program without the costs
            if ev["name"] not in (th.UPLOAD, th.ACCOUNT):
                tracer._append(ev)
        for name in METRICS:
            assert mf.load_metric(name).read(parent) is None, name
        assert th.summary(parent) is None
        # the accepted readers of the ring go on reading it
        assert mf.load_metric("step.weight_passes").read(parent) == 1
    finally:
        tracer.clear()
        for ev in kept:
            tracer._append(ev)
