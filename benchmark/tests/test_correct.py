"""``correct`` has to be able to come out false: the control (the reference
with int8-rounded weights in the program's place) and a run whose timed
path is broken underneath.  Tiny sizes, CPU, float32 program; the readings
on the chip at the cells' own sizes are in PERF.md."""

import time

import numpy as np
import pytest

from benchmark.harness import serve
from conftest import TINY, TINY_MIX, tiny_cell


def _run(cell, seed, **kw):
    lines = []
    run = serve.run(cell, TINY, TINY_MIX, seed=seed, seconds=1.0,
                    t_start=time.perf_counter(),
                    say=lambda w, f: lines.append((w, f)), **kw)
    return run, {c["name"]: c for c in run["checks"]}


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_sound_run_is_correct_and_the_int8_control_is_not(seed):
    cell = tiny_cell()
    cell["check"]["sample"] = 80
    run, rows = _run(cell, seed, control_bits=8)
    assert run["correct"], run["checks"]
    assert rows["served_gap_max"]["value"] <= 1e-5
    control = {c["name"]: c for c in run["control"]}
    # int8 rounding moves the first token at some of ~1000 positions by far
    # more than float32 association does
    assert not all(c["ok"] for c in run["control"]), run["control"]
    assert control["served_gap_max"]["value"] > 3 * max(
        rows["served_gap_max"]["value"], 1e-5)


@pytest.mark.parametrize("engine", [
    {"kv_cache_dtype": "int8"},
    {"kv_cache_dtype": "int8", "paged": True, "block_len": 16,
     "num_blocks": 33}], ids=["contiguous", "paged"])
def test_the_programs_own_int8_kv_path_is_not_correct(engine):
    """The lower-precision path the program has itself, switched on under
    the timed engine (``control.py --engine``): its served tokens lie
    further from the reference's best than the limit allows."""
    cell = tiny_cell(**engine)
    cell["check"]["sample"] = 40
    # sound float32 runs read 1e-5 at the most (the test above), int8 KV
    # some 5e-4: a limit between them with room on both sides
    cell["check"]["limits"]["served_gap_max"] = 5e-5
    run, rows = _run(cell, 11)
    assert not run["correct"]
    assert not rows["served_gap_max"]["ok"]


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    import paddle_tpu.serving as serving

    class Broken(serving.ServingEngine):
        """Every fifth step hands slot 0 the next token id instead."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            inner, calls = self._step_fn, [0]

            def step_fn(*args):
                nxt, cache = inner(*args)
                calls[0] += 1
                if calls[0] % 5 == 0:
                    nxt = np.array(nxt)
                    nxt[0] = (nxt[0] + 1) % TINY["vocab_size"]
                return nxt, cache

            self._step_fn = step_fn

    monkeypatch.setattr(serving, "ServingEngine", Broken)
    cell = tiny_cell()
    cell["check"]["sample"] = 40
    run, rows = _run(cell, 7)
    assert not run["correct"]
    assert not rows["served_gap_max"]["ok"]
    assert rows["served_gap_max"]["value"] > 0.01


def test_a_backlog_that_empties_is_not_correct():
    mix = dict(TINY_MIX, backlog_requests_per_s=20)
    run = serve.run(tiny_cell(), TINY, mix, seed=1, seconds=1.0,
                    t_start=time.perf_counter(), say=lambda w, f: None)
    rows = {c["name"]: c for c in run["checks"]}
    assert not rows["backlog_left_min"]["ok"] and not run["correct"]
