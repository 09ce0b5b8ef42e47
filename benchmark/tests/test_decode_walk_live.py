"""``kernel.decode_walk_live_pct`` against the tracer's ring as a tiny
engine leaves it on the CPU: the ticks' ``kv_blocks`` over their ``kv_walk``,
held to the kernel's own bounds, and a ring whose spans carry no such args
(a program from before them)."""

import time

import numpy as np

from benchmark.harness import engine_spans as es
from benchmark.harness import manifest as mf
from benchmark.tests.test_sample_sort_skipped import drive, engine  # noqa: F401

READ = mf.load_metric("kernel.decode_walk_live_pct").read


def test_the_share_is_read_from_the_ring(engine):  # noqa: F811
    from paddle_tpu import observability as obs
    from paddle_tpu.ops.pallas.decode_attention import (group_blocks,
                                                        walk_counts)

    obs.reset()
    calls, walk_of = [], engine._kv_walk
    engine._kv_walk = lambda *c: calls.append(
        [(np.array(p), s) for p, s in c]) or walk_of(*c)
    try:
        run = drive(engine, [None, None], new_tokens=20)
    finally:
        del engine._kv_walk
    ticks = es.ring_spans(run, "serving.decode")
    assert len(ticks) >= 20
    need = sum(a["kv_blocks"] for _, a in ticks)
    walk = sum(a["kv_walk"] for _, a in ticks)
    assert 0 < need < walk          # 8-position blocks in groups of 64
    assert READ(run) == 100.0 * need / walk
    # a tick's args are the kernel's bounds over the positions it uploads,
    # its rows' and its chunk part's (chunk-free at the end: position 0),
    # on every layer: the last tick's deepest row is four blocks deep, and
    # every row's walk, an idle row's one block too, is one group
    c = engine.config
    g = c.num_attention_heads // c.num_key_value_heads
    geom = dict(bk=engine.block_len, n_cols=engine.max_blocks)
    (rows_pos, rows_s), (chunk_pos, chunk_s) = calls[-1]
    assert max(rows_pos) // engine.block_len == 3
    assert (rows_s, list(chunk_pos), chunk_s) == (1, [0],
                                                  engine.prefill_chunk)
    rows = walk_counts(rows_pos, 1, g, **geom)
    chunk = walk_counts([0], engine.prefill_chunk, g, **geom)
    assert rows == (sum(int(p) // engine.block_len + 1 for p in rows_pos),
                    len(rows_pos) * group_blocks(engine.block_len))
    last = ticks[-1][1]
    assert (last["kv_blocks"], last["kv_walk"]) == tuple(
        c.num_hidden_layers * (r + k) for r, k in zip(rows, chunk))
    # each window reads its own ticks only
    again = drive(engine, [None], new_tokens=3)
    assert READ(again) != READ(run)


def test_none_where_the_spans_carry_no_such_args():
    """The parent's ``serving.decode`` spans have ``slots`` and
    ``sample_path``; a window with no tick at all reads None too."""
    from paddle_tpu import observability as obs

    obs.reset()
    w0 = time.perf_counter()
    assert READ({"window": (w0, time.perf_counter())}) is None
    for _ in range(3):
        with obs.get_tracer().span("serving.decode", slots=2,
                                   sample_path="greedy"):
            time.sleep(0.001)
    run = {"window": (w0, time.perf_counter())}
    assert len(es.ring_spans(run, "serving.decode")) == 3
    assert READ(run) is None
