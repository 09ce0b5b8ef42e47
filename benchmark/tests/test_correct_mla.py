"""``correct`` for the latent-attention cell has to be able to come out
false: both controls (the reference with int8-rounded weights, and the
reference over ANOTHER document's tokens in the shared prefix's place) fail
it, and so does a program whose trie hands a request another document's
blocks.  The new per-layer readers read the run's record.  Tiny sizes, CPU,
float32 program; the readings on the chip at the cell's own size are in
PERF.md."""

import time

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import serve_mla
from tiny_mla import TINY, TINY_MIX, tiny_cell


def _run(**kw):
    return serve_mla.run(tiny_cell(), TINY, TINY_MIX, seed=2**31 + 5,
                         seconds=1.5, t_start=time.perf_counter(),
                         say=lambda w, f: None, **kw)


@pytest.fixture(scope="module")
def run():
    return _run(control_bits=8)


def test_sound_run_is_correct_and_both_controls_are_not(run):
    rows = {c["name"]: c for c in run["checks"]}
    assert run["correct"], run["checks"]
    assert rows["served_gap_max"]["value"] <= 2e-4
    control = {c["name"]: c for c in run["control"]}
    assert set(control) == {"served_gap_max", "served_gap_mean",
                            "other_doc.served_gap_max",
                            "other_doc.served_gap_mean"}
    assert not control["served_gap_mean"]["ok"]
    assert not control["other_doc.served_gap_max"]["ok"]
    assert not control["other_doc.served_gap_mean"]["ok"]
    assert run["failed"] == 0 and run["attempted"] > 0


def test_the_sample_holds_two_on_one_document_and_one_on_another():
    recs = [{"index": i, "prompt": [1] * (40 + i), "tokens": [1, 2],
             "temperature": 0.0, "tenant": t}
            for i, t in enumerate([0, 1, 0, 2, 1, 0, 2])]
    got = serve_mla.sample_shared(recs, 3, seed=9)
    assert got[0]["index"] == 6                 # the longest, tenant 2
    assert got[1]["tenant"] == 2 and got[2]["tenant"] != 2
    assert len(serve_mla.sample_shared(recs, 5, seed=9)) == 5
    assert len({r["index"] for r in serve_mla.sample_shared(recs, 5, 9)}) == 5


def test_the_trie_served_most_prompt_tokens_and_the_pool_holds_them_once(run):
    w0, w1 = run["window"]
    rows = [(p, h) for t, p, h in run["admissions"] if w0 <= t <= w1]
    assert rows and all(h in (0, 32) for _, h in rows)
    hit = mf.load_metric("cache.prefix_hit_pct").read(run)
    assert hit == pytest.approx(
        100 * sum(h for _, h in rows) / sum(p for p, _ in rows))
    assert 50 < hit < 100
    # live positions: a document once, not once a row
    naive = [sum(len(r.req.prompt) + sum(1 for x in r.times if x <= tk[1])
                 for r in run["order"] if r.times and r.times[0] <= tk[1]
                 and not (r.done and r.times[-1] < tk[1]))
             for tk in run["ticks"]]
    mine = [tk[3] for tk in run["ticks"]]
    assert all(m <= n for m, n in zip(mine, naive)) and sum(mine) < sum(naive)
    live = mf.load_metric("cache.live_kv_pct").read(run)
    assert 0 < live < 100
    assert mf.load_metric("cache.prefix_hit_pct").read(
        dict(run, admissions=[])) is None


def test_span_readers(run):
    ratio = mf.load_metric("cache.shared_walk_ratio").read(run)
    assert 1.0 < ratio < 4.0            # at most 4 rows on one document
    share = mf.load_metric("sched.chunk_tick_pct").read(run)
    assert 0.0 < share <= 100.0
    walk = mf.load_metric("kernel.decode_walk_live_pct").read(run)
    assert 0.0 < walk <= 100.0


def test_roofline_reader_on_a_recorded_shape_of_trace(run):
    """The device-trace reader against a hand-made reduced trace with the
    kernel's name as the program gives it: the least time of the spans'
    distinct positions and summed depths over the rows' kernel's seconds;
    the chunk's kernel is left out; nothing to read without the kernel."""
    from benchmark.harness import (engine_spans, flops_bytes,
                                   flops_bytes_mla, peaks)
    cfg = dict(TINY, dtype="bfloat16")
    rec = dict(run, config=cfg, peaks=peaks.peaks_for("TPU v5 lite"),
               trace_slice=run["window"],
               trace={"ops": {
                   "pallas:_step_impl_decode_rows_latent_flash_decode:"
                   "bf16[4,1,8,128]": (3e-6, 10),
                   "pallas:_step_impl_prompt_chunk_latent_flash_decode:"
                   "bf16[1,1,32,128]": (5.0, 10),
                   "fusion:bf16[3,64]": (9.0, 99)}})
    spans = engine_spans.ring_spans(run, "serving.decode")
    least = sum(flops_bytes.roofline_seconds(
        *flops_bytes_mla.decode_rows_attention(
            cfg, a["rows_positions"], a["rows_depth"]), rec["peaks"])[0]
        for _, a in spans)
    got = mf.load_metric("kernel.mla_decode_attn_roofline").read(rec)
    assert got == pytest.approx(100 * least / 3e-6, rel=1e-6)
    assert mf.load_metric("kernel.mla_decode_attn_roofline").read(
        dict(rec, trace={"ops": {"fusion:bf16[3,64]": (9.0, 99)}})) is None
    # the least bytes are the entry's values, not its stored lanes
    flops, nbytes = flops_bytes_mla.decode_rows_attention(cfg, 100, 300)
    assert nbytes == 160 * 2 * 100 * 3
    assert flops == 2 * 4 * (160 + 128) * 300 * 3
    assert flops_bytes_mla.kv_bytes_per_position(cfg) == 3 * 256 * 2


def test_a_trie_that_hands_out_another_documents_blocks_is_not_correct(
        monkeypatch):
    """The program's fault the other-document control stands for: every
    request's prefix is looked up as document 0's, so a request on another
    document adopts document 0's blocks and reads them."""
    from paddle_tpu.serving.kv_cache import BlockManager
    doc0 = np.random.default_rng([0x5EED, 0]).integers(1, 256, 32)
    real = BlockManager.admit

    def admit(self, slot, prompt, prompt_len, *a, **kw):
        looked_up = np.array(prompt, np.int32)
        looked_up[:32] = doc0
        return real(self, slot, looked_up, prompt_len, *a, **kw)
    monkeypatch.setattr(BlockManager, "admit", admit)
    bad = _run()
    rows = {c["name"]: c for c in bad["checks"]}
    assert not bad["correct"]
    assert not rows["served_gap_mean"]["ok"]


def test_only_a_request_that_could_have_no_token_yet_is_left_out():
    """The chunks a prompt needs are counted from what the trie did not
    serve: a request that adopted its document needs one chunk, not five."""
    from types import SimpleNamespace as NS

    def rec(index, prompt_len, slot, times):
        return NS(req=NS(index=index, prompt=[1] * prompt_len), slot=slot,
                  times=times, due=0.0)
    ticks = [(t - 0.1, float(t), 2, 0) for t in range(1, 11)]
    stamps = {"window": (2.0, 10.0), "t_zero": 0.0, "ticks": ticks}
    streaming = rec(1, 40, 8.0, [])        # 5 chunks of 8, 3 ticks had
    adopted = rec(2, 40, 8.0, [])          # 32 adopted: 1 chunk, 3 ticks
    m = {"judged": [streaming, adopted], "failed": 2,
         "ttft_ms": [8000.0, 8000.0], "queue_wait_ms": [0, 0]}
    cell = {"engine": {"chunked": True, "prefill_chunk": 8}}
    kept, out = serve_mla.mid_prefill_at_end(
        m, stamps, cell, {id(adopted): (8.0, 40, 32)})
    assert [r.req.index for r in kept["judged"]] == [2]
    assert kept["failed"] == 1
    assert out == [{"index": 1, "prompt_tokens": 40, "adopted_tokens": 0,
                    "chunks_needed": 5, "ticks_had": 3}]
