"""The prefill program's row count follows what a row costs (ISSUE 41).

Below ``engine._ROW_FILLS_CHIP`` positions a row's products are bound by the
weights' stream: rows beside it ride for nothing, so a wave is padded to
``prefill_batch`` rows as it ever was.  From that bucket on a row is bound
by compute: a dummy row costs what a real one does, so each request is
prefilled ALONE, in a one-row program at its own bucket.  One program a
bucket either way.  Held here on a tiny llama, for the paged pool and the
contiguous cache, a prefix hit, the int8 pool and a recompute resume:
(a) the ``serving.prefill`` spans say the rows each call was padded to, and a
wave that mixes short and long prompts shares one call among the short ones;
(b) the tokens are, request by request, those of the same requests served
with every wave whole; (c) one program a bucket: whichever way a bucket is
first met, nothing traces or compiles at it afterwards.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving import engine as engine_mod

MAXLEN, BL, NB = 64, 8, 4
# the tiny model's prompts are tiny: the bucket from which a row goes alone,
# brought down from the chip's 256 positions (the last test runs at 256)
LONE = 32
CASES = {
    "paged": dict(paged=True, prefix_cache=False),
    "contiguous": dict(paged=False),
    "prefix-hit": dict(paged=True, prefix_cache=True),
    "int8": dict(paged=True, prefix_cache=True, kv_cache_dtype="int8"),
    "recompute": dict(paged=True, preempt="recompute", num_blocks=13),
}


@pytest.fixture(scope="module")
def lm():
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    return model


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 256, n).astype(np.int32)


def _engine(lm, case, whole_waves=False, **over):
    eng = ServingEngine(lm, **{**dict(num_slots=6, max_length=MAXLEN,
                                      block_len=BL, prefill_batch=NB,
                                      seed=3), **CASES[case], **over})
    assert eng._lone_from == engine_mod._ROW_FILLS_CHIP == 256
    # every wave whole, as before ISSUE 41; or long from ``LONE`` on
    eng._lone_from = MAXLEN + 1 if whole_waves else LONE
    return eng


def _waves():
    return [e["args"] for e in obs.get_tracer().events()
            if e["name"] == "serving.prefill"]


def _serve(eng, case):
    """A trace of short and long prompts, alone and in bursts; returns the
    tokens by request, in submission order."""
    knob = SamplingParams(temperature=0.8, top_k=7)
    shared = _prompt(2 * BL, 40)
    rids = []
    if case == "recompute":
        # two low-priority requests decode; two high-priority arrivals find
        # the pool full and evict them; the victims' resumes are long by
        # then (prompt + committed tokens) and re-prefill alone
        rids += [eng.submit(_prompt(n, n), max_new_tokens=12, priority=0)
                 for n in (12, 10)]
        for _ in range(3):
            eng.step()
        rids += [eng.submit(_prompt(n, n), max_new_tokens=12, priority=5)
                 for n in (14, 20)]
        eng.drain()
        return [eng.result(r) for r in rids]
    # a long prompt alone, left to retire ...
    rids.append(eng.submit(np.append(shared, _prompt(5, 41)),
                           max_new_tokens=5))
    eng.drain()
    # ... a burst of two short prompts and two long ones, one sampling ...
    rids += [eng.submit(_prompt(n, n), max_new_tokens=6,
                        sampling=knob if n == 12 else None)
             for n in (9, 12, 20, 30)]
    eng.drain()
    # ... and one more alone, opening with the first one's two blocks
    rids.append(eng.submit(np.append(shared, _prompt(3, 42)),
                           max_new_tokens=5))
    eng.drain()
    return [eng.result(r) for r in rids]


# -- (a) the spans say the rows ----------------------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_the_span_says_the_rows_each_call_ran(lm, case):
    eng = _engine(lm, case)
    obs.get_tracer().clear()
    _serve(eng, case)
    waves = [(w["rows"], w["padded_rows"], w["bucket"], w["tokens"])
             for w in _waves()]
    # a short bucket's call is padded to NB rows, a long one's runs one
    assert all(padded == (1 if bucket >= LONE else NB) and rows <= padded
               for rows, padded, bucket, _ in waves)
    if case == "recompute":
        m = eng.metrics()["preempt"]
        assert sum(m["preemptions"].values()) > 0
        assert m["preemptions"] == m["resumes"]
        assert {(1, 1, 32), (2, NB, 16)} <= {w[:3] for w in waves}
        return
    # alone; the burst: its two short prompts share a call at THEIR bucket,
    # then each long one alone at its own; alone again (a contiguous wave
    # is a run of prompts of one bucket: two waves, the same calls)
    first, short, long_a, long_b, last = waves
    assert first == (1, 1, 32, 21)
    assert short == (2, NB, 16, 9 + 12)
    assert (long_a, long_b) == ((1, 1, 32, 20), (1, 1, 32, 30))
    if case in ("prefix-hit", "int8"):
        # the last prompt adopted the first one's two blocks: what is left
        # to compute is short, and a short row is padded as ever
        assert eng.kv.stats["prefix_hit_tokens"] == 2 * BL
        assert last == (1, NB, 8, 3)
    else:
        assert last == (1, 1, 32, 2 * BL + 3)


def test_a_burst_of_short_prompts_is_one_whole_wave(lm):
    """What batching rows is for — several short prompts in one tick — is
    as it was: one call, ``prefill_batch`` rows, the longest's bucket."""
    eng = _engine(lm, "paged")
    obs.get_tracer().clear()
    for burst in (1, 2, NB):
        for i in range(burst):
            eng.submit(_prompt(9 + i, burst * 10 + i), max_new_tokens=3)
        eng.drain()
    assert [(w["rows"], w["padded_rows"], w["bucket"]) for w in _waves()] \
        == [(1, NB, 16), (2, NB, 16), (NB, NB, 16)]
    assert eng.prefill_traces == 1


def test_an_engine_of_one_row_waves_has_one_table(lm):
    eng = ServingEngine(lm, num_slots=3, max_length=MAXLEN, prefill_batch=1)
    assert list(eng._wave_tables) == [1]
    assert eng._prefill_table is eng._wave_tables[1]
    obs.get_tracer().clear()
    for n in (9, 12):
        eng.submit(_prompt(n, n), max_new_tokens=3)
    eng.drain()
    assert [(w["rows"], w["padded_rows"]) for w in _waves()] == [(1, 1)] * 2
    assert eng.prefill_traces == 1


def test_a_row_runs_after_the_row_whose_blocks_it_adopted(lm):
    """Rows of one wave may share a prefix the wave itself computes: the
    later row adopted, at admission, blocks the earlier row's prefill is
    still to write.  In one call every layer's scatter precedes its read;
    split, the calls keep the wave's order."""
    a = _prompt(30, 90)                          # long: alone, bucket 32
    b = np.append(a[:2 * BL], _prompt(4, 91))    # its suffix is short
    c = _prompt(9, 92)

    def serve(whole):
        eng = _engine(lm, "prefix-hit", whole_waves=whole)
        obs.get_tracer().clear()
        rids = [eng.submit(p, max_new_tokens=5) for p in (c, a, b)]
        eng.drain()
        assert eng.kv.stats["prefix_hit_tokens"] == 2 * BL
        return ([eng.result(r) for r in rids],
                [(w["rows"], w["padded_rows"], w["bucket"], w["tokens"])
                 for w in _waves()])
    want, whole = serve(True)
    assert whole == [(3, NB, 32, 9 + 30 + 4)]
    got, waves = serve(False)
    assert waves == [(1, NB, 16, 9), (1, 1, 32, 30), (1, NB, 8, 4)]
    assert got == want


# -- (b) same tokens ---------------------------------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_tokens_are_those_of_whole_waves(lm, case):
    got = _serve(_engine(lm, case), case)
    obs.get_tracer().clear()
    want = _serve(_engine(lm, case, whole_waves=True), case)
    assert {w["padded_rows"] for w in _waves()} == {NB}
    assert all(len(toks) >= 5 for toks in want)
    if case != "recompute":
        # the sampling row draws by its wave's number and its row in it,
        # which are other numbers once the wave is split: greedy rows alone
        # are the same whatever shares their call
        assert len(got.pop(2)) == len(want.pop(2)) == 6
    assert got == want


# -- (c) one program a bucket ------------------------------------------------

@pytest.fixture(scope="module")
def compiles():
    from benchmark.harness.compile_log import CompileLog
    return CompileLog()


def _prefill_compiles(log):
    """The prefill programs XLA was asked to compile since the last call (a
    cache hit counts: the host traced and lowered to get there)."""
    rows, log.rows = log.rows, []
    return [name for name, _ in rows if "_prefill_impl" in name]


@pytest.mark.parametrize("first", ["alone", "burst"])
@pytest.mark.parametrize("case", ["paged", "contiguous", "int8"])
def test_a_bucket_has_one_program_however_it_is_first_met(lm, compiles,
                                                          case, first):
    """A warm-up of one prompt a bucket reaches every program the traffic
    can: the rows are the bucket's, not the wave's, so a burst that comes
    later, under load, finds its buckets' programs compiled."""
    eng = _engine(lm, case)
    _prefill_compiles(compiles)

    def wave(requests, seed):
        # lengths 10 and 24: a short bucket (16) and a long one (32)
        for i in range(requests):
            eng.submit(_prompt((10, 24)[i % 2], seed + i), max_new_tokens=3)
        eng.drain()
    wave(2 if first == "alone" else NB, 50)
    assert eng.prefill_traces == 2 and len(_prefill_compiles(compiles)) == 2
    wave(NB if first == "alone" else 2, 60)
    wave(1, 70)
    wave(NB, 80)
    assert eng.prefill_traces == 2 and _prefill_compiles(compiles) == []
    assert eng.step_traces == 1


def test_the_trace_budget_is_the_buckets(lm):
    """``max_length`` 64 allows the buckets 8, 16, 32 and 64: four
    programs, and the budget is exactly that (the watchdog raises in the
    suite at a fifth)."""
    eng = _engine(lm, "contiguous")
    assert eng._wave_buckets() == [8, 16, 32, 64]
    for n in (5, 12, 20, 40):
        eng.submit(_prompt(n, n), max_new_tokens=2)
        eng.drain()
    for n in (5, 12, 20, 40):
        for i in range(NB):
            eng.submit(_prompt(n - i, n + i), max_new_tokens=2)
        eng.drain()
    assert eng.prefill_traces == 4
    with pytest.raises(obs.RetraceError):
        # a fifth: the 16-bucket's program at the other row count
        eng._prefill_fn(*eng._lint_args(16, 1))


# -- at the chip's own threshold ---------------------------------------------

@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
def test_a_prompt_of_256_positions_goes_alone(paged):
    """Nothing brought down: a 200-token prompt's bucket is 256, where a row
    fills the chip; a 100-token prompt's is 128, where it does not."""
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(max_position_embeddings=512))
    model.eval()

    def serve(whole):
        eng = ServingEngine(model, num_slots=4, max_length=512, block_len=BL,
                            paged=paged, prefix_cache=False, seed=3)
        if whole:
            eng._lone_from = 513
        obs.get_tracer().clear()
        rids = [eng.submit(_prompt(n, n), max_new_tokens=4)
                for n in (100, 90, 200, 300)]
        eng.drain()
        return ([eng.result(r) for r in rids],
                [(w["rows"], w["padded_rows"], w["bucket"]) for w in _waves()])
    got, waves = serve(False)
    assert waves == [(2, NB, 128), (1, 1, 256), (1, 1, 512)]
    want, whole = serve(True)
    assert {w[1] for w in whole} == {NB}
    assert got == want
