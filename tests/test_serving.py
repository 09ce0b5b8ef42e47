"""Continuous-batching serving engine (paddle_tpu/serving).

The gold-standard property mirrors test_generation.py's: the engine's
greedy output for a prompt must be TOKEN-IDENTICAL to the whole-scan
``greedy_generate`` for the same prompt — regardless of which slot the
request lands in, what else shares the batch, or when it was admitted.
On top of that, the step function must compile exactly once (the
continuous-batching premise: no per-request retraces).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.serving import Request, SamplingParams, ServingEngine

MAXLEN = 64


@pytest.fixture(scope="module")
def lm():
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    return model


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def _reference(lm, prompt, n_new, eos=None):
    """greedy_generate run at the ENGINE's cache length, truncated at EOS
    inclusive — the engine emits no pad tail."""
    out = np.asarray(lm.generate(jnp.asarray(prompt[None], jnp.int32),
                                 max_new_tokens=n_new, max_length=MAXLEN,
                                 eos_token_id=eos))[0, len(prompt):]
    if eos is not None:
        hits = np.where(out == eos)[0]
        if hits.size:
            out = out[:hits[0] + 1]
    return list(int(t) for t in out)


def test_greedy_parity_across_staggered_waves(lm):
    """≥3 admission waves, mixed prompt lengths, fewer slots than
    requests: every output token-identical to greedy_generate, and the
    step function traced exactly once."""
    prompts = [_prompt(n, seed=10 + i)
               for i, n in enumerate((5, 9, 7, 12, 6, 10))]
    eng = ServingEngine(lm, num_slots=3, max_length=MAXLEN)
    rids = [eng.submit(prompts[0], max_new_tokens=8),
            eng.submit(prompts[1], max_new_tokens=8)]          # wave 1
    eng.step()
    eng.step()
    rids.append(eng.submit(prompts[2], max_new_tokens=8))      # wave 2
    eng.step()
    rids += [eng.submit(prompts[3], max_new_tokens=8),
             eng.submit(prompts[4], max_new_tokens=8),
             eng.submit(prompts[5], max_new_tokens=8)]         # wave 3
    results = dict(eng.drain())
    assert eng.step_traces == 1, (
        f"step function retraced: {eng.step_traces} traces")
    for i, rid in enumerate(rids):
        want = _reference(lm, prompts[i], 8)
        assert results[rid] == want, (
            f"request {i} diverged from greedy_generate: "
            f"{results[rid]} != {want}")


def test_arrival_order_and_drain(lm):
    """drain() returns outputs in submission order even when later short
    requests finish before earlier long ones."""
    long_p, short_p = _prompt(6, seed=21), _prompt(4, seed=22)
    eng = ServingEngine(lm, num_slots=4, max_length=MAXLEN)
    r0 = eng.submit(long_p, max_new_tokens=12)
    r1 = eng.submit(short_p, max_new_tokens=2)
    out = eng.drain()
    assert [rid for rid, _ in out] == [r0, r1]
    assert out[0][1] == _reference(lm, long_p, 12)
    assert out[1][1] == _reference(lm, short_p, 2)


def test_slot_reuse_after_eos(lm):
    """One slot, several requests, EOS mid-stream: the freed slot must be
    recycled and the recycled run must not see the previous tenant's KV."""
    p1, p2 = _prompt(8, seed=32), _prompt(5, seed=33)
    # find a prompt whose greedy stream contains a token FIRST occurring
    # mid-stream — that token as EOS forces a genuine mid-run retirement
    # (tiny random models often repeat one token, so probe a few seeds)
    p0 = eos = cut = None
    for seed in range(31, 63):
        cand = _prompt(5, seed=seed)
        ref = _reference(lm, cand, 8)
        firsts = [j for j, t in enumerate(ref) if ref.index(t) == j]
        mid = [j for j in firsts if 1 <= j < 7]
        if mid:
            p0, cut = cand, mid[0]
            eos = ref[cut]
            break
    assert p0 is not None, "no probe prompt produced a mid-stream token"
    eng = ServingEngine(lm, num_slots=1, max_length=MAXLEN,
                        eos_token_id=eos)
    rids = [eng.submit(p, max_new_tokens=8) for p in (p0, p1, p2)]
    results = dict(eng.drain())
    assert eng.step_traces == 1
    for rid, p in zip(rids, (p0, p1, p2)):
        assert results[rid] == _reference(lm, p, 8, eos=eos)
    # p0 retired AT its EOS mid-stream (truncation actually happened)
    assert len(results[rids[0]]) == cut + 1
    assert results[rids[0]][-1] == eos


def test_mixed_length_batch_correctness(lm):
    """Prompts of very different lengths admitted together (one padded
    prefill bucket + one sub-bucket) decode correctly side by side."""
    prompts = [_prompt(n, seed=40 + i) for i, n in enumerate((3, 15, 8, 13))]
    eng = ServingEngine(lm, num_slots=4, max_length=MAXLEN, prefill_batch=4)
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    results = dict(eng.drain())
    for rid, p in zip(rids, prompts):
        assert results[rid] == _reference(lm, p, 6)
    # buckets 8 and 16 → at most two compiled prefill programs
    assert eng.prefill_traces <= 2


def test_mixed_sampling_params_share_the_batch(lm):
    """A sampled request riding next to greedy ones must not perturb the
    greedy rows (per-slot sampling vectors, one program)."""
    g0, g1, s0 = _prompt(5, seed=51), _prompt(7, seed=52), _prompt(6, 53)
    eng = ServingEngine(lm, num_slots=3, max_length=MAXLEN, seed=3)
    rg0 = eng.submit(g0, max_new_tokens=6)
    rs = eng.submit(s0, max_new_tokens=6,
                    sampling=SamplingParams(temperature=0.9, top_k=8,
                                            top_p=0.95))
    rg1 = eng.submit(g1, max_new_tokens=6)
    results = dict(eng.drain())
    assert eng.step_traces == 1
    assert results[rg0] == _reference(lm, g0, 6)
    assert results[rg1] == _reference(lm, g1, 6)
    assert len(results[rs]) == 6
    assert all(0 <= t < lm.config.vocab_size for t in results[rs])


def test_quantized_model_serves(lm):
    """quantize_for_decode-wrapped models ride the same engine (packed
    params prepared in-graph) and match their own generate() output."""
    from paddle_tpu.models.quantized import quantize_for_decode

    qlm = quantize_for_decode(lm)
    p = _prompt(6, seed=61)
    want = np.asarray(qlm.generate(jnp.asarray(p[None], jnp.int32),
                                   max_new_tokens=5, max_length=MAXLEN))
    eng = ServingEngine(qlm, num_slots=2, max_length=MAXLEN)
    rid = eng.submit(p, max_new_tokens=5)
    results = dict(eng.drain())
    assert results[rid] == [int(t) for t in want[0, len(p):]]


def test_submit_validation(lm):
    eng = ServingEngine(lm, num_slots=2, max_length=16)
    with pytest.raises(ValueError, match="max_length"):
        eng.submit(_prompt(10, seed=71), max_new_tokens=8)
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(_prompt(4, seed=72), max_new_tokens=0)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        ServingEngine(lm, num_slots=2, max_length=4096)


def test_recurrent_models_rejected():
    from paddle_tpu.models.mamba import Mamba2ForCausalLM, tiny_mamba2_config

    pt.seed(9)
    model = Mamba2ForCausalLM(tiny_mamba2_config())
    model.eval()
    with pytest.raises(NotImplementedError,
                       match="does not declare it as serving state"):
        ServingEngine(model, num_slots=2, max_length=32)


def test_idle_step_skips_device_dispatch(lm):
    """An idle tick (empty queue, no active slots — a server polling for
    traffic) must return immediately without dispatching the fully-masked
    decode step to the device."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
    real = eng._step_fn

    def boom(*a, **k):
        raise AssertionError("idle tick dispatched a device decode step")

    eng._step_fn = boom
    try:
        for _ in range(3):
            assert eng.step() == []
        assert eng.last_occupancy == 0
        assert eng._ticks == 0          # no device work was even counted
    finally:
        eng._step_fn = real
    # the engine still serves normally after idling
    p = _prompt(4, seed=91)
    rid = eng.submit(p, max_new_tokens=2)
    assert dict(eng.drain())[rid] == _reference(lm, p, 2)


def _staggered_trace(eng, long_p, shorts):
    """Two short decodes in flight, a LONG prompt arrives mid-decode,
    two more shorts queue behind it — the head-of-line-blocking trace."""
    rids = [eng.submit(shorts[0], max_new_tokens=10),
            eng.submit(shorts[1], max_new_tokens=10)]
    eng.step()
    eng.step()
    rids.append(eng.submit(long_p, max_new_tokens=6))
    eng.step()
    rids += [eng.submit(shorts[2], max_new_tokens=8),
             eng.submit(shorts[3], max_new_tokens=8)]
    return rids, dict(eng.drain())


def test_chunked_engine_matches_wave_engine(lm):
    """ISSUE 5 acceptance: the mixed-step (chunked prefill) engine's
    greedy outputs are token-identical to the wave engine on a staggered
    trace where a long prompt arrives while short requests are
    mid-decode, with the mixed step compiled exactly once (the armed
    watchdog raises on any retrace)."""
    long_p = _prompt(40, seed=70)
    shorts = [_prompt(n, seed=71 + i) for i, n in enumerate((5, 7, 6, 9))]
    wave = ServingEngine(lm, num_slots=3, max_length=MAXLEN)
    rw, outw = _staggered_trace(wave, long_p, shorts)
    ck = ServingEngine(lm, num_slots=3, max_length=MAXLEN, chunked=True,
                       prefill_chunk=8)
    rc, outc = _staggered_trace(ck, long_p, shorts)
    assert ck.step_traces == 1, (
        f"mixed step retraced: {ck.step_traces} traces")
    assert ck.prefill_traces == 0      # no wave-prefill programs at all
    for a, b in zip(rw, rc):
        assert outw[a] == outc[b], (outw[a], outc[b])
    # the long prompt really streamed in chunks (40 tokens / 8 = 5)
    m = ck.metrics()["chunked"]
    assert m["prefill_chunks"] >= 5 + len(shorts)
    assert m["chunk_queue_depth"]["count"] > 0
    # and the long output matches greedy_generate directly too
    assert outc[rc[2]] == _reference(lm, long_p, 6)


def test_chunked_decode_priority_policy_parity(lm):
    """chunk_policy='decode' (chunks interleave with chunk-free ticks)
    changes scheduling, never tokens."""
    long_p = _prompt(26, seed=75)
    shorts = [_prompt(n, seed=76 + i) for i, n in enumerate((5, 7, 6, 9))]
    wave = ServingEngine(lm, num_slots=3, max_length=MAXLEN)
    rw, outw = _staggered_trace(wave, long_p, shorts)
    ck = ServingEngine(lm, num_slots=3, max_length=MAXLEN, chunked=True,
                       prefill_chunk=8, chunk_policy="decode")
    rc, outc = _staggered_trace(ck, long_p, shorts)
    assert ck.step_traces == 1
    for a, b in zip(rw, rc):
        assert outw[a] == outc[b]


def test_chunked_single_chunk_and_eos_at_first_token(lm):
    """A prompt shorter than the chunk budget completes in one mixed
    step; retirement at the first token (max_new_tokens=1) works from
    the chunk-completion path."""
    p = _prompt(5, seed=85)
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN, chunked=True,
                        prefill_chunk=16)
    r0 = eng.submit(p, max_new_tokens=1)
    r1 = eng.submit(_prompt(7, seed=86), max_new_tokens=4)
    out = dict(eng.drain())
    assert out[r0] == _reference(lm, p, 1)
    assert len(out[r0]) == 1
    assert out[r1] == _reference(lm, _prompt(7, seed=86), 4)


def test_queue_accounting_under_chunked_admission(lm):
    """ISSUE 5 satellite: a request queued across many ticks has its
    queue-wait recorded ONCE at admission (not per chunk), and
    queue_depth is correct between submit() and the first step()."""
    eng = ServingEngine(lm, num_slots=1, max_length=MAXLEN, chunked=True,
                        prefill_chunk=4)
    p0, p1 = _prompt(18, seed=80), _prompt(6, seed=81)
    r0 = eng.submit(p0, max_new_tokens=2)
    r1 = eng.submit(p1, max_new_tokens=2)
    # between submit() and the first step() nothing is admitted yet
    assert eng.queue_depth == 2
    eng.step()
    # head admitted into the slot (prefilling); the second still queued
    assert eng.queue_depth == 1
    assert eng._m_queue_wait.count == 1
    for _ in range(4):                 # 18/4 -> 5 chunks; r1 stays queued
        eng.step()
    assert eng._m_queue_wait.count == 1, (
        "queue-wait re-observed per chunk")
    out = dict(eng.drain())
    assert eng._m_queue_wait.count == 2   # exactly once per request
    assert out[r0] == _reference(lm, p0, 2)
    assert out[r1] == _reference(lm, p1, 2)


def test_queue_depth_between_submit_and_step_wave(lm):
    """Same queue_depth contract for the wave engine (regression guard
    for the accounting audit)."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
    for i in range(3):
        eng.submit(_prompt(4 + i, seed=90 + i), max_new_tokens=2)
    assert eng.queue_depth == 3
    eng.step()
    assert eng.queue_depth <= 1
    assert eng._m_queue_wait.count >= 2   # admitted requests observed once
    eng.drain()
    assert eng._m_queue_wait.count == 3


def test_chunked_idle_step_skips_device_dispatch(lm):
    """The idle-tick contract holds in chunked mode: no queue, no active
    slot, no prefill cursor — no device dispatch."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN, chunked=True)
    real = eng._step_fn

    def boom(*a, **k):
        raise AssertionError("idle tick dispatched a mixed step")

    eng._step_fn = boom
    try:
        for _ in range(3):
            assert eng.step() == []
        assert eng._ticks == 0
    finally:
        eng._step_fn = real
    p = _prompt(4, seed=95)
    rid = eng.submit(p, max_new_tokens=2)
    assert dict(eng.drain())[rid] == _reference(lm, p, 2)


class _ScriptedDrafter:
    """Test drafter: proposes each request's KNOWN greedy continuation
    (so windows verify fully), optionally corrupting the draft at a
    fixed offset (forcing a mid-window rejection + rollback at a
    deterministic point).  ``refs``: [(prompt, ref_stream)]."""

    def __init__(self, refs, k, corrupt_at=None, vocab=None):
        self.refs = sorted(refs, key=lambda pr: -len(pr[0]))
        self.k, self.corrupt_at, self.vocab = k, corrupt_at, vocab

    def propose(self, history):
        hist = [int(t) for t in history]
        for p, ref in self.refs:
            lp = len(p)
            if hist[:lp] == [int(t) for t in p]:
                g = len(hist) - lp            # generated so far
                prop = list(ref[g:g + self.k])
                if self.corrupt_at is not None \
                        and self.corrupt_at < len(prop):
                    prop[self.corrupt_at] = (
                        (prop[self.corrupt_at] + 1) % self.vocab)
                return np.asarray(prop, np.int32)
        return np.zeros((0,), np.int32)


def test_spec_decode_parity_staggered(lm):
    """ISSUE 7 acceptance (contiguous): the spec engine's greedy outputs
    are token-identical to the plain engine's on the staggered trace —
    with the real n-gram self-drafter proposing (and the model
    rejecting some of it: real rollbacks) — and the verify step
    compiled exactly once under the armed watchdog."""
    long_p = _prompt(40, seed=70)
    shorts = [_prompt(n, seed=71 + i) for i, n in enumerate((5, 7, 6, 9))]
    plain = ServingEngine(lm, num_slots=3, max_length=MAXLEN)
    rp, outp = _staggered_trace(plain, long_p, shorts)
    spec = ServingEngine(lm, num_slots=3, max_length=MAXLEN,
                         spec_decode=True, spec_k=4)
    rs, outs = _staggered_trace(spec, long_p, shorts)
    assert spec.step_traces == 1, (
        f"verify step retraced: {spec.step_traces} traces")
    for a, b in zip(rp, rs):
        assert outp[a] == outs[b], (outp[a], outs[b])
    m = spec.metrics()["spec"]
    assert m["drafted_tokens"] > 0            # the drafter really fired
    # committed-token accounting: tok counters move by COMMITTED tokens
    assert int(spec._m_tokens.value()) == sum(
        len(outs[r]) for r in rs)


def test_spec_chunked_parity_staggered(lm):
    """spec × chunked (contiguous): the mixed verify step matches the
    wave engine token for token while a long prompt streams in chunks —
    one compiled program, prefill suspended rows drafting nothing."""
    long_p = _prompt(40, seed=70)
    shorts = [_prompt(n, seed=71 + i) for i, n in enumerate((5, 7, 6, 9))]
    wave = ServingEngine(lm, num_slots=3, max_length=MAXLEN)
    rw, outw = _staggered_trace(wave, long_p, shorts)
    ck = ServingEngine(lm, num_slots=3, max_length=MAXLEN, chunked=True,
                       prefill_chunk=8, spec_decode=True, spec_k=3)
    rc, outc = _staggered_trace(ck, long_p, shorts)
    assert ck.step_traces == 1
    assert ck.prefill_traces == 0
    for a, b in zip(rw, rc):
        assert outw[a] == outc[b], (outw[a], outc[b])
    assert ck.metrics()["spec"]["drafted_tokens"] > 0


def test_spec_forced_midwindow_rejection_rolls_back(lm):
    """A drafter scripted to corrupt draft #3 forces a rejection INSIDE
    every window: rows must commit exactly the verified prefix (3
    tokens: 2 verified drafts + the bonus), roll back the rest, and the
    stream must stay token-identical to plain greedy decode."""
    p = _prompt(6, seed=140)
    ref = _reference(lm, p, 12)
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN,
                        spec_decode=True, spec_k=4)
    eng._drafter = _ScriptedDrafter([(p, ref)], k=4, corrupt_at=2,
                                    vocab=lm.config.vocab_size)
    rid = eng.submit(p, max_new_tokens=12)
    out = dict(eng.drain())
    assert out[rid] == ref
    m = eng.metrics()["spec"]
    assert m["rollbacks"] >= 2                # every full window rejected
    assert m["draft_miss_tokens"] >= 2
    # the accepted-per-step histogram saw the 3-token commits
    bc = eng._m_spec_accept.bucket_counts()
    assert bc["3"] - bc["2"] >= 1             # cumulative → per-bucket


def test_spec_eos_inside_accepted_window(lm):
    """EOS landing mid-window: the row must stop AT the EOS (tokens
    after it in the verified window are discarded), retire with reason
    'eos', and match the EOS-truncated reference exactly."""
    p0 = eos = cut = None
    for seed in range(31, 80):
        cand = _prompt(5, seed=seed)
        ref = _reference(lm, cand, 10)
        firsts = [j for j, t in enumerate(ref) if ref.index(t) == j]
        mid = [j for j in firsts if 2 <= j <= 4]
        if mid:
            p0, cut = cand, mid[0]
            eos = ref[cut]
            break
    assert p0 is not None, "no probe prompt produced a mid-stream token"
    ref = _reference(lm, p0, 10, eos=eos)
    eng = ServingEngine(lm, num_slots=1, max_length=MAXLEN,
                        eos_token_id=eos, spec_decode=True, spec_k=4)
    eng._drafter = _ScriptedDrafter([(p0, _reference(lm, p0, 10))], k=4)
    rid = eng.submit(p0, max_new_tokens=10)
    out = dict(eng.drain())
    assert out[rid] == ref
    assert out[rid][-1] == eos and len(out[rid]) == cut + 1
    reg = __import__("paddle_tpu").observability.default_registry()
    assert reg.get("serving.retired").value(engine=eng._eid,
                                            reason="eos") == 1
    # the retiring step really committed a multi-token window
    assert eng._m_spec_accept.sum >= eng._m_spec_accept.count + 1


def test_spec_multi_token_accounting_counts_once(lm):
    """ISSUE 7 satellite (queue/metrics audit): an N-token accept is N
    tokens in ONE step — tokens_generated moves by N, the accept
    histogram absorbs one observation of N (its SUM equals committed
    tokens), TPOT stays one observation per retired request, and
    queue-wait one per admission."""
    prompts = [_prompt(5, seed=160), _prompt(8, seed=161)]
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN,
                        spec_decode=True, spec_k=4)
    refs = [(p, _reference(lm, p, 6)) for p in prompts]
    eng._drafter = _ScriptedDrafter(refs, k=4)   # multi-token accepts
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    out = dict(eng.drain())
    total = sum(len(out[r]) for r in rids)
    assert total == 12
    assert int(eng._m_tokens.value()) == total
    assert int(eng._m_spec_accept.sum) == total - 2   # prefill tokens
    assert eng._m_spec_accept.count < total - 2       # ⇒ multi-accepts
    assert eng._m_tpot.count == len(rids)             # once per request
    assert eng._m_queue_wait.count == len(rids)
    reg = __import__("paddle_tpu").observability.default_registry()
    fam = reg.get("serving.retired")
    retired = sum(c.value() for c in fam.children()
                  if c.labels.get("engine") == eng._eid)
    assert retired == len(rids)
    # one step-latency observation per VERIFY tick (prefill waves bump
    # _ticks but are not decode steps)
    assert eng._m_step_ms.count == eng._ticks - int(eng._m_waves.value())
    # draft/verify spans were emitted (serving.spec instrumentation)
    names = {e["name"] for e in
             __import__("paddle_tpu").observability.get_tracer().events()}
    assert "serving.draft" in names and "serving.verify" in names


def test_spec_sampled_rows_ride_along(lm):
    """A sampled request next to greedy ones in spec mode: greedy rows
    keep exact parity (and keep speculating); the sampled row decodes
    one exact-distribution token per step."""
    g0, s0 = _prompt(5, seed=51), _prompt(6, seed=53)
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN, seed=3,
                        spec_decode=True, spec_k=4)
    rg = eng.submit(g0, max_new_tokens=6)
    rs = eng.submit(s0, max_new_tokens=6,
                    sampling=SamplingParams(temperature=0.9, top_k=8,
                                            top_p=0.95))
    results = dict(eng.drain())
    assert eng.step_traces == 1
    assert results[rg] == _reference(lm, g0, 6)
    assert len(results[rs]) == 6
    assert all(0 <= t < lm.config.vocab_size for t in results[rs])


def test_ngram_drafter_units():
    """The prompt-lookup proposer: longest-n-gram-first, most recent
    prior occurrence, k-cap, and honest empty-handedness."""
    from paddle_tpu.serving import NgramDrafter

    d = NgramDrafter(4, max_ngram=3)
    # tail [7, 8] occurred earlier; the 4 tokens after it are proposed
    h = [1, 7, 8, 9, 2, 3, 5, 7, 8]
    assert list(d.propose(h)) == [9, 2, 3, 5]
    # most RECENT occurrence wins (tail [5] matched at its later site)
    assert list(NgramDrafter(2, max_ngram=1).propose(
        [5, 1, 5, 2, 5])) == [2, 5]
    # longer n-gram beats shorter: [3, 5] over the later bare [5]
    assert list(NgramDrafter(2, max_ngram=3).propose(
        [3, 5, 9, 9, 5, 4, 3, 5])) == [9, 9]
    # proposal truncated by history end, never fabricated
    assert list(NgramDrafter(4, max_ngram=2).propose(
        [4, 6, 1, 4, 6])) == [1, 4, 6]
    # no recurring n-gram → no proposal
    assert NgramDrafter(4).propose([1, 2, 3, 4, 5]).size == 0
    assert NgramDrafter(4).propose([9]).size == 0
    with pytest.raises(ValueError, match="k must be"):
        NgramDrafter(0)
    with pytest.raises(ValueError, match="min_ngram"):
        NgramDrafter(2, max_ngram=0)


def test_per_row_position_decode_matches_scalar(lm):
    """The serving-enabling primitive: decode_step with a per-row
    position VECTOR must equal per-row scalar decode_steps."""
    from paddle_tpu.models import init_kv_cache

    ids = jnp.asarray(_prompt(2 * 7, seed=81).reshape(2, 7), jnp.int32)
    cache = init_kv_cache(lm.config, 2, 24)
    # row 0 holds 5 cached tokens, row 1 holds 7 — advance both one step
    logits0, cache = lm.decode_step(ids[:, :5], cache, 0)
    _, c1 = lm.decode_step(ids[1:2, 5:], cache[:, :, 1:2], 5)
    cache = cache.at[:, :, 1:2].set(c1)
    positions = jnp.asarray([5, 7], jnp.int32)
    tok = jnp.asarray([[3], [4]], jnp.int32)
    vec_logits, vec_cache = lm.decode_step(tok, cache, positions)
    for r, pos in enumerate((5, 7)):
        srow, crow = lm.decode_step(tok[r:r + 1], cache[:, :, r:r + 1],
                                    jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(vec_logits[r]),
                                   np.asarray(srow[0]),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(vec_cache[:, :, r]),
                                   np.asarray(crow[:, :, 0]),
                                   rtol=2e-4, atol=2e-4)


def test_draft_model_from_truncates_and_aliases(lm):
    """ISSUE 20: the layer-truncated draft model is ZERO-COPY — every
    shared parameter is the target's own array object, the architecture
    keeps the target's vocab/embedding geometry (rejection sampling
    needs q on p's support), and the depth is actually truncated."""
    from paddle_tpu.models import draft_model_from

    dm, dparams = draft_model_from(lm, num_layers=1)
    assert dm.config.num_hidden_layers == 1
    assert dm.config.vocab_size == lm.config.vocab_size
    assert dm.config.hidden_size == lm.config.hidden_size

    src = lm.state_dict(include_buffers=True)
    shared = [k for k in dparams if k in src]
    assert shared and all(dparams[k] is src[k] for k in shared)
    # nothing invented: every draft param either aliases the target's
    # or belongs to the draft skeleton itself
    own = dm.state_dict(include_buffers=True)
    assert set(dparams) == set(own)

    with pytest.raises(ValueError):
        draft_model_from(lm, num_layers=0)
    with pytest.raises(ValueError):
        draft_model_from(
            lm, num_layers=lm.config.num_hidden_layers + 1)
