"""Int8-quantized KV cache (ISSUE 13): kernel/reference dequant parity,
the quantized block pool's lifecycle edge cases, end-to-end engine
parity behind ``FLAGS_serving_kv_cache_dtype``, and the graph-lint
dtype-promotion scope for the dequant widening.

Acceptance spine: every cache layout the engine composes (contiguous /
paged × wave / chunked × plain / spec) serves its bf16 twin's greedy
output up to argmax flips inside the dequant logit bound, with the step
compiled exactly once; ``mixed`` demotes exactly the cold full prefix
blocks and its accounting gauges agree with the manager's per-block
dtype marks;
an int8->float widening OUTSIDE the decode-attention/quantize regions
is a lint finding while the in-kernel dequant stays clean.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import static_analysis as sa
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.models.generation import init_kv_cache
from paddle_tpu.ops.attention import (cached_decode_attention_reference,
                                      decode_attention_path)
from paddle_tpu.ops.attention import paged_decode_attention_reference
from paddle_tpu.ops.pallas.decode_attention import (
    decode_attention_pallas, paged_decode_attention_pallas)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import BlockManager, init_paged_kv_cache
from paddle_tpu.static_analysis.rules import DtypePromotionRule

MAXLEN = 64
BL = 8
# max |logit delta| an int8 cache may cause on the tiny model (BASELINE.md
# "Quantization accounting"; measured ~1e-2)
DEQUANT_LOGIT_BOUND = 0.25


@pytest.fixture(scope="module")
def lm():
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    return model


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def _quantize_blocks(x, bl):
    """(B, L, Hkv, D) -> int8 payload + (B, L//bl, Hkv) f32 scales with
    per-block-per-kv-head absmax/127 — the convention the scatter-time
    writer maintains on device."""
    b, L, hkv, d = x.shape
    blocks = x.reshape(b, L // bl, bl, hkv, d)
    sc = np.abs(blocks).max(axis=(2, 4)) / 127.0          # (B, nb, Hkv)
    safe = np.where(sc > 0, sc, 1.0)
    q = np.clip(np.round(blocks / safe[:, :, None, :, None]), -127, 127)
    deq = (q * safe[:, :, None, :, None]).reshape(b, L, hkv, d)
    return q.astype(np.int8).reshape(b, L, hkv, d), sc.astype(np.float32), deq


# ---------------------------------------------------------------- ops --


def test_paged_int8_kernel_matches_dequantized_reference():
    """The tentpole read path: the Pallas kernel fed int8 pool blocks +
    block-table-indexed scales must match the bf16 math path run on the
    explicitly dequantized cache — the dequant happens inside the
    KV-chunk loop, the online-softmax merge unchanged."""
    b, s, hq, hkv, d, bl, mb = 2, 1, 8, 2, 64, 128, 2
    L = mb * bl
    rng = np.random.default_rng(3)
    kc = rng.normal(size=(b, L, hkv, d)).astype(np.float32)
    vc = rng.normal(size=(b, L, hkv, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    pos = jnp.asarray([77, 200], jnp.int32)
    tables = np.asarray([[1, 2], [3, 4]], np.int32)

    kq, ks, kdeq = _quantize_blocks(kc, bl)
    vq, vs, vdeq = _quantize_blocks(vc, bl)
    want = cached_decode_attention_reference(
        q, jnp.asarray(kdeq), jnp.asarray(vdeq), pos)

    # scatter rows into layer 1 of a stacked 2-layer, 6-block pool per the
    # tables; layer 0 holds another payload under other scales
    npool, layer = 6, 1
    pool = np.zeros((2, 2, npool, bl, hkv * d), np.int8)
    pool[0] = rng.integers(-127, 128, pool[0].shape)
    psc = np.zeros((2, 2, npool, hkv), np.float32)
    psc[0] = 0.5
    for r in range(b):
        for j in range(mb):
            phys = int(tables[r, j])
            sl = slice(j * bl, (j + 1) * bl)
            pool[layer, 0, phys] = kq[r, sl].reshape(bl, hkv * d)
            pool[layer, 1, phys] = vq[r, sl].reshape(bl, hkv * d)
            psc[layer, 0, phys] = ks[r, j]
            psc[layer, 1, phys] = vs[r, j]

    got = paged_decode_attention_pallas(
        q, jnp.asarray(pool), layer, pos, jnp.asarray(tables),
        pool_scale=jnp.asarray(psc), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the XLA gather+dequant path is the same oracle through the table
    got_ref = paged_decode_attention_reference(
        q, jnp.asarray(pool), layer, pos, jnp.asarray(tables),
        pool_scale=jnp.asarray(psc))
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("paged", [False, True],
                         ids=["contiguous", "paged"])
def test_int8_kernel_lowers_for_tpu(paged):
    """Lowering needs no chip: the Pallas TPU lowering checks every
    BlockSpec against the (8, 128) tiling at trace time, and PR 13's
    ``(1, hkv)`` scale blocks were refused there.  Engine geometry
    (hq 32 / hkv 8 / d 128, L 8192, block_len 128)."""
    b, hq, hkv, d, L, bl = 8, 32, 8, 128, 8192, 128
    S = jax.ShapeDtypeStruct
    q, pos = S((b, 1, hq, d), jnp.bfloat16), S((b,), jnp.int32)
    if paged:
        npool, layers = b * (L // bl) + 1, 4
        args = (q, S((layers, 2, npool, bl, hkv * d), jnp.int8), pos,
                S((b, L // bl), jnp.int32),
                S((layers, 2, npool, hkv), jnp.float32))

        def f(q, pool, pos, bt, sc):
            return paged_decode_attention_pallas(q, pool, layers - 1, pos,
                                                 bt, pool_scale=sc)
    else:
        kv, sc = S((b, L, hkv, d), jnp.int8), S((b, L // bl, hkv),
                                                jnp.float32)
        args = (q, kv, kv, pos, sc, sc)

        def f(q, k, v, pos, ks, vs):
            return decode_attention_pallas(q, k, v, pos, k_scale=ks,
                                           v_scale=vs)

    text = jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_contiguous_int8_reference_matches_dequantized():
    """Contiguous rows with per-granule scales through the XLA path."""
    b, s, hq, hkv, d, L = 2, 1, 4, 2, 32, 256
    gr = 128
    rng = np.random.default_rng(5)
    kc = rng.normal(size=(b, L, hkv, d)).astype(np.float32)
    vc = rng.normal(size=(b, L, hkv, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    pos = jnp.asarray([100, 250], jnp.int32)
    kq, ks, kdeq = _quantize_blocks(kc, gr)
    vq, vs, vdeq = _quantize_blocks(vc, gr)
    want = cached_decode_attention_reference(
        q, jnp.asarray(kdeq), jnp.asarray(vdeq), pos)
    got = cached_decode_attention_reference(
        q, jnp.asarray(kq), jnp.asarray(vq), pos,
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dispatch_quantized_short_cache_stays_on_xla():
    """Below the kernel length threshold the quantized path must still
    dispatch somewhere correct — the reference's gather+dequant."""
    path, reason = decode_attention_path(2, 1, 8, 2, 64, 64, False,
                                         quantized=True)
    assert path == "xla_math"


# ------------------------------------------------- pool lifecycle -----


def _mgr(**kw):
    kw.setdefault("num_blocks", 12)
    kw.setdefault("block_len", BL)
    return BlockManager(**kw)


def test_int8_pool_born_quantized_and_stays_quantized():
    m = _mgr(kv_dtype="int8")
    m.admit(0, _prompt(12, 0), 12, max_new_tokens=4)
    assert all(m.block_dtype(b) == "int8" for b in m.chain(0))
    m.release(0)
    assert m.quantized_blocks() >= 0          # gauges refresh, no throw


def test_mixed_demotes_only_full_prefix_blocks():
    m = _mgr(kv_dtype="mixed")
    events = []
    m.on_demote = events.append
    m.admit(0, _prompt(20, 1), 20, max_new_tokens=4)   # 2 full blocks + tail
    (bids,) = events
    assert len(bids) == 2
    assert [m.block_dtype(b) for b in m.chain(0)[:2]] == ["int8", "int8"]
    # the tail block holding position 20 is hot
    assert m.block_dtype(m.chain(0)[2]) == "bf16"
    assert m.quantized_blocks() == 2


def test_mixed_truncate_across_dtype_boundary_resets_to_hot():
    """Spec-decode rollback across the bf16/int8 boundary: blocks freed
    by truncate_to re-enter the pool at the pool default (hot), so the
    next tenant is never mislabeled quantized."""
    m = _mgr(kv_dtype="mixed", prefix_cache=False)
    m.admit(0, _prompt(9, 2), 9, max_new_tokens=30)
    for pos in range(9, 30):
        m.ensure_capacity(0, pos)
    chain = list(m.chain(0))
    m._dtype[chain[-1]] = 1                    # force a demoted tail
    m.truncate_to(0, 10)                       # roll back to 2 blocks
    freed = chain[len(m.chain(0)):]
    assert freed
    for b in freed:
        assert m.block_dtype(b) == "bf16"
    # pure-int8 pool: the same rollback resets to the int8 default
    mi = _mgr(kv_dtype="int8", prefix_cache=False)
    mi.admit(0, _prompt(9, 2), 9, max_new_tokens=30)
    for pos in range(9, 30):
        mi.ensure_capacity(0, pos)
    ci = list(mi.chain(0))
    mi.truncate_to(0, 10)
    for b in ci[len(mi.chain(0)):]:
        assert mi.block_dtype(b) == "int8"


def test_mixed_cow_into_demoted_shared_block_goes_hot():
    """A fork writing into a demoted shared block COWs onto a fresh
    block: the private copy is hot again (its content is already at
    simulated-int8 precision, but future writes land at full precision)
    while the shared original stays demoted for its other readers."""
    m = _mgr(kv_dtype="mixed")
    p = _prompt(16, 3)                          # exactly 2 full blocks
    m.admit(0, p, p.size, max_new_tokens=4)
    shared = list(m.chain(0)[:2])
    assert all(m.block_dtype(b) == "int8" for b in shared)
    hit = m.admit(1, np.concatenate([p, _prompt(3, 4)]), 19,
                 max_new_tokens=4)
    assert hit == 16                            # trie adoption, int8 hits
    cow = m.ensure_writable(1, 1)
    assert cow is not None
    src, dst = cow
    assert src == shared[1]
    assert m.block_dtype(src) == "int8"         # other reader unchanged
    assert m.block_dtype(dst) == "bf16"         # private copy is hot


def test_mixed_prefix_hits_adopt_int8_blocks():
    """LRU-parked demoted blocks revive through the trie WITH their
    dtype: a prefix hit adopts quantized content (and the hit counters
    prove adoption, not recompute)."""
    m = _mgr(kv_dtype="mixed")
    p = _prompt(16, 5)
    m.admit(0, p, p.size, max_new_tokens=4)
    demoted = list(m.chain(0)[:2])
    m.release(0)
    assert m.quantized_blocks() == 2            # parked, content persists
    hit = m.admit(1, np.concatenate([p, _prompt(2, 6)]), 18,
                 max_new_tokens=4)
    assert hit == 16
    assert list(m.chain(1)[:2]) == demoted
    assert all(m.block_dtype(b) == "int8" for b in demoted)


def test_mixed_eviction_resets_dtype_and_gauges():
    m = _mgr(num_blocks=6, kv_dtype="mixed")    # 5 usable
    m.set_block_nbytes({"bf16": 1000, "int8": 300})
    p = _prompt(16, 7)
    m.admit(0, p, p.size, max_new_tokens=4)             # 3 blocks, 2 demoted
    m.release(0)                                # 2 parked + 1 freed
    assert m.quantized_blocks() == 2
    # pool pressure: a 4-block admission must evict the parked pair —
    # whose dtype marks reset — while the NEW prompt's 3 full prefix
    # blocks demote at their own registration
    m.admit(1, _prompt(25, 8), 25, max_new_tokens=6)
    assert m.quantized_blocks() == 3
    chain = m.chain(1)
    assert [m.block_dtype(b) for b in chain] == ["int8"] * 3 + ["bf16"]
    # bytes gauges follow the dtype marks: 3 demoted + 1 hot tail
    assert int(m._g_bytes["int8"].value()) == 3 * 300
    assert int(m._g_bytes["bf16"].value()) == 1 * 1000


def test_fresh_block_tracking_excludes_cow_destinations():
    """drain_fresh feeds the engine's device scale reset: appended
    blocks are fresh (a reused block's stale scale must not leak into
    its new tenant), COW destinations are NOT (the device copy carries
    the source's live scale)."""
    m = _mgr(kv_dtype="int8")
    p = _prompt(16, 9)
    m.admit(0, p, p.size, max_new_tokens=4)
    fresh = m.drain_fresh()
    assert sorted(fresh) == sorted(m.chain(0))
    assert m.drain_fresh() == []                # drained
    m.admit(1, np.concatenate([p, _prompt(3, 10)]), 19, max_new_tokens=4)
    m.drain_fresh()
    src, dst = m.ensure_writable(1, 1)
    assert dst not in m.drain_fresh()


# --------------------------------------------------- engine parity ----


LAYOUTS = [
    ("contiguous", {}),
    ("paged", dict(paged=True, block_len=BL)),
    ("paged+chunked", dict(paged=True, block_len=BL, chunked=True,
                           prefill_chunk=4)),
    ("contiguous+chunked", dict(chunked=True, prefill_chunk=4)),
    ("paged+spec", dict(paged=True, block_len=BL, spec_decode=True,
                        spec_k=3)),
]


def _serve(lm, kw, prompts, n_new=8):
    kw = dict({"num_slots": 3, "max_length": MAXLEN, "prefill_batch": 2},
              **kw)
    eng = ServingEngine(lm, **kw)
    rids = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    out = dict(eng.drain())
    return [out[r] for r in rids], eng


# the contiguous identity sweep duplicates what the paged layouts
# prove about int8 quantisation itself; tier-1 keeps the paged layouts
# (the serving default) and nightlies the contiguous one
@pytest.mark.parametrize(
    "name,kw",
    [pytest.param(n, kw, id=n,
                  marks=[pytest.mark.slow] if n == "contiguous" else [])
     for n, kw in LAYOUTS])
def test_int8_engine_agrees_with_bf16_inside_dequant_bound(lm, name, kw):
    """int8 KV serves what the bf16 engine in the same layout serves,
    up to the dequant error: tokens match until the first argmax the
    ~1e-2 logit perturbation can flip — one whose full-precision margin
    between the two candidates sits inside twice the documented 0.25
    logit bound — and the step compiles exactly once.  (Greedy token
    identity over whole horizons holds only for prompt seeds whose
    near-ties happen to fall clean under one jaxlib's CPU numerics.)"""
    prompts = [_prompt(n, 120 + n) for n in (5, 12, 3, 20)]
    want, _ = _serve(lm, kw, prompts)
    got, eng = _serve(lm, dict(kw, kv_cache_dtype="int8"), prompts)
    for p, w, g in zip(prompts, want, got):
        assert len(g) == len(w)
        i = next((i for i in range(len(w)) if w[i] != g[i]), None)
        if i is None:
            continue
        ids = jnp.asarray(np.concatenate([p, w[:i]])[None], jnp.int32)
        logits = np.asarray(lm(ids)[0, -1].astype(jnp.float32))
        assert 0 <= logits[w[i]] - logits[g[i]] < 2 * DEQUANT_LOGIT_BOUND
    assert eng.step_traces == 1
    assert eng.kv_dtype == "int8" and eng.quantized
    if eng.paged:
        assert eng.metrics()["kv_cache"]["kv_dtype"] == "int8"


@pytest.mark.slow
def test_int8_block_reuse_matches_fresh_pool_exactly(lm):
    """Regression for the stale-scale hazard: requests landing on REUSED
    physical blocks must be served bit-identically to the same requests
    on a fresh int8 engine.  The engine zeroes reused blocks' device
    scale rows before dispatch; if a previous tenant's scale leaked into
    the running max, the second wave's quantization would coarsen and
    this int8-vs-int8 comparison — exact by construction — would
    diverge."""
    kw = dict(paged=True, block_len=BL, kv_cache_dtype="int8")
    first = [_prompt(n, 40 + n) for n in (12, 9)]
    second = [_prompt(n, 50 + n) for n in (17, 6)]

    def run(batches):
        eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN,
                            prefill_batch=2, prefix_cache=False, **kw)
        outs = []
        for batch in batches:
            rids = [eng.submit(p, max_new_tokens=10) for p in batch]
            out = dict(eng.drain())
            outs.append([out[r] for r in rids])
        return outs, eng

    (want,), _ = run([second])                  # fresh pool, zero scales
    (_, got), eng = run([first, second])        # second wave reuses blocks
    assert got == want
    assert eng.step_traces == 1


def test_mixed_mode_parity_demotion_and_accounting(lm):
    """mixed serves parity output while demoting exactly the cold full
    prefix blocks; the demotion counter, the manager's per-block marks,
    and the bytes_by_dtype gauges all agree."""
    prompts = [_prompt(n, 60 + n) for n in (5, 12, 3, 20)]
    kw = dict(paged=True, block_len=BL)
    want, _ = _serve(lm, kw, prompts, n_new=12)
    got, eng = _serve(lm, dict(kw, kv_cache_dtype="mixed"), prompts,
                      n_new=12)
    assert got == want
    assert eng.step_traces == 1
    assert eng._pending_demote == []            # every demotion applied
    mk = eng.metrics()["kv_cache"]
    assert mk["kv_dtype"] == "mixed"
    # prompts of 12 and 20 tokens hold 1 + 2 cold full prefix blocks
    assert mk["quantized_blocks"] == 3
    assert eng._m_demoted.value() == 3
    per_block = eng.kv._block_nbytes
    assert mk["bytes_by_dtype"]["int8"] == 3 * per_block["int8"]


def test_mixed_requires_paged(lm):
    with pytest.raises(ValueError):
        ServingEngine(lm, num_slots=2, max_length=MAXLEN,
                      kv_cache_dtype="mixed")
    with pytest.raises(ValueError):
        ServingEngine(lm, num_slots=2, max_length=MAXLEN,
                      kv_cache_dtype="fp8")


def test_int8_weights_compose_with_int8_kv(lm):
    """FLAGS_serving_int8_weights flips the engine's linear layers to
    the weight-only int8 path; composed with the int8 cache the engine
    still drains with one step trace and a wrapped model."""
    prompts = [_prompt(n, 80 + n) for n in (5, 9)]
    got, eng = _serve(lm, dict(paged=True, block_len=BL,
                               kv_cache_dtype="int8", int8_weights=True),
                      prompts)
    assert hasattr(eng.model, "unwrapped")
    assert eng.step_traces == 1
    assert all(len(o) == 8 for o in got)


def test_cache_hbm_bytes_shrinks_and_dequant_error_hook(lm):
    """Satellite 1 + 3: the dtype-aware HBM accounting reports the int8
    pool at well under half the bf16 bytes, and the parity oracle's
    observation lands in the serving.kv_dequant_error summary."""
    kw = dict(paged=True, block_len=BL)
    e16 = ServingEngine(lm, num_slots=3, max_length=MAXLEN, **kw)
    e8 = ServingEngine(lm, num_slots=3, max_length=MAXLEN,
                       kv_cache_dtype="int8", **kw)
    assert e8.cache_hbm_bytes < 0.55 * e16.cache_hbm_bytes
    ids = jnp.asarray(_prompt(9, 91)[None], jnp.int32)

    def logits(quantized):
        # one prefill + one cached decode step: the first read that
        # actually sees quantized K/V
        cache = init_kv_cache(lm.config, 1, MAXLEN, quantized=quantized)
        _, cache = lm.decode_step(ids, cache, 0)
        out, _ = lm.decode_step(jnp.asarray([[5]], jnp.int32), cache,
                                jnp.asarray([9], jnp.int32))
        return np.asarray(out[0, -1].astype(jnp.float32))

    delta = float(np.abs(logits(True) - logits(False)).max())
    e8.observe_dequant_error(delta)
    assert e8._m_dequant_err.count == 1
    assert e8._m_dequant_err.sum == pytest.approx(delta)
    assert delta < DEQUANT_LOGIT_BOUND


def test_quantized_cache_pytrees():
    cfg = tiny_llama_config()
    c = init_kv_cache(cfg, 2, 128, quantized=True)
    assert c["kv"].dtype == jnp.int8 and c["scale"].dtype == jnp.float32
    assert c["scale"].shape[3] == 1             # one granule per 128
    pool = init_paged_kv_cache(cfg, num_blocks=4, block_len=8,
                               quantized=True)
    assert pool["kv"].dtype == jnp.int8
    assert pool["scale"].shape == (cfg.num_hidden_layers, 2, 4,
                                   cfg.num_key_value_heads)


# ----------------------------------------------------- graph lint -----


def test_lint_flags_int8_widening_outside_kernel():
    """Offender: dequantizing the cache OUTSIDE the decode-attention
    scope rematerializes the full-precision copy — a finding."""
    rule = DtypePromotionRule(min_bytes=0)

    def offender(q, kv, sc):
        return q @ (kv.astype(jnp.float32) * sc[:, None])

    fs = sa.analyze(offender,
                    jnp.zeros((8, 128), jnp.bfloat16),
                    jnp.zeros((128, 128), jnp.int8),
                    jnp.zeros((128,), jnp.float32), rules=(rule,))
    assert [f.rule for f in fs] == ["dtype-promotion"]
    assert "int8" in fs[0].message


def test_lint_allows_dequant_inside_named_scope():
    """Clean twin: the same widening inside the named reference region
    (``pjit[_dequant_decode_attention]``) is the deliberate, scoped
    dequant."""
    rule = DtypePromotionRule(min_bytes=0)

    @jax.jit
    def _dequant_decode_attention(kv, sc):
        return kv.astype(jnp.float32) * sc[:, None]

    def clean(q, kv, sc):
        return q @ _dequant_decode_attention(kv, sc)

    fs = sa.analyze(clean,
                    jnp.zeros((8, 128), jnp.bfloat16),
                    jnp.zeros((128, 128), jnp.int8),
                    jnp.zeros((128,), jnp.float32), rules=(rule,))
    assert fs == []


def test_int8_engine_lints_clean_and_meshes(lm):
    """The CI contract on the quantized hot path: zero findings from
    the full rule set, and the mp2dp2 pre-flight's dtype-aware HBM
    cross-check agrees with the engine's accounting."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN, paged=True,
                        block_len=BL, kv_cache_dtype="int8")
    assert eng.lint_step() == []
    pf = eng.mesh_preflight("mp2dp2")
    assert pf["findings"] == []
    assert pf["cache_check"]["ok"]
    assert pf["cache_check"]["engine_cache_hbm_bytes"] == \
        eng.cache_hbm_bytes


def test_mesh_placed_int8_engine_parity(lm):
    """One mesh-sharded int8 layout on the virtual devices: greedy
    parity with the single-chip int8 engine, one trace, placement
    matches the pre-flight prediction."""
    prompts = [_prompt(n, 95 + n) for n in (5, 12)]
    kw = dict(paged=True, block_len=BL, kv_cache_dtype="int8",
              num_slots=4)                      # dp=2 divides the slots
    want, _ = _serve(lm, kw, prompts)
    got, eng = _serve(lm, dict(kw, mesh="mp2dp2"), prompts)
    assert got == want
    assert eng.step_traces == 1
    pc = eng.mesh_preflight().get("placement_check") or {}
    assert pc.get("ok")
