"""The SDAR-MoE model (models/sdar.py) — a block-diffusion decoder over
softmax-routed held experts — and the serving engine's block tick: a row's
tick is one pass over a block of positions under the block-causal mask, and
tokens leave it a block at a time.  Tiny widths, seeded weights, CPU; held
against the benchmark's plain reference (benchmark/reference/sdar_arch.py),
which generates with no cache at all."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights_sdar
from benchmark.reference import sdar_arch
from paddle_tpu import nn
from paddle_tpu import observability as obs
from paddle_tpu.distributed.moe import HeldExpertsMoE, SoftmaxTopKGate
from paddle_tpu.models import SdarMoeForCausalLM, tiny_sdar_config
from paddle_tpu.models.generation import BlockDiffusion, unmask_block
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.kv_cache import init_paged_kv_cache

# the benchmark's configuration keys of the tiny model, as its files hold
# them (num_experts is the number HELD; the router keeps num_experts_routed).
# Matrices at unit gain for this width.
REF = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_hidden_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "num_experts": 4, "num_experts_routed": 8, "ep_size": 2, "ep_rank": 1,
       "num_experts_per_tok": 2, "norm_topk_prob": True,
       "rope_theta": 1000000.0, "rms_norm_eps": 1e-6, "dtype": "float32",
       "initializer_range": 0.125, "block_length": 4, "mask_token_id": 255,
       "denoising_steps": 4, "remasking_strategy": "low_confidence_dynamic",
       "confidence_threshold": 0.9}
B, MASK = REF["block_length"], REF["mask_token_id"]
CHUNK = BLOCK = 8
STATIC = "low_confidence_static"


def _seeded(seed=3, **over):
    """(model, weights under the reference's names) of the tiny REF."""
    with nn.abstract_parameters():
        model = SdarMoeForCausalLM(tiny_sdar_config(
            ep_size=2, ep_rank=1, **over))
    model.eval()
    made = weights_sdar.make_weights(REF, seed, "float32")
    model.set_state_dict({weights_sdar.program_name(n): w
                          for n, w in made.items()})
    return model, made


def _engine(model, **over):
    kw = dict(num_slots=4, max_length=64, paged=True, chunked=True,
              prefill_chunk=CHUNK, block_len=BLOCK, prefix_cache=False)
    return ServingEngine(model, **{**kw, **over})


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, MASK, n).astype(np.int32)


@pytest.fixture(scope="module")
def seeded():
    return _seeded()


# -- the router and the share ------------------------------------------------

def test_softmax_gate_is_softmax_then_topk_then_renormalised():
    gate = SoftmaxTopKGate(16, 8, 3, dtype="float32")
    x = jax.random.normal(jax.random.key(0), (5, 16))
    idx, w = gate.route(x)
    p = jax.nn.softmax(x @ gate.weight, axis=-1)
    top = np.argsort(-np.asarray(p), axis=-1)[:, :3]
    assert (np.sort(np.asarray(idx), -1) == np.sort(top, -1)).all()
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    picked = np.take_along_axis(np.asarray(p), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    _, raw = SoftmaxTopKGate(16, 8, 3, norm_topk_prob=False,
                             dtype="float32").route(x)
    assert (np.asarray(raw).sum(-1) < 1.0).all()


def test_the_eight_ep8_shares_add_up_to_the_uncut_layer():
    """The guide's share test: each of eight ranks holds one of the tiny
    router's eight experts; their parts of one layer's result add up to
    what the reference gives with every expert held."""
    whole = dict(REF, num_experts=8, ep_size=1, ep_rank=0)
    made = weights_sdar.make_weights(whole, 5, "float32")
    w = sdar_arch.layer_weights(made, 1)
    y = jax.random.normal(jax.random.key(1), (1, 11, 64))
    with jax.default_matmul_precision("highest"):
        want = sdar_arch.expert_layer(y, w, whole)
        gate = SoftmaxTopKGate(64, 8, 2, dtype="float32")
        gate.set_state_dict({"weight": w["router"]})
        idx, wgt = gate.route(y.reshape(-1, 64))
        total = jnp.zeros_like(y)
        for r in range(8):
            share = HeldExpertsMoE(64, 32, 8, 2, held=(r, r + 1),
                                   dtype="float32")
            share.set_state_dict({
                "gate_proj": w["experts_gate"][r:r + 1],
                "up_proj": w["experts_up"][r:r + 1],
                "down_proj": w["experts_down"][r:r + 1]})
            total = total + share(y, idx, wgt)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


# -- the model against the reference ----------------------------------------

def test_forward_is_the_reference_under_the_block_mask(seeded):
    model, made = seeded
    ids = _prompt(14, seed=1)
    got = model(jnp.asarray(ids)[None])[0]
    want = sdar_arch.logits(made, REF, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    causal = sdar_arch.logits(made, REF, ids, mask="causal")
    assert float(jnp.abs(causal - want).max()) > 0.05


def test_a_chunk_then_a_block_over_the_pool_give_the_reference_logits(seeded):
    """Prefill and then a denoising forward through the paged cache agree
    with the reference's full pass: the block's logits are those of the
    committed tokens and the block, under the block mask."""
    model, made = seeded
    prompt = _prompt(8, seed=2)
    block = np.array([prompt[0], MASK, 17, MASK], np.int32)
    pool = init_paged_kv_cache(model.config, 4, BLOCK)
    table = jnp.asarray([[1, 2]], jnp.int32)
    _, pool = model.decode_step(jnp.asarray(prompt)[None], pool,
                                jnp.zeros((1,), jnp.int32), table)
    got, _ = model.decode_step(jnp.asarray(block)[None], pool,
                               jnp.full((1,), 8, jnp.int32), table)
    want = sdar_arch.logits(made, REF, np.concatenate([prompt, block]))[-B:]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_all_forwards_side_by_side_are_the_sequential_ones(seeded):
    """The reference's one-pass form (the clean sequence and its noised
    copies side by side) reproduces the logits its own cache-free
    generation saw, forward by forward."""
    _, made = seeded
    prompt = _prompt(6, seed=4)
    tokens, steps, seen = sdar_arch.generate(made, REF, prompt, 10)
    seq = np.concatenate([prompt, tokens])
    when = np.concatenate([np.zeros(6, np.int64), steps])
    fed, f, pos = sdar_arch.forwards_fed(seq, when, REF)
    hidden = sdar_arch.hidden_states(made, REF, seq, fed)
    got = sdar_arch.head_logits(made, REF, hidden[f, pos])
    # the generation's forwards in order: block by block, forward by forward
    at = iter(seen)
    for blk in range(1, len(seq) // B):
        for fwd in range(1, int(when[blk * B:(blk + 1) * B].max()) + 1):
            lg = next(at)
            for j in range(blk * B, (blk + 1) * B):
                hit = np.nonzero((f == fwd) & (pos == j))[0]
                if hit.size:
                    np.testing.assert_allclose(
                        np.asarray(got[hit[0]]), lg[j - blk * B],
                        rtol=1e-3, atol=1e-3)


# -- the engine against the reference ---------------------------------------

CASES = [  # prompt length, max_new_tokens: P mod B of 0 and not 0, outputs
    (8, 8), (9, 6), (3, 9), (21, 5), (4, 1), (5, 2)]  # ending inside a block


@pytest.mark.parametrize("strategy", [None, STATIC])
def test_engine_tokens_and_unmask_order_are_the_references(seeded, strategy):
    """Greedy, both strategies: what the engine delivers, and the forward
    at which each token was unmasked, is what the reference's cache-free
    generation gives — with rows at different steps of their blocks in one
    tick (five requests over four slots, staggered by their prompts)."""
    model, made = seeded
    eng = _engine(model)
    sp = SamplingParams(unmask_strategy=strategy)
    rids = [eng.submit(_prompt(p, seed=10 + i), max_new_tokens=n,
                       sampling=sp) for i, (p, n) in enumerate(CASES)]
    eng.drain()
    for i, (rid, (p, n)) in enumerate(zip(rids, CASES)):
        tokens, steps, _ = sdar_arch.generate(
            made, REF, _prompt(p, seed=10 + i), n, strategy=strategy)
        assert eng.result(rid) == tokens, (p, n)
        assert eng.unmask_steps(rid) == steps, (p, n)
        assert len(tokens) == n
    assert eng.step_traces == 1
    assert eng.kv.blocks_in_use() == 0
    if strategy == STATIC:      # one position a forward, four a block
        assert set(eng.unmask_steps(rids[0])) == {1, 2, 3, 4}


def test_a_threshold_every_position_passes_unmasks_a_block_in_one_forward(
        seeded):
    model, made = seeded
    eng = _engine(model)
    rid = eng.submit(_prompt(7, seed=3), max_new_tokens=9,
                     sampling=SamplingParams(unmask_threshold=0.0))
    eng.drain()
    tokens, steps, _ = sdar_arch.generate(made, REF, _prompt(7, seed=3), 9,
                                          threshold=0.0)
    assert eng.result(rid) == tokens
    assert eng.unmask_steps(rid) == steps == [1] * 9
    m = obs.snapshot()
    fwd = sum(r["value"] for r in m["serving.diffusion.forwards"]["series"]
              if r["labels"]["engine"] == eng._eid)
    # 7 = 4 committed + 3 given: blocks of 1 + 4 + 4 tokens, one denoising
    # forward each and a commit between them
    assert fwd == 5


def test_a_long_prompt_arriving_mid_decode_changes_nobodys_tokens(seeded):
    """Chunked: a 30-token prompt streams in over four ticks while two rows
    are mid-block; everybody's tokens are the reference's."""
    model, made = seeded
    eng = _engine(model)
    first = [eng.submit(_prompt(p, seed=20 + i), max_new_tokens=12)
             for i, p in enumerate((5, 8))]
    for _ in range(6):
        eng.step()
    late = eng.submit(_prompt(30, seed=22), max_new_tokens=6)
    eng.drain()
    for rid, (p, n, s) in zip(first + [late],
                              ((5, 12, 20), (8, 12, 21), (30, 6, 22))):
        tokens, steps, _ = sdar_arch.generate(made, REF, _prompt(p, seed=s),
                                              n)
        assert eng.result(rid) == tokens
        assert eng.unmask_steps(rid) == steps
    assert eng.step_traces == 1


def test_result_grows_a_block_at_a_time_and_first_token_is_a_delivery(seeded):
    model, _ = seeded
    eng = _engine(model)
    rid = eng.submit(_prompt(6, seed=5), max_new_tokens=11)
    sizes = []
    while eng.num_active or eng.queue_depth or eng.num_pending:
        eng.step()
        sizes.append(len(eng.result(rid)))
    grown = sorted(set(sizes))
    # 6 = 4 committed + 2 given: the first block delivers 2, then 4, 4, 1
    assert grown == [0, 2, 6, 10, 11]
    log = obs.get_request_log().event_names(eng.request_uid(rid))
    assert log.count("first_token") == 1
    assert log.index("first_token") > log.index("prefill_chunk")


def test_lint_is_green_with_rows_in_every_state(seeded):
    """Rows idle, mid-prompt, denoising, delivered-and-awaiting-commit and
    committing in one engine: one trace, no finding."""
    model, _ = seeded
    eng = _engine(model)
    for i, p in enumerate((4, 9, 14)):
        eng.submit(_prompt(p, seed=30 + i), max_new_tokens=9,
                   sampling=SamplingParams(unmask_threshold=0.0)
                   if i == 0 else None)
        eng.step()
    states = set()
    for _ in range(12):
        eng.step()
        for i, slot in enumerate(eng._slots):
            if slot is not None:
                masked = int((eng._blocks[i] == MASK).sum())
                states.add("clean" if masked == 0 else
                           "open" if masked == B - slot.given else "part")
    assert states == {"clean", "open", "part"}
    assert eng.lint_step() == []
    assert eng.step_traces == 1


def test_sampled_rows_run_and_replay(seeded):
    model, _ = seeded
    outs = []
    for _ in range(2):
        eng = _engine(model, seed=7)
        rid = eng.submit(_prompt(5, seed=6), max_new_tokens=8,
                         sampling=SamplingParams(temperature=0.8))
        eng.drain()
        outs.append(eng.result(rid))
        assert MASK not in outs[-1] and len(outs[-1]) == 8
    assert outs[0] == outs[1]


# -- what is refused, by name ------------------------------------------------

@pytest.mark.parametrize("kw, names", [
    (dict(paged=False), "contiguous cache"),
    (dict(chunked=False), "wave prefill"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(spec_decode=True), "speculative decoding"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(int8_weights=True), "int8_weights"),
    (dict(mesh="mp2"), "a mesh"),
    (dict(preempt="recompute"), "preempt"),
    (dict(host_blocks=4), "host_blocks"),
])
def test_layouts_the_block_tick_does_not_run_are_refused_by_name(
        seeded, kw, names):
    model, _ = seeded
    with pytest.raises(NotImplementedError, match=names):
        _engine(model, **kw)


def test_export_is_refused_and_cancel_frees_a_row_mid_block(seeded):
    model, _ = seeded
    eng = _engine(model)
    rid = eng.submit(_prompt(6, seed=8), max_new_tokens=9)
    for _ in range(3):
        eng.step()
    with pytest.raises(NotImplementedError, match="block under denoising"):
        eng.export_request(rid)
    assert eng.cancel(rid)
    assert eng.kv.blocks_in_use() == 0
    assert (eng._blocks == MASK).all()
    with pytest.raises(ValueError, match="multiples of the model's block"):
        _engine(model, prefill_chunk=6)


# -- the unmasking epilogue --------------------------------------------------

def _built_logits(conf_tokens):
    """(1, B, V) logits whose position j puts ``conf_tokens[j] = (token,
    logit)`` first, everything else at 0."""
    lg = np.zeros((1, B, 256), np.float32)
    for j, (tok, val) in enumerate(conf_tokens):
        lg[0, j, tok] = val
    return jnp.asarray(lg)


def _unmask(lg, block, n_static=0, threshold=0.9):
    new, n = unmask_block(
        lg, jnp.asarray([block], jnp.int32), MASK, jax.random.key(0),
        jnp.zeros(1), jnp.zeros(1, jnp.int32), jnp.ones(1),
        jnp.asarray([n_static], jnp.int32),
        jnp.asarray([threshold], jnp.float32))
    return np.asarray(new)[0].tolist(), int(n[0])


def test_the_dynamic_rule_unmasks_every_position_over_the_threshold():
    """Logits built so that two masked positions pass 0.9, one does not and
    one is already unmasked: both go in ONE forward, the third stays."""
    lg = _built_logits([(3, 20.0), (5, 0.5), (7, 20.0), (9, 20.0)])
    new, n = _unmask(lg, [MASK, MASK, MASK, 44])
    assert (new, n) == ([3, MASK, 7, 44], 2)
    # none passes: the single most confident one, ties to the earlier
    flat = _built_logits([(3, 1.0), (5, 2.0), (7, 2.0), (9, 0.5)])
    assert _unmask(flat, [MASK] * 4) == ([MASK, 5, MASK, MASK], 1)
    # the static rule takes the n most confident whatever the threshold
    assert _unmask(flat, [MASK] * 4, n_static=2) == (
        [MASK, 5, 7, MASK], 2)
    # a mask-free block (a commit forward) goes out as it came in
    assert _unmask(lg, [1, 2, 3, 4]) == ([1, 2, 3, 4], 0)


def test_the_mask_token_is_never_a_candidate():
    lg = _built_logits([(MASK, 30.0)] * 4)
    new, n = _unmask(lg, [MASK] * 4)
    assert n == 1 and MASK not in [t for t in new if t != MASK] \
        and sum(t != MASK for t in new) == 1
    assert BlockDiffusion(4, MASK, steps=2).static_count(STATIC) == 2
    with pytest.raises(ValueError, match="unmasking strategy"):
        BlockDiffusion(4, MASK).static_count("random")


# -- the kernel's mask -------------------------------------------------------

def _dense_block_causal(q, k, v, pos, block):
    """Dense softmax attention of q (s, hq, d) at positions pos.. over the
    contiguous k/v (L, hkv, d) under the block-causal mask, in float64."""
    s, hq, d = q.shape
    g = hq // k.shape[1]
    kk, vv = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
    sc = np.einsum("shd,lhd->hsl", q, kk) / np.sqrt(d)
    qi = pos + np.arange(s)
    see = np.arange(k.shape[0])[None, :] // block <= qi[:, None] // block
    sc = np.where(see[None], sc, -np.inf)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("hsl,lhd->shd", w, vv)


@pytest.mark.parametrize("s", [4, 24], ids=["block-rows", "chunk-tiles"])
def test_block_masked_kernel_is_a_dense_block_causal_softmax(s):
    """Interpret mode, G = 8 (q tiles of 8 positions, two blocks a tile),
    rows at depth 0, inside the first pool block, at its edge, past it at a
    depth that is no multiple of 128, and deep: the Pallas body and the XLA
    reference both against a dense block-causal softmax; every pool block
    behind a row's last visible key holds NaN."""
    from paddle_tpu.ops.attention import paged_decode_attention_reference
    from paddle_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_pallas)
    hq, hkv, d, bl, mb = 16, 2, 32, 128, 4
    depths = np.array([0, 4, 124, 128, 200, 380], np.int32)
    rng = np.random.default_rng(s)
    b = len(depths)
    q = rng.normal(size=(b, s, hq, d)).astype(np.float32)
    kc = rng.normal(size=(b, mb * bl, hkv, d)).astype(np.float32)
    vc = rng.normal(size=(b, mb * bl, hkv, d)).astype(np.float32)
    # rows' blocks scattered over the pool behind the null block
    tables = 1 + rng.permutation(b * mb).reshape(b, mb).astype(np.int32)
    pool = np.zeros((2, 2, b * mb + 1, bl, hkv * d), np.float32)
    for r in range(b):
        last = (depths[r] + s - 1) // bl
        for c in range(mb):
            for which, src in ((0, kc), (1, vc)):
                pool[1, which, tables[r, c]] = (
                    src[r, c * bl:(c + 1) * bl].reshape(bl, -1)
                    if c <= last else np.nan)
    want = np.stack([_dense_block_causal(q[r], kc[r], vc[r], depths[r], B)
                     for r in range(b)])
    args = (jnp.asarray(q), jnp.asarray(pool), 1, jnp.asarray(depths),
            jnp.asarray(tables))
    got = paged_decode_attention_pallas(*args, block=B, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    clean = jnp.nan_to_num(jnp.asarray(pool))
    ref = paged_decode_attention_reference(args[0], clean, *args[2:],
                                           block=B)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5, atol=2e-5)
    causal = paged_decode_attention_reference(args[0], clean, *args[2:])
    assert float(jnp.abs(causal - ref).max()) > 1e-2
    with pytest.raises(NotImplementedError, match="block mask"):
        paged_decode_attention_pallas(args[0][:, :3], *args[1:], block=B,
                                      interpret=True)


# -- the block tick's spans and counters (no wall-clock limit here) ----------

def test_the_block_ticks_spans_and_counters_add_up(seeded):
    """``serving.decode`` keeps its name and its accepted args and states
    the block tick beside them: ``block``, ``masked_in``, ``unmasked``,
    ``commits``, ``delivered``; over a whole drain they add up to what the
    requests got, and the counters say the same."""
    model, _ = seeded
    eng = _engine(model)
    obs.get_tracer().clear()
    want = 0
    for i, (p, n) in enumerate(((9, 10), (4, 7), (18, 5))):
        eng.submit(_prompt(p, seed=40 + i), max_new_tokens=n)
        want += n
    eng.drain()
    evs = [e for e in obs.get_tracer().events() if e["ph"] == "X"]
    rows = [e["args"] for e in evs if e["name"] == "serving.decode"]
    chunks = [e for e in evs if e["name"] == "serving.chunk"]
    assert rows and chunks
    names = {e["name"] for e in evs}
    assert {"serving.step", "serving.grow", "serving.build_inputs",
            "serving.dispatch", "serving.readback",
            "serving.advance"} <= names and "serving.verify" not in names
    for a in rows:
        assert a["block"] == B and a["weight_passes"] == 1
        # the program the tick ran: the block rows, and the chunk's
        # positions where a chunk ran beside them (the stub of them the
        # rows-alone program keeps where none did)
        assert a["pass_rows"] == 4 * B + (
            CHUNK if a["parts"] == 2 else eng._stub_chunk)
        assert 0 <= a["unmasked"] <= a["masked_in"] <= a["slots"] * B
        assert a["commits"] <= a["slots"]
        assert a["kv_walk"] >= a["kv_blocks"] > 0
        assert a["sample_path"] == "greedy"
    assert sum(a["parts"] - 1 for a in rows) == len(chunks) < len(rows)
    # a tick's real tokens: its live rows' blocks and its chunk's tokens
    by_tick = {}
    for e in chunks:
        by_tick[e["ts"]] = e["args"]["tokens"]
    assert sum(a["pass_tokens"] for a in rows) == sum(
        a["slots"] * B for a in rows) + sum(by_tick.values())
    assert sum(a["delivered"] for a in rows) == want
    forwards = sum(a["slots"] for a in rows)
    snap = obs.snapshot()

    def counted(name):
        return sum(r["value"] for r in snap[name]["series"]
                   if r["labels"]["engine"] == eng._eid)
    assert counted("serving.diffusion.forwards") == forwards
    assert counted("serving.diffusion.delivered") == want
    assert counted("serving.diffusion.unmasked") == sum(
        a["unmasked"] for a in rows)
    assert counted("serving.diffusion.commits") == sum(
        a["commits"] for a in rows)
    # every generated position was unmasked once; what a request's last
    # block held past max_new_tokens was unmasked and not delivered
    assert want <= counted("serving.diffusion.unmasked") < want + 3 * B
    assert counted("serving.tokens_generated") == want
    # the experts' load rides out as for the other expert models
    load = eng.expert_load
    assert load["pairs"].shape == (3, 4) and load["layer_calls"] > 0
