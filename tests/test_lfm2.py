"""The LFM2-MoE model (models/lfm2.py): gated short-convolution layers
whose per-slot state lives beside the paged pool, attention on some layers
only, sigmoid-routed held experts — and the serving engine serving it:
state that no position addresses must never take junk.  Tiny widths, seeded
weights, CPU, Pallas interpreted where a kernel is meant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.harness import weights_lfm2
from benchmark.reference import lfm2_arch
from paddle_tpu import nn
from paddle_tpu import observability as obs
from paddle_tpu.models import (LlamaForCausalLM, Lfm2MoeConfig,
                               Lfm2MoeForCausalLM, tiny_lfm2_config,
                               tiny_llama_config)
from paddle_tpu.models.lfm2 import Lfm2MoE
from paddle_tpu.ops.attention import paged_decode_attention_reference
from paddle_tpu.ops.pallas.decode_attention import (
    paged_decode_attention_pallas)
from paddle_tpu.serving import ServingEngine

# the benchmark's configuration keys of the tiny model, as its files hold
# them (num_experts is the number HELD; the router keeps num_experts_routed).
# Matrices at unit gain for this width: at 0.02 the tied head would read the
# input token's own embedding and nothing a mixer does
REF = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_hidden_layers": 6,
       "num_dense_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_experts": 4, "num_experts_routed": 8,
       "ep_size": 2, "ep_rank": 1, "num_experts_per_tok": 2,
       "norm_topk_prob": True, "routed_scaling_factor": 1,
       "use_expert_bias": True, "conv_L_cache": 3, "conv_bias": False,
       "layer_types": ["conv", "conv", "full_attention", "conv",
                       "full_attention", "conv"],
       "rope_theta": 1000000.0, "norm_eps": 1e-5, "dtype": "float32",
       "initializer_range": 0.125}
CHUNK = BLOCK = 8


def _config(**over):
    return tiny_lfm2_config(max_position_embeddings=256, **over)


def _seeded(seed=3, **over):
    """(model, weights under the reference's names) of the tiny REF."""
    with nn.abstract_parameters():
        model = Lfm2MoeForCausalLM(_config(ep_size=2, ep_rank=1, **over))
    model.eval()
    made = weights_lfm2.make_weights(REF, seed, "float32")
    model.set_state_dict({weights_lfm2.program_name(n): w
                          for n, w in made.items()})
    return model, made


def _ids(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(1, 256, shape),
                       jnp.int32)


# -- the model against the plain equations -----------------------------------

def test_forward_matches_the_plain_equations():
    """Every rule at once — two norms a layer, the gated convolution with
    zeros before the sequence, q/k norms then RoPE, dense then expert FFN,
    the router with its bias and 1e-6, the held share (rank 1 of 2), the
    tied head behind the final norm — against
    benchmark/reference/lfm2_arch.py, which imports nothing of the program;
    float32 on both sides."""
    model, made = _seeded()
    ids = _ids((40,))
    want = lfm2_arch.logits(made, REF, np.asarray(ids))
    got = model(ids[None])[0]
    assert float(jnp.abs(got - want).max()) < 2e-4
    # the tokens are the model's and not the input's own embedding
    assert (np.asarray(jnp.argmax(got, -1)) != np.asarray(ids)).mean() > 0.5
    # and the reference's controls are different functions
    for control in ({"history": False}, {"weight_bits": 8}):
        assert float(jnp.abs(lfm2_arch.logits(
            made, REF, np.asarray(ids), **control) - want).max()) > 1e-3


def test_generate_matches_forward():
    model, _ = _seeded()
    ids = _ids((2, 11))
    out = model.generate(ids, max_new_tokens=6)
    want = jnp.argmax(model(out[:, :-1]), -1)[:, 10:]
    assert np.array_equal(np.asarray(out[:, 11:]), np.asarray(want))


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(NotImplementedError, match="without bias"):
        Lfm2MoeConfig(conv_bias=True)
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(num_hidden_layers=3, layer_types=("conv", "conv"))
    with pytest.raises(ValueError, match="do not split"):
        Lfm2MoeConfig(ep_size=5)
    published = Lfm2MoeConfig()
    assert published.layers_of("full_attention") == (2, 6, 10, 14, 18, 21)
    assert published.head_dim == 64 and published.experts_held == (0, 32)


# -- the cache form, as the engine's step program drives it ------------------

def _serving_cache(model, slots):
    """The model's serving cache with JUNK in every state row (a slot is
    reused without a reset) and a table row of 6 blocks a slot."""
    cache = model.init_serving_cache(slots, 1 + 6 * slots, BLOCK)
    junk = jax.random.normal(jax.random.key(5), cache["conv"].shape)
    tables = 1 + np.arange(6 * slots, dtype=np.int32).reshape(slots, 6)
    return dict(cache, conv=junk), jnp.asarray(tables)


def _rows(cache, start, n):
    return dict(cache, conv=cache["conv"][:, start:start + n])


@pytest.mark.parametrize("plen", range(CHUNK + 1, 2 * CHUNK + 1))
def test_chunks_then_decode_through_the_cache_match_forward(plen):
    """A prompt cut at every offset of a chunk (a pad tail of every length,
    7 down to 0) into a fresh chunk and a continuation that reads the
    carried state, in slot 1 of 3 whose state row held junk; then four
    decode steps in a rows part where row 0 is idle and row 2 belongs to a
    prefilling slot.  Logits against the full forward pass; the other rows'
    state bit for bit what it was."""
    model, _ = _seeded()
    ids = np.asarray(_ids((plen + 4,), seed=plen))
    want = model(jnp.asarray(ids)[None])[0]
    cache, tables = _serving_cache(model, 3)
    before = cache["conv"]
    for start in (0, CHUNK):
        clen = min(CHUNK, plen - start)
        cids = np.zeros((1, CHUNK), np.int32)
        cids[0, :clen] = ids[start:start + clen]
        logits, part = model.decode_step(
            jnp.asarray(cids), _rows(cache, 1, 1), jnp.asarray([start]),
            block_tables=tables[1:2],
            valid=(jnp.arange(CHUNK) < clen)[None])
        cache = dict(part, conv=cache["conv"].at[:, 1:2].set(part["conv"]))
        assert float(jnp.abs(logits[0, :clen]
                             - want[start:start + clen]).max()) < 2e-4
    held = cache["conv"]
    assert np.array_equal(held[:, 0], before[:, 0])
    assert np.array_equal(held[:, 2], before[:, 2])
    active = jnp.asarray([False, True, False])
    null = jnp.zeros((1, 6), jnp.int32)
    for step in range(4):
        pos = plen + step
        toks = jnp.asarray([[7], [ids[pos]], [9]], jnp.int32)
        logits, cache = model.decode_step(
            toks, cache, jnp.asarray([3, pos, 0]),
            block_tables=jnp.concatenate([null, tables[1:2], null]),
            valid=active[:, None])
        assert float(jnp.abs(logits[1, 0] - want[pos]).max()) < 2e-4
        # an idle row and a prefilling slot's row: held, even at position 0
        assert np.array_equal(cache["conv"][:, 0], before[:, 0])
        assert np.array_equal(cache["conv"][:, 2], before[:, 2])
    # the state is the last two gated inputs and nothing the pad tail made
    assert not np.array_equal(cache["conv"][:, 1], held[:, 1])


def test_a_chunk_of_padding_alone_leaves_the_state_alone():
    """The chunk-free tick's chunk part: no valid token, nothing moves
    (the engine aims it at the null row all the same)."""
    model, _ = _seeded()
    cache, tables = _serving_cache(model, 2)
    _, part = model.decode_step(
        jnp.zeros((1, CHUNK), jnp.int32), _rows(cache, 1, 1),
        jnp.asarray([0]), block_tables=jnp.zeros((1, 6), jnp.int32),
        valid=jnp.zeros((1, CHUNK), bool))
    assert np.array_equal(part["conv"], cache["conv"][:, 1:2])


# -- the serving engine ------------------------------------------------------

def _engine(model, **kw):
    kw = {"num_slots": 3, "max_length": 64, "paged": True, "chunked": True,
          "prefill_chunk": CHUNK, "block_len": BLOCK, "prefix_cache": False,
          **kw}
    return ServingEngine(model, **kw)


def _prompts(lengths):
    return [np.asarray(_ids((n,), seed=n)) for n in lengths]


def test_engine_tokens_equal_generate_over_more_requests_than_slots():
    """Seven requests through three slots, prompts of under one chunk to
    almost four: every slot is reused, chunks and decode rows share ticks,
    and every token is ``generate()``'s greedy token."""
    model, _ = _seeded()
    prompts = _prompts((5, 17, 9, 23, 12, 3, 30))
    eng = _engine(model)
    rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.drain()
    assert eng.step_traces == 1
    for rid, p in zip(rids, prompts):
        want = np.asarray(model.generate(jnp.asarray(p)[None],
                                         max_new_tokens=8))[0, len(p):]
        assert eng.result(rid) == [int(t) for t in want]
    assert len({tuple(eng.result(r)) for r in rids}) > 3    # not degenerate


def test_a_reused_slot_serves_what_a_fresh_engine_serves():
    model, _ = _seeded()
    first, second = _prompts((19, 13))
    used = _engine(model, num_slots=1)
    used.submit(first, max_new_tokens=6)
    used.drain()
    rid = used.submit(second, max_new_tokens=6)
    used.drain()
    fresh = _engine(model, num_slots=1)
    rid2 = fresh.submit(second, max_new_tokens=6)
    fresh.drain()
    assert used.result(rid) == fresh.result(rid2)
    # the slot's row held the first request's state when the second came
    assert float(jnp.abs(used._cache["conv"][:, 0]).max()) > 0


def test_rows_part_leaves_idle_and_prefilling_rows_alone():
    """Tick by tick: while one request decodes and another streams its
    prompt, the state rows of idle slots never change, and a prefilling
    slot's row changes only by its own chunks."""
    model, _ = _seeded()
    eng = _engine(model, num_slots=4)
    eng.submit(_prompts((6,))[0], max_new_tokens=12)
    eng.step()                          # slot 0 decodes from here on
    eng.submit(_prompts((27,))[0], max_new_tokens=2)
    seen_chunks = []
    for _ in range(6):
        before = np.asarray(eng._cache["conv"])
        pf = eng._prefill
        eng.step()
        after = np.asarray(eng._cache["conv"])
        assert np.array_equal(after[:, 2:4], before[:, 2:4])   # idle slots
        if pf is not None:
            seen_chunks.append(pf.slot)
            assert not np.array_equal(after[:, pf.slot], before[:, pf.slot])
        assert not np.array_equal(after[:, 0], before[:, 0])   # decodes
    assert seen_chunks and set(seen_chunks) == {1}
    # one row a slot and the null row, which chunk-free ticks write to
    assert eng.state_rows == (eng.last_occupancy, 5)


def test_state_spans_and_gauges():
    model, _ = _seeded()
    eng = _engine(model)
    for p in _prompts((20, 6)):
        eng.submit(p, max_new_tokens=4)
    eng.drain()
    events = obs.get_tracer().events()
    chunks = [e["args"] for e in events if e["name"] == "serving.chunk"]
    assert [c["state"] for c in chunks if c["slot"] == chunks[0]["slot"]][:3] \
        == ["fresh", "carried", "carried"]
    decodes = [e["args"] for e in events if e["name"] == "serving.decode"]
    assert all(d["state_rows"] == d["slots"] for d in decodes)
    snap = obs.snapshot()
    mine = {name: next(r["value"] for r in snap[name]["series"]
                       if r["labels"]["engine"] == eng._eid)
            for name in ("kv_cache.state_rows", "kv_cache.state_rows_live",
                         "kv_cache.state_bytes")}
    assert mine["kv_cache.state_rows"] == 4
    # 4 convolution layers x 4 rows x 2 carried inputs x 64 channels, f32
    assert mine["kv_cache.state_bytes"] == 4 * 4 * 2 * 64 * 4
    assert eng.cache_hbm_bytes == mine["kv_cache.state_bytes"] + \
        eng._cache["attn"].nbytes
    # the pool is built for the layers that hold K/V: 2 of 6
    assert eng._cache["attn"].shape[0] == 2
    assert eng.kv._block_nbytes["bf16"] == 2 * 2 * 2 * 16 * BLOCK * 4


REFUSED = {
    "contiguous": {"paged": False},
    "wave": {"chunked": False},
    "prefix_cache": {"prefix_cache": True},
    "preempt_swap": {"preempt": "swap", "host_blocks": 4},
    "preempt_recompute": {"preempt": "recompute"},
    "host_tier": {"host_blocks": 4},
    "int8_kv": {"kv_cache_dtype": "int8"},
    "mesh": {"mesh": "mp2"},
    "spec_decode": {"spec_decode": True},
    "int8_weights": {"int8_weights": True},
}


@pytest.mark.parametrize("layout", list(REFUSED))
def test_unsupported_layouts_refuse_by_name(layout):
    model, _ = _seeded()
    with pytest.raises(NotImplementedError,
                       match="Lfm2MoeForCausalLM cannot be served with"):
        _engine(model, **REFUSED[layout])


def test_export_and_import_refuse_by_name():
    model, _ = _seeded()
    eng = _engine(model)
    rid = eng.submit(_prompts((12,))[0], max_new_tokens=8)
    for _ in range(3):
        eng.step()
    with pytest.raises(NotImplementedError, match="per-slot state"):
        eng.export_request(rid)
    with pytest.raises(NotImplementedError, match="per-slot state"):
        eng.import_request({})


@pytest.mark.parametrize("family", ["mamba", "rwkv"])
def test_models_with_undeclared_state_go_through_the_same_door(family):
    if family == "mamba":
        from paddle_tpu.models.mamba import (Mamba2ForCausalLM as M,
                                             tiny_mamba2_config as tiny)
    else:
        from paddle_tpu.models.rwkv import (RwkvForCausalLM as M,
                                            tiny_rwkv_config as tiny)
    pt.seed(9)
    model = M(tiny())
    model.eval()
    with pytest.raises(NotImplementedError,
                       match="does not declare it as serving state"):
        ServingEngine(model, num_slots=2, max_length=32)


def test_a_model_without_state_has_none_of_it():
    """llama's step program takes no ``cslot``, its engine counts no state
    rows and registers none of the state's series."""
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    eng = ServingEngine(model, num_slots=2, max_length=64, paged=True,
                        chunked=True, prefill_chunk=8, block_len=8)
    assert "cslot" not in [o.name for o in eng._step_table]
    assert eng.state_rows is None
    rid = eng.submit(np.asarray(_ids((12,))), max_new_tokens=3)
    eng.drain()
    assert len(eng.result(rid)) == 3
    snap = obs.snapshot()
    for name in ("kv_cache.state_rows", "kv_cache.state_rows_live",
                 "kv_cache.state_bytes"):
        assert not any(r["labels"].get("engine") == eng._eid
                       for r in snap.get(name, {}).get("series", ()))
    events = obs.get_tracer().events()
    assert not any("state" in e.get("args", {})
                   or "state_rows" in e.get("args", {})
                   for e in events if e["name"].startswith("serving."))
    lfm = _engine(_seeded()[0])
    assert "cslot" in [o.name for o in lfm._step_table]


def test_engine_preflight_counts_the_kv_layers():
    model, _ = _seeded()
    eng = _engine(model, max_length=256, prefill_chunk=64, block_len=128)
    report = eng.kernel_preflight()
    assert not report["findings"], report["findings"]
    assert eng._kv_layers == 2
    walk = eng._kv_walk((np.asarray([200, 5, 0]), 1))
    # two K/V layers: rows at depth 200 and 5 walk 2 + 1 blocks each layer
    assert walk["kv_blocks"] == 2 * (2 + 1 + 1)


# -- the two shares -----------------------------------------------------------

def _moe(ep_size=1, ep_rank=0):
    pt.seed(0)
    return Lfm2MoE(_config(ep_size=ep_size, ep_rank=ep_rank, hidden_size=32,
                           moe_intermediate_size=16))


def test_two_shares_add_up_to_the_uncut_layer():
    """The share test: rank 0's and rank 1's routed parts (4 + 4 experts of
    8) are the layer with every expert held; there is no shared expert, so
    nothing is counted once.  And the uncut layer is the plain loop."""
    whole = _moe()
    whole.gate.expert_bias = jax.random.normal(jax.random.key(9), (8,)) * 0.5
    x = jax.random.normal(jax.random.key(1), (3, 7, 32))
    idx, w = whole.gate.route(x.reshape(-1, 32))
    total = jnp.zeros_like(x)
    for rank in range(2):
        part = _moe(ep_size=2, ep_rank=rank)
        lo, hi = part.experts.held
        assert (lo, hi) == (4 * rank, 4 * rank + 4)
        for name in ("gate_proj", "up_proj", "down_proj"):
            setattr(part.experts, name,
                    getattr(whole.experts, name)[lo:hi])
        part.gate.weight = whole.gate.weight
        part.gate.expert_bias = whole.gate.expert_bias
        total = total + part(x)
    want = whole(x)
    assert float(jnp.abs(total - want).max()) < 1e-5
    plain = jnp.zeros((21, 32))
    xt = x.reshape(-1, 32)
    for t in range(21):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t])):
            h = jax.nn.silu(xt[t] @ whole.experts.gate_proj[e]) * (
                xt[t] @ whole.experts.up_proj[e])
            plain = plain.at[t].add(we * (h @ whole.experts.down_proj[e]))
    assert float(jnp.abs(plain.reshape(x.shape) - want).max()) < 1e-5
    # the weights are the unbiased scores over their sum + 1e-6
    s = jax.nn.sigmoid(xt @ whole.gate.weight)
    picked = jnp.take_along_axis(s, idx, -1)
    assert float(jnp.abs(w - picked / (picked.sum(-1, keepdims=True)
                                       + 1e-6)).max()) < 1e-6


# -- the flash-decode kernel at head size 64 ----------------------------------

@pytest.mark.parametrize("s,pos", [(1, [700, 5, 300]), (40, [600, 0, 250])],
                         ids=["decode", "chunk"])
def test_kernel_at_head_size_64_matches_the_xla_reference(s, pos):
    """Half a lane tile a head: the body slices each group's (keys, Hkv·D)
    buffer at 64-lane offsets.  Interpret mode, against the XLA reference,
    every block outside a row's walk NaN."""
    hkv, hq, d, bl = 2, 8, 64, 128
    key = jax.random.key(s)
    pool = jax.random.normal(key, (2, 2, 40, bl, hkv * d), jnp.float32)
    rng = np.random.default_rng(s)
    bt = rng.permutation(np.arange(1, 40))[:3 * 8].reshape(3, 8)
    pos = jnp.asarray(pos, jnp.int32)
    live = np.zeros(40, bool)
    for row, p in zip(bt, np.asarray(pos)):
        live[row[:(int(p) + s - 1) // bl + 1]] = True
    holed = jnp.where(jnp.asarray(live)[None, None, :, None, None], pool,
                      jnp.nan)
    q = jax.random.normal(jax.random.fold_in(key, 1), (3, s, hq, d))
    bt = jnp.asarray(bt, jnp.int32)
    got = paged_decode_attention_pallas(q, holed, 1, pos, bt, interpret=True)
    want = paged_decode_attention_reference(q, pool, 1, pos, bt)
    assert float(jnp.abs(got - want).max()) < 2e-5
