"""The Olmo-Hybrid model (models/olmo_hybrid.py): Gated DeltaNet layers
whose per-slot state — a float32 matrix a head and a bf16-typed convolution
window — lives beside the paged pool that only the attention layers write,
and the serving engine serving it: two slot leaves of unlike type, state
that no position addresses and that must never take junk, updated in place.
Tiny widths, seeded weights, CPU, Pallas interpreted where a kernel is
meant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights_olmo_hybrid
from benchmark.reference import olmo_hybrid_arch
from paddle_tpu import nn
from paddle_tpu import observability as obs
from paddle_tpu.models import (OlmoHybridConfig, OlmoHybridForCausalLM,
                               tiny_olmo_hybrid_config)
from paddle_tpu.serving import ServingEngine
from test_gated_delta import interpret_mode as interpreted

LINEAR, FULL = "linear_attention", "full_attention"
# the benchmark's configuration keys of the tiny model, as its files hold
# them.  Matrices at unit gain for this width
REF = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
       "num_hidden_layers": 5, "num_attention_heads": 4,
       "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": False, "attention_bias": False,
       "layer_types": [LINEAR, LINEAR, LINEAR, FULL, LINEAR],
       "linear_num_key_heads": 4, "linear_num_value_heads": 4,
       "linear_key_head_dim": 16, "linear_value_head_dim": 32,
       "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
       "dtype": "float32", "initializer_range": 0.125}
CHUNK = BLOCK = 8
STATE = ("conv", "delta")


def _seeded(seed=3, **over):
    """(model, weights under the reference's names) of the tiny REF."""
    with nn.abstract_parameters():
        model = OlmoHybridForCausalLM(tiny_olmo_hybrid_config(
            max_position_embeddings=256, **over))
    model.eval()
    made = weights_olmo_hybrid.make_weights(REF, seed, "float32")
    model.set_state_dict({weights_olmo_hybrid.program_name(n): w
                          for n, w in made.items()})
    return model, made


def _ids(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(1, 256, shape),
                       jnp.int32)


def test_forward_matches_the_plain_reference():
    model, made = _seeded()
    ids = _ids((2, 37))
    got = model(ids)
    for b in range(2):
        want = olmo_hybrid_arch.logits(made, REF, np.asarray(ids[b]))
        assert float(jnp.abs(got[b] - want).max()) < 2e-4
    # the state matters: without history the logits are another model's
    lost = olmo_hybrid_arch.logits(made, REF, np.asarray(ids[0]),
                                   history=False)
    assert float(jnp.abs(got[0, 1:] - lost[1:]).max()) > 0.1


def test_generate_matches_forward():
    model, _ = _seeded()
    ids = _ids((2, 11))
    out = model.generate(ids, max_new_tokens=6)
    assert out.shape == (2, 17)
    again = jnp.argmax(model(out[:, :-1]), -1)
    assert np.array_equal(out[:, 11:], again[:, 10:])


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig(num_hidden_layers=2, layer_types=("conv", FULL))
    with pytest.raises(NotImplementedError, match="key heads"):
        OlmoHybridConfig(linear_num_key_heads=15)
    with pytest.raises(NotImplementedError, match="attention bias"):
        OlmoHybridConfig(attention_bias=True)
    published = OlmoHybridConfig()
    assert published.layers_of(FULL) == tuple(range(3, 32, 4))
    assert published.head_dim == 128 and published.conv_channels == 11520


# -- the cache form, as the engine's step program drives it ------------------

def _serving_cache(model, slots):
    """The model's serving cache with JUNK in every state row of both
    leaves (a slot is reused without a reset) and a table row of 6 blocks a
    slot."""
    cache = model.init_serving_cache(slots, 1 + 6 * slots, BLOCK)
    junk = {k: jax.random.normal(jax.random.key(i), cache[k].shape,
                                 cache[k].dtype)
            for i, k in enumerate(STATE)}
    tables = 1 + np.arange(6 * slots, dtype=np.int32).reshape(slots, 6)
    return dict(cache, **junk), jnp.asarray(tables)


def _part(model, cache, ids, pos, tables, valid, slots):
    from paddle_tpu.models.parts import DecodePart
    (logits,), cache = model.decode_parts(
        [DecodePart(ids, pos, tables, valid, slots=slots)], cache)
    return logits, cache


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla_twin", "pallas_interpret"])
@pytest.mark.parametrize("plen", [CHUNK + 1, CHUNK + 5, 2 * CHUNK])
def test_chunks_then_decode_through_the_cache_match_forward(plen, interpret):
    """A prompt cut into a fresh chunk and a continuation that reads the
    carried state (a pad tail of 7, 3 and 0), in slot 1 of 3 whose rows of
    both leaves held junk; then four decode steps in a rows part where row
    0 is idle and row 2 belongs to a prefilling slot.  Logits against the
    full forward pass; the other rows' state bit for bit what it was."""
    model, _ = _seeded()
    ids = np.asarray(_ids((plen + 4,), seed=plen))
    want = model(jnp.asarray(ids)[None])[0]
    cache, tables = _serving_cache(model, 3)
    before = {k: np.asarray(cache[k]) for k in STATE}
    with interpreted(interpret):
        for start in (0, CHUNK):
            clen = min(CHUNK, plen - start)
            cids = np.zeros((1, CHUNK), np.int32)
            cids[0, :clen] = ids[start:start + clen]
            logits, cache = _part(
                model, cache, jnp.asarray(cids), jnp.asarray([start]),
                tables[1:2], (jnp.arange(CHUNK) < clen)[None],
                (jnp.asarray(1), 1))
            assert float(jnp.abs(logits[0, :clen]
                                 - want[start:start + clen]).max()) < 2e-4
        held = {k: np.asarray(cache[k]) for k in STATE}
        for k in STATE:
            assert np.array_equal(held[k][:, 0], before[k][:, 0])
            assert np.array_equal(held[k][:, 2], before[k][:, 2])
        active = jnp.asarray([False, True, False])
        null = jnp.zeros((1, 6), jnp.int32)
        for step in range(4):
            pos = plen + step
            toks = jnp.asarray([[7], [ids[pos]], [9]], jnp.int32)
            logits, cache = _part(
                model, cache, toks, jnp.asarray([3, pos, 0]),
                jnp.concatenate([null, tables[1:2], null]),
                active[:, None], (0, 3))
            assert float(jnp.abs(logits[1, 0] - want[pos]).max()) < 2e-4
            # an idle row and a prefilling slot's row: held, at position 0
            # too
            for k in STATE:
                assert np.array_equal(cache[k][:, 0], before[k][:, 0])
                assert np.array_equal(cache[k][:, 2], before[k][:, 2])
    for k in STATE:
        assert not np.array_equal(cache[k][:, 1], held[k][:, 1])
    assert cache["delta"].dtype == jnp.float32


def test_a_chunk_of_padding_alone_leaves_the_state_alone():
    """The chunk-free tick's chunk part: no valid token, nothing moves
    (the engine aims it at the null row all the same), in either leaf, by
    either path."""
    model, _ = _seeded()
    cache, _ = _serving_cache(model, 2)
    for interpret in (False, True):
        with interpreted(interpret):
            _, after = _part(
                model, cache, jnp.zeros((1, CHUNK), jnp.int32),
                jnp.asarray([0]), jnp.zeros((1, 6), jnp.int32),
                jnp.zeros((1, CHUNK), bool), (jnp.asarray(1), 1))
        for k in STATE:
            assert np.array_equal(after[k], cache[k])


# -- the serving engine ------------------------------------------------------

def _engine(model, **kw):
    kw = {"num_slots": 3, "max_length": 64, "paged": True, "chunked": True,
          "prefill_chunk": CHUNK, "block_len": BLOCK, "prefix_cache": False,
          **kw}
    return ServingEngine(model, **kw)


def _prompts(lengths):
    return [np.asarray(_ids((n,), seed=n)) for n in lengths]


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla_twin", "pallas_interpret"])
def test_engine_tokens_equal_generate_over_more_requests_than_slots(
        interpret):
    """Seven requests through three slots, prompts of under one chunk to
    almost four: every slot is reused, chunks and decode rows share ticks
    (the mixed program) and chunk-free ticks run the rows-alone program;
    every token is ``generate()``'s greedy token, and the served logits'
    argmax the plain reference's."""
    model, made = _seeded()
    prompts = _prompts((5, 17, 9, 23, 12, 3, 30))
    with interpreted(interpret):
        eng = _engine(model)
        rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.drain()
    assert eng.step_traces == 1
    for rid, p in zip(rids, prompts):
        want = np.asarray(model.generate(jnp.asarray(p)[None],
                                         max_new_tokens=8))[0, len(p):]
        assert eng.result(rid) == [int(t) for t in want]
        full = np.concatenate([p, want])
        ref = olmo_hybrid_arch.logits(made, REF, full[:-1])
        assert np.array_equal(np.argmax(ref[len(p) - 1:], -1), want)
    assert len({tuple(eng.result(r)) for r in rids}) > 3    # not degenerate
    paths = {(r["labels"]["op"], r["labels"]["path"])
             for r in obs.snapshot()["ops.kernel_path"]["series"]
             if r["labels"]["op"].startswith("gated_delta")}
    want_path = "pallas" if interpret else "xla_math"
    assert {("gated_delta_step", want_path),
            ("gated_delta_chunk", want_path)} <= paths


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
    # the slot's rows held the first request's state when the second came
    for k in STATE:
        assert float(jnp.abs(used._cache[k][:, 0]).max()) > 0


def test_rows_part_leaves_idle_and_prefilling_rows_alone():
    """Tick by tick: while one request decodes and another streams its
    prompt, the state rows of idle slots never change, and a prefilling
    slot's rows change only by its own chunks — in both leaves."""
    model, _ = _seeded()
    eng = _engine(model, num_slots=4)
    eng.submit(_prompts((6,))[0], max_new_tokens=12)
    eng.step()                          # slot 0 decodes from here on
    eng.submit(_prompts((27,))[0], max_new_tokens=2)
    seen_chunks = []
    for _ in range(6):
        before = {k: np.asarray(eng._cache[k]) for k in STATE}
        pf = eng._prefill
        eng.step()
        for k in STATE:
            after = np.asarray(eng._cache[k])
            assert np.array_equal(after[:, 2:4], before[k][:, 2:4])
            if pf is not None:
                assert not np.array_equal(after[:, pf.slot],
                                          before[k][:, pf.slot])
            assert not np.array_equal(after[:, 0], before[k][:, 0])
        if pf is not None:
            seen_chunks.append(pf.slot)
    assert seen_chunks and set(seen_chunks) == {1}
    # one row a slot and the null row, which chunk-free ticks aim at
    assert eng.state_rows == (eng.last_occupancy, 5)


def test_two_slot_leaves_of_unlike_type_and_their_gauges():
    model, _ = _seeded(dtype="bfloat16")
    eng = _engine(model)
    cache = eng._cache
    assert cache["conv"].dtype == jnp.bfloat16
    assert cache["delta"].dtype == jnp.float32
    # 4 linear layers x (3 slots + the null row)
    assert cache["conv"].shape == (4, 4, 3 * 4 * (16 + 16 + 32))
    assert cache["delta"].shape == (4, 4, 16, 4 * 32)
    assert cache["attn"].shape[0] == 1          # one K/V layer of five
    # the copy group's input, by the engine and off the pool as stored
    pool = cache["attn"]
    assert eng._kv_key_bytes == (
        pool.shape[1] * pool.shape[-1] * pool.dtype.itemsize)
    for p in _prompts((20, 6)):
        eng.submit(p, max_new_tokens=4)
    eng.drain()
    assert eng._cache["delta"].dtype == jnp.float32
    snap = obs.snapshot()
    mine = {name: next(r["value"] for r in snap[name]["series"]
                       if r["labels"]["engine"] == eng._eid)
            for name in ("kv_cache.state_rows", "kv_cache.state_bytes")}
    assert mine["kv_cache.state_rows"] == 4
    want = 4 * 4 * (3 * 256 * 2 + 16 * 128 * 4)     # both leaves
    assert mine["kv_cache.state_bytes"] == want
    assert eng.cache_hbm_bytes == want + eng._cache["attn"].nbytes
    events = obs.get_tracer().events()
    chunks = [e["args"] for e in events if e["name"] == "serving.chunk"]
    assert [c["state"] for c in chunks
            if c["slot"] == chunks[0]["slot"]][:3] == [
                "fresh", "carried", "carried"]


def test_the_cost_model_counts_a_rows_state():
    """A tick's predicted HBM time grows with the rows that decode by what
    each reads and writes of its matrix state; a model without such state
    pays nothing for it."""
    model, _ = _seeded()
    eng = _engine(model)
    cost = eng._perf.model
    # read + write of 4 layers of S and of the window, float32 here
    per_row = 2 * 4 * (4 * 16 * 32 * 4 + 3 * 256 * 4)
    assert cost.state_row_bytes == per_row
    one = cost.predict(1, 10)["state_stream_ms"]
    three = cost.predict(3, 10)["state_stream_ms"]
    assert one > 0 and three == pytest.approx(3 * one)
    assert cost.predict(3, 10)["predicted_ms"] >= (
        cost.predict(3, 10)["weight_stream_ms"] + three)


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
                       match="OlmoHybridForCausalLM cannot be served with"):
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


def test_engine_preflight_has_the_two_kernels_specs():
    model, _ = _seeded()
    eng = _engine(model, max_length=256, prefill_chunk=64, block_len=128)
    report = eng.kernel_preflight()
    assert not report["findings"], report["findings"]
    ops = {k["op"] for k in report["kernels"]}
    assert {"gated_delta_step", "gated_delta_chunk"} <= ops
    assert eng._kv_layers == 1
    # at the published widths the step holds a row's S four times over
    from paddle_tpu.static_analysis import gated_delta_specs, vmem_footprint
    step, walk = gated_delta_specs(12, 30, 96, 192, rows=80, chunk=256)
    assert 4 * 2211840 < vmem_footprint(step) < 16 << 20
    assert vmem_footprint(walk) < 4 << 20


@pytest.mark.parametrize("hkv,d,want", [(8, 128, 4), (30, 128, 2),
                                        (8, 64, 4), (4, 128, 4)])
def test_a_wide_pools_copy_group_is_cut_to_fit_vmem(hkv, d, want):
    """The flash-decode walk's copy group: 512 keys at every benchmarked
    width, 256 where K and V of a key are 15 KiB (30 K/V heads of 128)."""
    from paddle_tpu.ops.pallas.decode_attention import (
        group_blocks, stored_key_bytes, walk_counts)
    key = stored_key_bytes(hkv * d, 2, "bfloat16")
    assert key == 2 * hkv * d * 2
    assert group_blocks(128, key_bytes=key) == want
    need, walked = walk_counts(np.asarray([700]), 1, 1, bk=128, n_cols=32,
                               key_bytes=key)
    assert need == 6 and walked == -(-6 // want) * want
