"""The AFMoE model (models/afmoe.py), the dropless held-experts layer and its
sigmoid router (distributed/moe.py), the sliding window in the cached
attention path, and the serving engine serving all of it — tiny widths,
seeded weights, CPU, Pallas interpreted where a kernel is meant."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.harness import weights_afmoe
from benchmark.reference import afmoe_arch
from paddle_tpu import flags, nn
from paddle_tpu import observability as obs
from paddle_tpu.distributed import moe
from paddle_tpu.models import (AfmoeConfig, AfmoeForCausalLM,
                               LlamaForCausalLM, tiny_afmoe_config,
                               tiny_llama_config)
from paddle_tpu.models.afmoe import AfmoeMoE
from paddle_tpu.ops.attention import (cached_decode_attention_reference,
                                      paged_decode_attention,
                                      paged_decode_attention_reference)
from paddle_tpu.ops.pallas.decode_attention import (
    decode_attention_pallas, paged_decode_attention_pallas)
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas
from paddle_tpu.serving import ServingEngine
from paddle_tpu.static_analysis.core import iter_eqns

# the benchmark's configuration keys of the tiny model, as its files hold
# them (num_experts is the number HELD; the router keeps num_experts_routed)
REF = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_hidden_layers": 5,
       "num_dense_layers": 1, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
       "num_experts_routed": 8, "ep_size": 2, "ep_rank": 1,
       "num_experts_per_tok": 2, "num_shared_experts": 1,
       "route_norm": True, "route_scale": 2.448, "sliding_window": 16,
       "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
       "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "mup_enabled": True,
       "dtype": "float32"}


def _config(**over):
    return tiny_afmoe_config(max_position_embeddings=256, **over)


def _seeded(cfg=None, seed=3):
    """(model, weights under the reference's names) of the tiny REF."""
    cfg = cfg or _config(ep_size=2, ep_rank=1)
    with nn.abstract_parameters():
        model = AfmoeForCausalLM(cfg)
    model.eval()
    made = weights_afmoe.make_weights(REF, seed, "float32")
    model.set_state_dict({weights_afmoe.program_name(n): w
                          for n, w in made.items()})
    return model, made


def _ids(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(1, 256, shape),
                       jnp.int32)


# -- the model against the plain reference ----------------------------------

def test_forward_matches_the_plain_reference():
    """Every rule of the layer at once — sandwich norms, q/k norms, RoPE on
    window layers only, the window, the gate, the scaled embedding, the
    router with its bias, the held share (rank 1 of 2), the shared expert —
    against benchmark/reference/afmoe_arch.py, which imports nothing of the
    program; float32 on both sides."""
    model, made = _seeded()
    ids = _ids((40,))                    # past the window of 16
    want = afmoe_arch.logits(made, REF, np.asarray(ids))
    got = model(ids[None])[0]
    assert float(jnp.abs(got - want).max()) < 2e-4
    # and the reference's controls are different functions
    assert float(jnp.abs(afmoe_arch.logits(made, REF, np.asarray(ids),
                                           window=False) - want).max()) > 1e-2


def test_generate_matches_forward_past_the_window():
    model, _ = _seeded()
    ids = _ids((2, 20))
    out = model.generate(ids, max_new_tokens=10)
    teacher = jnp.argmax(model(out[:, :-1]), -1)[:, 19:]
    assert (np.asarray(out[:, 20:]) == np.asarray(teacher)).all()


def test_abstract_parameters_builds_without_initialising():
    with nn.abstract_parameters():
        model = AfmoeForCausalLM(_config())
    params = dict(model.named_parameters())
    assert all(isinstance(p.value, jax.ShapeDtypeStruct)
               for p in params.values())
    # buffers are computed as ever, and a placeholder is no array
    assert isinstance(model.model.rope_cos, jax.Array)
    with pytest.raises(Exception):
        model(_ids((1, 4)))
    with pytest.raises(ValueError, match="shape mismatch"):
        model.set_state_dict({"lm_head": jnp.zeros((3, 3))}, strict=False)


# -- the serving engine ------------------------------------------------------

def _serve(model, prompts, new_tokens, **kw):
    eng = ServingEngine(model, num_slots=4, max_length=128, paged=True,
                        block_len=8, num_blocks=70, **kw)
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    eng.drain()
    return eng, [eng.result(r) for r in rids]


@pytest.mark.parametrize("chunked", [True, False], ids=["chunked", "wave"])
def test_engine_serves_past_the_window_like_forward(chunked):
    """Prefill (in chunks of one block, or as a wave) then decode through
    the paged pool: a window of 2 blocks, contexts of 5; every served
    token is forward()'s greedy token on the same prefix."""
    model, _ = _seeded()
    prompts = [np.asarray(_ids((n,), seed=n)) for n in (28, 9, 33)]
    kw = {"chunked": True, "prefill_chunk": 8} if chunked else {}
    eng, served = _serve(model, prompts, 9, **kw)
    assert eng.step_traces == 1
    for p, toks in zip(prompts, served):
        full = jnp.asarray(np.concatenate([p, toks]))[None]
        want = jnp.argmax(model(full[:, :-1]), -1)[0, len(p) - 1:]
        assert toks == [int(t) for t in want]
    assert max(len(p) for p in prompts) + 9 >= 5 * 8


def test_engine_counts_expert_load_and_window_dead_positions():
    model, _ = _seeded()
    prompts = [np.asarray(_ids((n,), seed=n)) for n in (28, 9)]
    eng = ServingEngine(model, num_slots=4, max_length=128, paged=True,
                        block_len=8, num_blocks=70, chunked=True,
                        prefill_chunk=8)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    dead = []
    while eng.queue_depth or eng.last_occupancy or not dead:
        eng.step()
        dead.append(eng.window_dead_positions)
    load = eng.expert_load
    tokens = sum(len(p) for p in prompts) + 2 * 5   # the last token is
    pairs = load["pairs"]                           # sampled, not fed
    assert pairs.shape == (4, 4)                    # expert layers x held
    # every real token sends top_k pairs to each layer, held or elsewhere
    assert pairs.sum() + load["pairs_elsewhere"] == tokens * 2 * 4
    assert 0 < load["experts_touched"] <= load["layer_calls"] * 4
    # the registry's series: one a held expert over the layers (what the
    # cap on a family's children holds whole), equal to the exact totals
    snap = obs.snapshot()
    by_expert = {int(r["labels"]["expert"]): r["value"]
                 for r in snap["moe.expert_load"]["series"]
                 if r["labels"].get("engine") == eng._eid}
    assert by_expert == dict(enumerate(pairs.sum(axis=0)))
    touched = [r for r in snap["moe.experts_touched"]["series"]
               if r["labels"].get("engine") == eng._eid]
    assert (touched[0]["count"], touched[0]["sum"]) == (
        load["layer_calls"], load["experts_touched"])
    assert snap["moe.pairs_elsewhere"]["series"][0]["value"] == \
        load["pairs_elsewhere"]
    # the 28-token prompt's last tick wrote position 32: 32 + 1 - 16
    # positions lay behind the window on each of the 4 window layers
    assert max(dead) == 17 * 4
    assert "kv_cache.window_dead_positions" in snap


def test_llama_tick_is_what_it_was():
    """A model without experts: the step programs return tokens and cache
    and nothing else, the engine keeps no expert counters, and a window
    left out builds the kernel call it always built."""
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    for kw, n_out in (({}, 2), ({"chunked": True, "prefill_chunk": 8}, 3)):
        eng = ServingEngine(model, num_slots=2, max_length=64, paged=True,
                            block_len=8, **kw)
        out = jax.eval_shape(eng._step_fn.python_fn, *eng._lint_args())
        assert len(out) == n_out
        rid = eng.submit(np.asarray(_ids((12,))), max_new_tokens=3)
        eng.drain()
        assert len(eng.result(rid)) == 3
        assert eng.expert_load is None and eng.window_dead_positions == 0
        # and registers no series only another kind of model feeds
        snap = obs.snapshot()
        for name in ("moe.expert_load", "moe.pairs_elsewhere",
                     "moe.experts_touched", "kv_cache.window_dead_positions"):
            assert not any(r["labels"].get("engine") == eng._eid
                           for r in snap.get(name, {}).get("series", ()))
    assert "moe.expert_load" not in obs.snapshot()


def test_window_none_is_the_same_program():
    q = jnp.zeros((2, 1, 4, 128), jnp.float32)
    pool = jnp.zeros((1, 2, 9, 128, 256), jnp.float32)
    pos = jnp.asarray([200, 5], jnp.int32)
    bt = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q, pool: paged_decode_attention_pallas(
            q, pool, 0, pos, bt, interpret=True, **kw))(q, pool))
    assert text() == text(window=None)
    assert text() != text(window=128)


@pytest.mark.parametrize("layout", [
    {"paged": False}, {"kv_cache_dtype": "int8"}, {"mesh": "mp2"},
    {"spec_decode": True}, {"int8_weights": True}],
    ids=lambda kw: next(iter(kw)))
def test_unsupported_layouts_refuse_by_name(layout):
    model, _ = _seeded()
    kw = {"num_slots": 2, "max_length": 64, "paged": True, "block_len": 8,
          **layout}
    with pytest.raises(NotImplementedError, match="AfmoeForCausalLM cannot"):
        ServingEngine(model, **kw)


def test_engine_preflight_covers_the_window_and_the_grouped_product():
    model, _ = _seeded()
    eng = ServingEngine(model, num_slots=2, max_length=256, paged=True,
                        chunked=True, prefill_chunk=64, block_len=128)
    assert not eng.kernel_preflight()["findings"]    # 64 x 32: no kernel
    pt.seed(0)
    wide = AfmoeForCausalLM(_config(hidden_size=128,
                                    moe_intermediate_size=256))
    eng = ServingEngine(wide, num_slots=2, max_length=256, paged=True,
                        chunked=True, prefill_chunk=64, block_len=128)
    report = eng.kernel_preflight()
    assert not report["findings"], report["findings"]
    names = [f"{k['op']}[{k['variant']}]" for k in report["kernels"]]
    assert any("window=16" in n for n in names), names
    assert any(n.startswith("moe_experts[") for n in names), names


# -- the window in the cached-attention kernel -------------------------------

def _pool_case(s, seed=0):
    key = jax.random.key(seed)
    pool = jax.random.normal(key, (2, 2, 40, 128, 2 * 64), jnp.float32)
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(np.arange(1, 40))[:18].reshape(3, 6),
                     jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, s), (3, s, 4, 64),
                          jnp.float32)
    return q, pool, bt


@pytest.mark.parametrize("s,pos", [(1, [700, 5, 300]), (40, [600, 0, 250])],
                         ids=["decode_rows", "chunk"])
@pytest.mark.parametrize("window", [256, 100, 1])
def test_windowed_kernel_matches_the_xla_reference(s, pos, window):
    q, pool, bt = _pool_case(s)
    pos = jnp.asarray(pos, jnp.int32)
    got = paged_decode_attention_pallas(q, pool, 1, pos, bt, interpret=True,
                                        window=window)
    want = paged_decode_attention_reference(q, pool, 1, pos, bt,
                                            window=window)
    assert float(jnp.abs(got - want).max()) < 1e-5
    full = paged_decode_attention_reference(q, pool, 1, pos, bt)
    assert float(jnp.abs(full - want).max()) > 1e-3    # the window binds


def test_windowed_contiguous_kernel_and_dispatch():
    key = jax.random.key(1)
    k = jax.random.normal(key, (2, 512, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 1), (2, 512, 2, 64))
    q = jax.random.normal(jax.random.fold_in(key, 2), (2, 1, 4, 64))
    pos = jnp.asarray([500, 130], jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, interpret=True, window=128,
                                  block_kv=128)
    want = cached_decode_attention_reference(q, k, v, pos, window=128)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # the dispatcher hands the window to whichever path it takes
    q3, pool, bt = _pool_case(1)
    p3 = jnp.asarray([700, 5, 300], jnp.int32)
    assert float(jnp.abs(
        paged_decode_attention(q3, pool, 1, p3, bt, window=100)
        - paged_decode_attention_reference(q3, pool, 1, p3, bt, window=100)
    ).max()) < 1e-5


# -- the router and the held experts -----------------------------------------

def _moe_parts(ep_size=1, ep_rank=0, seed=0):
    pt.seed(seed)
    cfg = _config(ep_size=ep_size, ep_rank=ep_rank, hidden_size=32,
                  moe_intermediate_size=16)
    return AfmoeMoE(cfg), cfg


def test_ranks_partial_outputs_add_up_to_the_uncut_layer():
    """The share test: the 8 ranks' parts of the routed result, plus the
    shared expert once, are the layer with every expert held."""
    whole, _ = _moe_parts()
    bias = jax.random.normal(jax.random.key(9), (8,)) * 0.5
    whole.router.expert_bias = bias
    x = jax.random.normal(jax.random.key(1), (3, 7, 32))
    idx, w = whole.router.route(x.reshape(-1, 32))
    total = jnp.zeros_like(x)
    for rank in range(8):
        part, _ = _moe_parts(ep_size=8, ep_rank=rank)
        for name in ("gate_proj", "up_proj", "down_proj"):
            setattr(part.experts, name,
                    getattr(whole.experts, name)[rank:rank + 1])
        assert part.experts.held == (rank, rank + 1)
        total = total + part.experts(x, idx, w)
    want = whole(x)
    got = total + whole.shared_experts(x)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # and the uncut layer is the plain loop over chosen experts
    plain = whole.shared_experts(x).reshape(-1, 32)
    xt = x.reshape(-1, 32)
    for t in range(xt.shape[0]):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t])):
            h = jax.nn.silu(xt[t] @ whole.experts.gate_proj[e]) * (
                xt[t] @ whole.experts.up_proj[e])
            plain = plain.at[t].add(we * (h @ whole.experts.down_proj[e]))
    assert float(jnp.abs(plain.reshape(x.shape) - want).max()) < 1e-5


def test_dropless_under_skew():
    """Every token to one expert: no capacity, so none is dropped."""
    layer, _ = _moe_parts()
    x = jax.random.normal(jax.random.key(2), (64, 32))
    idx = jnp.full((64, 2), 5, jnp.int32).at[:, 1].set(2)
    w = jnp.ones((64, 2), jnp.float32)
    with moe.expert_load() as load:
        got = layer.experts(x, idx, w)

    def expert(e):
        return (jax.nn.silu(x @ layer.experts.gate_proj[e])
                * (x @ layer.experts.up_proj[e])) @ layer.experts.down_proj[e]
    assert float(jnp.abs(got - expert(5) - expert(2)).max()) < 1e-5
    counts = np.asarray(load[0])
    assert counts[5] == 64 and counts[2] == 64 and counts.sum() == 128
    # an expert no pair chose has no rows: the grouped product skips it
    assert (counts[[0, 1, 3, 4, 6, 7]] == 0).all() and counts[8] == 0


def test_bias_moves_selection_and_not_weight():
    gate = moe.SigmoidTopKGate(16, 8, 2, route_scale=2.448)
    x = jax.random.normal(jax.random.key(3), (32, 16))
    idx0, w0 = gate.route(x)
    gate.expert_bias = jnp.zeros((8,)).at[6].set(10.0)
    idx1, w1 = gate.route(x)
    assert (np.asarray(idx1)[:, 0] == 6).all()          # always chosen
    assert not (np.asarray(idx0) == 6).any(axis=1).all()
    scores = jax.nn.sigmoid(gate.logits(x))
    chosen = jnp.take_along_axis(scores, idx1, axis=-1)
    want = chosen / chosen.sum(-1, keepdims=True) * 2.448
    assert float(jnp.abs(w1 - want).max()) < 1e-6       # no bias in it
    assert float(jnp.abs(w1.sum(-1) - 2.448).max()) < 1e-5


def test_padding_rows_read_no_expert_and_count_nowhere():
    layer, _ = _moe_parts(ep_size=2, ep_rank=0)
    x = jax.random.normal(jax.random.key(4), (6, 32))
    idx = jnp.asarray([[0, 1]] * 3 + [[2, 6]] * 3, jnp.int32)
    w = jnp.ones((6, 2), jnp.float32)
    valid = jnp.asarray([True, True, False, True, False, False])
    with moe.expert_load() as load:
        out = layer.experts(x, idx, w, valid=valid)
    # held [0, 4): pairs of real rows only; expert 6 is held elsewhere
    assert np.asarray(load[0]).tolist() == [2, 2, 1, 0, 1]
    assert float(jnp.abs(out[jnp.asarray([2, 4, 5])]).max()) == 0.0
    # the collector only collects: the layer computes the same without it,
    # and real rows the same whether or not padding is marked
    assert float(jnp.abs(layer.experts(x, idx, w, valid=valid)
                         - out).max()) == 0.0
    real = jnp.asarray([0, 1, 3])
    assert float(jnp.abs(layer.experts(x, idx, w)[real]
                         - out[real]).max()) < 1e-6


def _held_layer(k, held, num_experts=16, d=32, f=16, seed=0):
    pt.seed(seed)
    return moe.HeldExpertsMoE(d, f, num_experts, k, held=held)


def _per_pair(layer, x, idx, w, valid=None):
    """The plain reference: a loop over tokens and choices in float32. Also
    the load vector: pairs of real tokens a held expert, then held
    elsewhere."""
    lo, hi = layer.held
    gate, up, down = (np.asarray(m, np.float32) for m in (
        layer.gate_proj, layer.up_proj, layer.down_proj))
    x, idx, w = np.asarray(x, np.float32), np.asarray(idx), np.asarray(w)
    out = np.zeros_like(x)
    load = np.zeros((hi - lo + 1,), np.int64)
    for t in range(x.shape[0]):
        if valid is not None and not bool(valid[t]):
            continue
        for j in range(idx.shape[1]):
            e = int(idx[t, j]) - lo
            if not 0 <= e < hi - lo:
                load[-1] += 1
                continue
            load[e] += 1
            g, u = x[t] @ gate[e], x[t] @ up[e]
            out[t] += w[t, j] * ((g / (1 + np.exp(-g)) * u) @ down[e])
    return out, load


def _routing(case, t=24):
    """(k, held, idx (t, k), valid) of a named case."""
    rng = np.random.default_rng(7)

    def top(k, num_experts=16):
        return np.stack([rng.permutation(num_experts)[:k] for _ in range(t)])
    if case in ("k1", "k4", "k8"):
        k = int(case[1:])
        return k, (0, 8), top(k), None
    if case == "all_to_one_held":
        return 2, (0, 8), np.full((t, 2), 5), None
    if case == "none_held":
        return 4, (0, 8), 8 + top(4, 8), None
    if case == "padding_in_the_middle":
        valid = np.ones((t,), bool)
        valid[[0, 5, 6, 7, 13, t - 1]] = False
        return 4, (0, 8), top(4), valid
    assert case == "held_from_6"
    return 4, (6, 11), top(4), None


@pytest.mark.parametrize("case", [
    "k1", "k4", "k8", "all_to_one_held", "none_held",
    "padding_in_the_middle", "held_from_6"])
def test_held_experts_match_the_per_pair_loop(case):
    k, held, idx, valid = _routing(case)
    layer = _held_layer(k, held)
    x = jax.random.normal(jax.random.key(11), (idx.shape[0], 32))
    w = jax.random.uniform(jax.random.key(12), idx.shape, minval=0.1)
    with moe.expert_load() as load:
        got = layer(x, jnp.asarray(idx, jnp.int32), w,
                    valid=None if valid is None else jnp.asarray(valid))
    want, pairs = _per_pair(layer, x, idx, w, valid)
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-5
    assert np.asarray(load[0]).tolist() == pairs.tolist()
    if valid is not None:
        assert float(np.abs(np.asarray(got)[~valid]).max()) == 0.0


def test_held_experts_scatter_nothing():
    """Group sizes by a count, the result by a gather and a sum over k: the
    layer's jaxpr holds no scatter of any kind, and says so in
    ``ops.kernel_path`` once a layer call."""
    k, held, idx, valid = _routing("padding_in_the_middle")
    layer = _held_layer(k, held)
    x = jnp.zeros((idx.shape[0], 32))
    w = jnp.ones(idx.shape, jnp.float32)

    def two_layer_calls(x, idx, w, valid):
        with moe.expert_load() as load:
            x = layer(x, idx, w, valid=valid)
            return layer(x, idx, w), load
    names = {eqn.primitive.name for _, eqn in iter_eqns(
        jax.make_jaxpr(two_layer_calls)(
            x, jnp.asarray(idx, jnp.int32), w, jnp.asarray(valid)).jaxpr)}
    assert not {n for n in names if "scatter" in n}, names
    assert {"sort", "gather", "cumsum", "ragged_dot_general"} <= names
    counted = {(r["labels"]["op"], r["labels"]["path"]): r["value"]
               for r in obs.snapshot()["ops.kernel_path"]["series"]}
    assert counted["moe_combine", "gather_sum"] == 2


def test_rows_behind_the_last_group_never_reach_the_sum(monkeypatch):
    """Pallas on (interpreted): every grouped product's rows behind the last
    group are poisoned with NaN — the kernel leaves them as its output
    buffer was — and the result stays finite and the per-pair loop's."""
    k, held, idx, valid = _routing("padding_in_the_middle")
    layer = _held_layer(k, held, d=128, f=128)
    x = jax.random.normal(jax.random.key(13), (idx.shape[0], 128))
    w = jax.random.uniform(jax.random.key(14), idx.shape, minval=0.1)
    real_fn, poisoned = moe._grouped_matmul_fn, []

    def poisoning(rows, kk, nn, pallas=None):
        grouped = real_fn(rows, kk, nn, pallas)

        def product(xs, wts, group_sizes):
            out = grouped(xs, wts, group_sizes)
            behind = jnp.arange(rows)[:, None] >= group_sizes.sum()
            poisoned.append(behind.sum())
            return jnp.where(behind, jnp.nan, out)
        return product
    monkeypatch.setattr(moe, "_grouped_matmul_fn", poisoning)
    old = flags.flag("pallas_interpret")
    flags.set_flags({"pallas_interpret": True})
    try:
        got = layer(x, jnp.asarray(idx, jnp.int32), w, valid=jnp.asarray(valid))
    finally:
        flags.set_flags({"pallas_interpret": old})
    assert len(poisoned) == 3 and min(int(p) for p in poisoned) > 0
    assert bool(jnp.isfinite(got).all())
    want, _ = _per_pair(layer, x, idx, w, valid)
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-4
    paths = {(r["labels"]["op"], r["labels"]["path"])
             for r in obs.snapshot()["ops.kernel_path"]["series"]}
    assert ("moe_experts", "pallas_gmm") in paths


@pytest.mark.parametrize("sizes,rows", [([5, 0, 20, 10], 48),
                                        ([0, 0, 0, 3], 16),
                                        ([16, 16, 16, 16], 64)])
def test_grouped_matmul_kernel_matches_ragged_dot(sizes, rows):
    key = jax.random.key(0)
    xs = jax.random.normal(key, (rows, 256), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (4, 256, 128))
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul_pallas(xs, w, gs, tiling=(16, 128, 128),
                                interpret=True)
    want = jax.lax.ragged_dot(xs, w, gs)
    n = sum(sizes)
    assert float(jnp.abs(got[:n] - want[:n]).max()) < 1e-4


def test_held_experts_take_the_kernel_where_pallas_is_on():
    old = flags.flag("pallas_interpret")
    pt.seed(0)
    layer = AfmoeMoE(_config(hidden_size=128, moe_intermediate_size=128))
    x = jax.random.normal(jax.random.key(5), (9, 128))
    idx, w = layer.router.route(x)
    want = layer.experts(x, idx, w)                      # ragged_dot
    flags.set_flags({"pallas_interpret": True})
    try:
        got = layer.experts(x, idx, w)
    finally:
        flags.set_flags({"pallas_interpret": old})
    assert float(jnp.abs(got - want).max()) < 1e-5
    paths = {(r["labels"]["op"], r["labels"]["path"])
             for r in obs.snapshot()["ops.kernel_path"]["series"]}
    assert ("moe_experts", "pallas_gmm") in paths
    assert ("moe_experts", "xla_reference") in paths


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(NotImplementedError, match="group limit"):
        AfmoeConfig(n_group=8, topk_group=4)
    with pytest.raises(ValueError, match="do not split"):
        AfmoeConfig(ep_size=7)
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeConfig(num_hidden_layers=2, layer_types=("full_attention",))
    cfg = dataclasses.replace(AfmoeConfig(ep_size=8, ep_rank=3))
    assert cfg.experts_held == (96, 128)
    assert cfg.layer_types[:4] == ("sliding_attention",) * 3 + (
        "full_attention",)
