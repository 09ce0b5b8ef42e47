"""The latent-attention MoE model (models/latent_moe.py) — multi-head latent
attention over a paged pool of ONE entry a position, sigmoid-routed held
experts beside a shared expert — and the serving engine over a declared pool
entry.  Tiny widths, seeded weights, float32, CPU; held against the
benchmark's plain reference (benchmark/reference/mla_arch.py), which has no
cache and absorbs nothing.

Tolerances.  Program and reference are both float32 here and differ in the
order of their sums (the absorbed form contracts over the latent where the
plain form contracts over a head) and in the reference's "highest" products:
logits of magnitude ~1 agree to a few 1e-6.  The limits below are 2e-4 on a
logit (LOGIT_TOL) — fifty times that, and under a tenth of what the same
model computes with bf16 operands (3.7e-3 at these widths, asserted in
``test_bf16_where_float32_is_stated_fails``).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights_mla
from benchmark.reference import mla_arch
from paddle_tpu import nn
from paddle_tpu import observability as obs
from paddle_tpu.models import LatentMoeForCausalLM, tiny_latent_moe_config
from paddle_tpu.models.latent_moe import rope_pairs
from paddle_tpu.models.parts import DecodePart
from paddle_tpu.ops.attention import (latent_decode_attention,
                                      latent_decode_attention_reference)
from paddle_tpu.ops.pallas.decode_attention import (
    LatentLayout, SharedWalk, latent_decode_attention_pallas, walk_counts)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import init_paged_kv_cache

LOGIT_TOL = 2e-4

# the benchmark's configuration keys of the tiny model, as its files hold
# them (n_routed_experts is the number HELD; the router keeps
# n_experts_routed).  Matrices at unit gain for this width.
REF = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_hidden_layers": 3,
       "first_k_dense_replace": 1, "num_attention_heads": 4,
       "q_lora_rank": 48, "kv_lora_rank": 128, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 32, "v_head_dim": 16, "n_routed_experts": 4,
       "n_experts_routed": 8, "ep_size": 2, "ep_rank": 1,
       "n_shared_experts": 1, "num_experts_per_tok": 2,
       "norm_topk_prob": True, "routed_scaling_factor": 2.5,
       "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "dtype": "float32"}
BLOCK = CHUNK = 8


def _seeded(seed=3, dtype="float32", **over):
    """(model, weights under the reference's names) of the tiny REF."""
    with nn.abstract_parameters():
        model = LatentMoeForCausalLM(tiny_latent_moe_config(
            ep_size=2, ep_rank=1, dtype=dtype, **over))
    model.eval()
    made = weights_mla.make_weights(REF, seed, dtype)
    model.set_state_dict({weights_mla.program_name(n): w
                          for n, w in made.items()})
    return model, made


def _engine(model, **over):
    kw = dict(num_slots=4, max_length=128, paged=True, chunked=True,
              prefill_chunk=CHUNK, block_len=BLOCK, num_blocks=80,
              prefix_cache=True)
    return ServingEngine(model, **{**kw, **over})


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def _served_gap(made, prompt, tokens):
    """Per served position: the reference's best logit minus the served
    token's (``check.served_gaps``' number)."""
    full = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    ref = np.asarray(mla_arch.logits(made, REF, full[:-1]))
    rows = ref[len(prompt) - 1:]
    return rows.max(-1) - rows[np.arange(len(tokens)), tokens]


@pytest.fixture(scope="module")
def seeded():
    return _seeded()


# -- (a) forward against the reference ---------------------------------------

def test_forward_matches_the_reference_logits(seeded):
    model, made = seeded
    ids = _ids(37)
    got = np.asarray(model(jnp.asarray(ids)[None]))[0]
    want = np.asarray(mla_arch.logits(made, REF, ids))
    assert np.abs(want).max() > 0.5           # logits of a real size
    assert np.abs(got - want).max() < LOGIT_TOL
    # the head over a slice of rows, cut before the head
    rows = slice(30, 36)
    np.testing.assert_allclose(
        np.asarray(mla_arch.logits(made, REF, ids, rows=rows)), want[rows],
        atol=1e-6)


def test_bf16_where_float32_is_stated_fails():
    """The tolerance is tight enough: the same weights served with bf16
    operands miss it by an order of magnitude."""
    model, made = _seeded(dtype="bfloat16")
    ids = _ids(37)
    got = np.asarray(model(jnp.asarray(ids)[None]), np.float32)[0]
    want = np.asarray(mla_arch.logits(
        {k: v.astype(jnp.float32) for k, v in made.items()}, REF, ids))
    assert np.abs(got - want).max() > 10 * LOGIT_TOL


def test_rope_turns_interleaved_pairs():
    x = jax.random.normal(jax.random.key(1), (1, 5, 3, 8))
    cos, sin = mla_arch.rope_tables(5, 8, 10000.0)
    got = rope_pairs(x, cos, sin)
    want = mla_arch.rotate_pairs(x[0], cos, sin)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-6)
    # lanes (0, 1) turn together by position x theta^0 = position radians
    a, b = np.asarray(x[0, 3, 0, :2])
    np.testing.assert_allclose(
        np.asarray(got[0, 3, 0, :2]),
        [a * np.cos(3) - b * np.sin(3), b * np.cos(3) + a * np.sin(3)],
        atol=1e-5)
    ids = jnp.asarray([[4, 2, 0, 1, 3]])
    moved = rope_pairs(x, cos, sin, ids)
    np.testing.assert_allclose(
        np.asarray(moved[0, 0]),
        np.asarray(mla_arch.rotate_pairs(x[0, :1], cos[4:5], sin[4:5])[0]),
        atol=1e-6)


# -- (b), (c) chunks then decode through the latent pool ---------------------

def _through_the_pool(model, ids, prompt_len):
    """Logits (len(ids), V) of prefill by chunks then decode a token at a
    time over a paged latent pool the test lays out: block tables that
    scatter the sequence's blocks."""
    cfg = model.config
    n_blocks = -(-len(ids) // BLOCK)
    cache = init_paged_kv_cache(cfg, 2 * n_blocks + 1, BLOCK,
                                entry=model.serving_traits.pool_entry)
    table = jnp.asarray([list(range(2 * n_blocks, n_blocks, -1))], jnp.int32)
    out = []
    at = 0
    while at < prompt_len:
        n = min(CHUNK, prompt_len - at)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :n] = ids[at:at + n]
        valid = jnp.arange(CHUNK)[None] < n
        (lg,), cache = model.decode_parts(
            [DecodePart(jnp.asarray(chunk), jnp.asarray([at], jnp.int32),
                        table, valid)], cache)
        out.append(np.asarray(lg[0, :n]))
        at += n
    for t in range(prompt_len, len(ids)):
        (lg,), cache = model.decode_parts(
            [DecodePart(jnp.asarray(ids[t:t + 1])[None],
                        jnp.asarray([t], jnp.int32), table,
                        jnp.ones((1, 1), bool))], cache)
        out.append(np.asarray(lg[0]))
    return np.concatenate(out), cache


def test_chunks_then_decode_through_the_pool_match_the_reference(seeded):
    model, made = seeded
    ids = _ids(45, seed=5)
    got, cache = _through_the_pool(model, ids, prompt_len=29)
    want = np.asarray(mla_arch.logits(made, REF, ids))
    assert np.abs(got - want).max() < LOGIT_TOL
    # the pool: one array a layer, the entry padded to whole lane tiles,
    # zeros in the lanes the entry pads and in blocks no table names
    e = model.serving_traits.pool_entry
    assert cache.shape[:2] == (3, 1) and cache.shape[-1] == e.width == 256
    values = model.config.entry_values
    assert values == 160 and not np.asarray(cache[..., values:]).any()
    assert not np.asarray(cache[:, :, 0]).any()


def test_absorbed_form_is_the_plain_form(seeded):
    """The same weights: ``forward`` up-projects every cached position,
    ``decode`` carries the up-projection to the query and the result."""
    model, _ = seeded
    ids = _ids(26, seed=9)
    plain = np.asarray(model(jnp.asarray(ids)[None]))[0]
    absorbed, _ = _through_the_pool(model, ids, prompt_len=26)
    assert np.abs(plain - absorbed).max() < LOGIT_TOL


def test_engine_serves_what_the_reference_puts_first(seeded):
    """Through ``ServingEngine``: chunked prefill + paged decode over the
    latent pool, two requests at once; every served token's logit lies
    within the tolerance of the reference's best."""
    model, made = seeded
    eng = _engine(model)
    prompts = [_ids(n, seed=n) for n in (27, 9, 41)]
    rids = [eng.submit(p, max_new_tokens=11) for p in prompts]
    eng.drain()
    assert eng.step_traces == 1
    for p, rid in zip(prompts, rids):
        toks = eng.result(rid)
        assert len(toks) == 11
        assert _served_gap(made, p, toks).max() < LOGIT_TOL
    load = eng.expert_load
    assert load["pairs"].shape == (2, 4)        # expert layers x held


# -- (d) the Pallas body against its XLA twin --------------------------------

@pytest.fixture(scope="module")
def latent_pool():
    rng = np.random.default_rng(0)
    pool = rng.normal(size=(2, 1, 40, 128, 256)).astype(np.float32)
    pool[..., 160:] = 0.0
    return jnp.asarray(pool, jnp.bfloat16)


def _q(shape, seed):
    q = np.random.default_rng(seed).normal(size=shape).astype(np.float32)
    q[..., 160:] = 0.0
    return jnp.asarray(0.2 * q, jnp.bfloat16)


@pytest.mark.parametrize("name, s, pos, tables", [
    # rows: a depth that crosses a group (2 blocks) and a block boundary, a
    # row at position 0, an idle row (null table), a row mid-block
    ("rows", 1, [600, 0, 0, 257],
     [[1, 2, 3, 4, 5, 0], [6, 7, 8, 9, 10, 11], [0] * 6, [3, 2, 1, 0, 0, 0]]),
    # a chunk whose tiles cross a block boundary, over a prefix of 2 blocks
    ("chunk", 24, [250], [[12, 13, 1, 0, 0, 0]]),
    # a chunk at position 0
    ("chunk-at-0", 24, [0], [[6, 7, 8, 9, 10, 11]]),
])
def test_latent_kernel_matches_its_xla_twin(latent_pool, name, s, pos,
                                            tables):
    layout = LatentLayout(value_width=128, q_rows=16, group_keys=256)
    q = _q((len(pos), s, 4, 256), seed=len(name))
    pos = jnp.asarray(pos, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    got = latent_decode_attention_pallas(q, latent_pool, 1, pos, tables,
                                         layout, 0.1, interpret=True)
    want = latent_decode_attention_reference(q, latent_pool, 1, pos, tables,
                                             128, 0.1)
    assert got.shape == (len(pos), s, 4, 128)
    # bf16 outputs: an ulp of the output's rounding (2^-8 of its size)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -7, atol=2 ** -8)


def _share(rows, groups, tiles=4, members=4):
    """The :class:`SharedWalk` of ``groups`` ([(rows, columns)]) as the
    engine lays it out: tiles of ``members`` rows, a tile's first row its
    leader, the live tiles first, a place no row holds naming the leader."""
    n, at = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
    tile_rows = np.zeros((tiles, members), np.int32)
    tile_n = np.zeros(tiles, np.int32)
    t = 0
    for mem, cols in groups:
        for i in range(0, len(mem), members):
            cut = mem[i:i + members]
            tile_rows[t] = cut[0]
            tile_rows[t, :len(cut)] = cut
            tile_n[t] = n[cut] = cols
            at[cut] = t * members + np.arange(len(cut))
            t += 1
    return SharedWalk(*(jnp.asarray(x) for x in (n, at, tile_rows, tile_n)))


DOC, OTHER, NAN_BLOCK, COLS = [1, 2, 3], [4, 5], 39, 8


def _row(shared, own):
    return (shared + own + [0] * COLS)[:COLS]


# name: (positions, tables, [(a group's rows, its shared columns)]) at 4
# heads, tiles of 4 rows (16 MXU rows) and copy groups of 2 blocks
TWO_PART = {
    "a-group-of-2": ([400, 500], [_row(DOC, [10]), _row(DOC, [11])],
                     [([0, 1], 3)]),
    # three tiles (4 + 4 + 3 rows) on one document
    "a-group-of-11": ([384 + 11 * i for i in range(11)],
                      [_row(DOC, [10 + i]) for i in range(11)],
                      [(list(range(11)), 3)]),
    "two-groups-of-different-depth": (
        [400, 500, 300, 290, 370],
        [_row(DOC, [10]), _row(DOC, [11]), _row(OTHER, [12]),
         _row(OTHER, [13]), _row(OTHER, [14])],
        [([0, 1], 3), ([2, 3, 4], 2)]),
    "a-row-in-no-group-among-grouped": (
        [400, 777, 500], [_row(DOC, [10]), _row([20, 21, 22, 23, 24, 25, 26],
                                                []), _row(DOC, [11])],
        [([0, 2], 3)]),
    # the idle row's position lies past the table: its walk is the null
    # block's, as without a shared part
    "an-idle-row-parked-past-the-cache": (
        [400, COLS * 128 + 5, 500], [_row(DOC, [10]), [0] * COLS,
                                     _row(DOC, [11])], [([0, 2], 3)]),
    "an-own-part-of-the-current-block-alone": (
        [384, 389, 511], [_row(DOC, [10]), _row(DOC, [11]),
                          _row(DOC, [12])], [([0, 1, 2], 3)]),
    # one shared block, then 5 and 6 blocks of a row's own: three copy
    # groups of 2
    "an-own-part-over-two-copy-groups": (
        [700, 800], [_row([1], [10, 11, 12, 13, 14]),
                     _row([1], [15, 16, 17, 18, 19, 20])], [([0, 1], 1)]),
}


@pytest.mark.parametrize("name", list(TWO_PART))
def test_two_part_walk_matches_the_twin_and_the_one_part_walk(latent_pool,
                                                              name):
    """A run of leading columns walked once for the rows that share it,
    then each row's own columns from what its tile left: the XLA twin's
    numbers, and the one-part walk's."""
    layout = LatentLayout(value_width=128, q_rows=16, group_keys=256)
    pos, tables, groups = TWO_PART[name]
    q = _q((len(pos), 1, 4, 256), seed=len(name))
    pos = jnp.asarray(pos, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    shared = _share(len(pos), groups)
    walk = functools.partial(latent_decode_attention_pallas, q, latent_pool,
                             1, pos, tables, layout, 0.1, interpret=True)
    two, one = walk(shared=shared), walk()
    want = latent_decode_attention_reference(q, latent_pool, 1, pos, tables,
                                             128, 0.1)
    twin = latent_decode_attention_reference(q, latent_pool, 1, pos, tables,
                                             128, 0.1, shared=shared)
    assert two.shape == (len(pos), 1, 4, 128)
    for other in (want, one, twin):
        np.testing.assert_allclose(np.asarray(two, np.float32),
                                   np.asarray(other, np.float32),
                                   rtol=2 ** -7, atol=2 ** -8)


def test_two_part_walk_reads_no_column_outside_its_two_ranges(latent_pool):
    """A member's own table is never read in the columns its tile walks
    (the leader's row is), no row's past its last block, an empty tile's
    not at all: those columns name a block of NaN here."""
    layout = LatentLayout(value_width=128, q_rows=16, group_keys=256)
    pool = latent_pool.at[:, :, NAN_BLOCK].set(jnp.nan)
    pos = jnp.asarray([400, 500, 640, 130], jnp.int32)
    real = [_row(DOC, [10]), _row(DOC, [11]), _row(DOC, [12, 13, 14]),
            _row([20, 21], [])]
    seen = np.full((4, COLS), NAN_BLOCK, np.int32)
    seen[0, :4] = real[0][:4]           # the leader: shared columns and own
    seen[1, 3] = real[1][3]             # members: their own columns alone
    seen[2, 3:6] = real[2][3:6]
    seen[3, :2] = real[3][:2]           # a row alone: its whole walk
    shared = _share(4, [([0, 1, 2], 3)])
    shared = shared._replace(tile_rows=shared.tile_rows.at[1:].set(1))
    q = _q((4, 1, 4, 256), seed=7)
    got = latent_decode_attention_pallas(
        q, pool, 1, pos, jnp.asarray(seen), layout, 0.1, interpret=True,
        shared=shared)
    want = latent_decode_attention_reference(
        q, pool, 1, pos, jnp.asarray(real, jnp.int32), 128, 0.1)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -7, atol=2 ** -8)


def test_latent_kernel_refuses_what_it_does_not_take(latent_pool):
    lay = LatentLayout(value_width=128)
    q = _q((1, 1, 4, 256), 0)
    pos, bt = jnp.zeros((1,), jnp.int32), jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="latent pool"):
        latent_decode_attention_pallas(
            q, jnp.zeros((2, 2, 4, 128, 256), jnp.bfloat16), 0, pos, bt,
            lay, 0.1, interpret=True)
    with pytest.raises(NotImplementedError, match="128-aligned"):
        latent_decode_attention_pallas(
            q, jnp.zeros((2, 1, 4, 64, 256), jnp.bfloat16), 0, pos, bt,
            lay, 0.1, interpret=True)
    with pytest.raises(NotImplementedError, match="heads"):
        latent_decode_attention_pallas(
            q, latent_pool, 0, pos, bt,
            LatentLayout(value_width=128, q_rows=2), 0.1, interpret=True)


def test_dispatch_counts_the_latent_route(latent_pool):
    def count():
        rows = obs.snapshot().get("ops.kernel_path", {"series": []})
        return {tuple(sorted(r["labels"].items())): r["value"]
                for r in rows["series"]
                if r["labels"].get("cache") == "latent"}
    before = count()
    q = _q((1, 1, 4, 256), 1)
    latent_decode_attention(q, latent_pool, 0, jnp.asarray([5], jnp.int32),
                            jnp.asarray([[1, 0]], jnp.int32),
                            LatentLayout(value_width=128), 0.1)
    gained = {k: v - before.get(k, 0) for k, v in count().items()
              if v > before.get(k, 0)}
    # on a CPU without the interpret flag: the XLA twin, by name
    assert gained == {(("cache", "latent"), ("op", "decode_attention"),
                       ("path", "xla_math")): 1}


def test_walk_counts_follow_the_layout():
    """A latent layout's tiles and groups are its own: 4 heads at a q tile
    of 16 rows cut a 24-token chunk into 6 tiles, a group holds 2 blocks."""
    lay = LatentLayout(value_width=128, q_rows=16, group_keys=256)
    blocks, walk = walk_counts([250], 24, 4, bk=128, n_cols=6, latent=lay)
    # tiles end at 253, 257, ..., 273: blocks 0..1, then 0..2 five times
    assert (blocks, walk) == (2 + 5 * 3, 2 + 5 * 4)
    assert walk_counts([600, 0], 1, 4, bk=128, n_cols=6, latent=lay) == (
        5 + 1, 6 + 2)
    # behind a shared walk of 3 columns the first row walks 2 of its own;
    # a tile at the last shared position walks the 3, once for its rows
    assert walk_counts([600, 0], 1, 4, bk=128, n_cols=6, latent=lay,
                       first=[3, 0]) == (2 + 1, 2 + 2)
    assert walk_counts([3 * 128 - 1], 1, 16, bk=128, n_cols=6,
                       latent=lay) == (3, 4)
    # a first column past a row's last block is its last block
    assert walk_counts([130], 1, 4, bk=128, n_cols=6, latent=lay,
                       first=[5]) == (1, 2)


# -- (e) the prefix trie over a latent pool ----------------------------------

def test_shared_prefix_through_the_trie(seeded):
    """Two requests that share a prefix of whole blocks give, through the
    trie, what they give alone; a third on a different prefix of the same
    length adopts nothing."""
    model, made = seeded
    doc, other = _ids(32, seed=100), _ids(32, seed=101)
    prompts = [np.concatenate([doc, _ids(7, seed=1)]),
               np.concatenate([doc, _ids(5, seed=2)]),
               np.concatenate([other, _ids(6, seed=3)])]
    alone = []
    for p in prompts:
        eng = _engine(model)
        rid = eng.submit(p, max_new_tokens=9)
        eng.drain()
        alone.append(eng.result(rid))
        assert eng.kv.stats["prefix_hit_tokens"] == 0
    eng = _engine(model)
    shared = []
    hits = []
    for p in prompts:           # one after another: a hit needs the blocks
        rid = eng.submit(p, max_new_tokens=9)       # written and registered
        eng.drain()
        shared.append(eng.result(rid))
        hits.append(int(eng.kv.stats["prefix_hit_tokens"]))
    assert hits == [0, 32, 32]          # the third adopted nothing
    assert shared == alone
    for p, toks in zip(prompts, shared):
        assert _served_gap(made, p, toks).max() < LOGIT_TOL


def test_rows_on_one_prefix_walk_it_together(seeded):
    """Four requests decoding AT ONCE on one document and one on another:
    the four are one tile that reads the document through its leader's
    table row (the XLA twin does what the kernel does), and give what each
    gives alone; the leader retires first and the rest regroup."""
    model, made = seeded
    doc, other = _ids(32, seed=100), _ids(32, seed=101)
    prompts = [np.concatenate([doc, _ids(7, seed=1)]),
               np.concatenate([doc, _ids(5, seed=2)]),
               np.concatenate([doc, _ids(3, seed=4)]),
               np.concatenate([doc, _ids(6, seed=5)]),
               np.concatenate([other, _ids(6, seed=3)])]
    new = [6, 30, 30, 30, 30]           # the first, deepest row goes first
    alone = []
    for p, n in zip(prompts, new):
        eng = _engine(model)
        rid = eng.submit(p, max_new_tokens=n)
        eng.drain()
        alone.append(eng.result(rid))
    eng = _engine(model, num_slots=6)
    assert [x.shape for x in eng._share] == [(6,), (6,), (2, 64), (2,)]
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    grouped, leaders = [], []
    while eng.queue_depth or eng.num_pending or eng.num_active:
        eng.step()
        grouped.append(int((eng._share.n > 0).sum()))
        leaders.append(int(eng._share.tile_rows[0, 0]))
        # what a row shares lies whole blocks behind its current one
        live = np.flatnonzero(eng._active)
        assert (eng._share.n[live] <= eng._positions[live] // BLOCK).all()
    assert eng.step_traces == 1
    assert int(eng.kv.stats["prefix_hit_tokens"]) == 3 * 32
    assert [eng.result(r) for r in rids] == alone
    for p, rid in zip(prompts, rids):
        assert _served_gap(made, p, eng.result(rid)).max() < LOGIT_TOL
    # three rows on the document make a tile, the fourth joins it, the
    # leader retires and the other three go on under another
    assert 4 in grouped and grouped[grouped.index(4):].count(3) > 5
    at4 = grouped.index(4)
    assert leaders[at4] != leaders[at4 + grouped[at4:].index(3)]
    assert (eng._share.tile_n[:1] == 4).all() or not eng._share.n.any()
    spans = [ev["args"] for ev in obs.get_tracer().events()
             if ev["name"] == "serving.decode"]
    assert {a["rows_grouped"] for a in spans} >= {0, 3, 4}
    assert {a["shared_tiles"] for a in spans} == {0, 1}


def test_spans_count_shared_blocks_once(seeded):
    """Three rows decoding on one adopted prefix: the rows span says how
    many of the blocks their walks read are distinct, and the walk reads
    the document's blocks once for the three."""
    model, _ = seeded
    eng = _engine(model)
    doc = _ids(32, seed=100)
    for n in (3, 2, 4):
        rid = eng.submit(np.concatenate([doc, _ids(n, seed=n)]),
                         max_new_tokens=20)
        while not eng.result(rid):
            eng.step()
    eng.step()
    spans = [ev for ev in obs.get_tracer().events()
             if ev["name"] == "serving.decode"
             and ev.get("args", {}).get("slots") == 3]
    a = spans[-1]["args"]
    depth = [int(p) + 1 for p in eng._positions[eng._active]]
    # the span was written before the tick advanced the rows by one
    assert a["rows_depth"] == sum(depth) - 3
    per_row = [-(-(d - 1) // BLOCK) for d in depth]
    # four blocks of the document, once; each row's own blocks after them
    assert a["rows_distinct"] == 4 + sum(n - 4 for n in per_row)
    assert a["rows_positions"] == 32 + sum(d - 1 - 32 for d in depth)
    # the walk reads what is distinct: the one tile's four columns, then
    # each row's own
    assert (a["rows_grouped"], a["shared_tiles"]) == (3, 1)
    assert a["rows_blocks"] == a["rows_distinct"] < sum(per_row)
    # by the layout's tiles and groups, three layers: the tile's 4 blocks
    # + the live rows' own + the idle row's one + the one of the stub the
    # rows-alone program keeps of the chunk part (no chunk this tick)
    own = sum(n - 4 for n in per_row)
    assert (a["parts"], a["kv_blocks"]) == (1, 3 * (4 + own + 1 + 1))
    assert a["kv_walk"] >= a["kv_blocks"]
    snap = obs.snapshot()["kv_cache.position_bytes"]["series"]
    mine = [r for r in snap if r["labels"].get("engine") == eng._eid]
    assert mine[0]["value"] == 3 * 256 * 4      # layers x stored lanes x f32


# -- (f) the shares add up ---------------------------------------------------

def test_the_ranks_shares_add_up_to_the_whole_layer():
    """Every rank's routed part plus the shared expert once is the uncut
    reference's layer: what a rank leaves out is what the others hold."""
    whole = dict(REF, n_routed_experts=8, ep_size=1, ep_rank=0)
    made = weights_mla.make_weights(whole, 11, "float32")
    w = mla_arch.layer_weights(made, 2)
    y = jax.random.normal(jax.random.key(2), (13, 64))
    with jax.default_matmul_precision("highest"):
        want = mla_arch.expert_layer(y, w, whole)
    total = 0.0
    for rank in range(4):
        with nn.abstract_parameters():
            model = LatentMoeForCausalLM(tiny_latent_moe_config(
                ep_size=4, ep_rank=rank))
        mlp = model.model.layers[2].mlp
        held = slice(2 * rank, 2 * rank + 2)
        mlp.set_state_dict({
            "gate.weight": w["router"], "gate.expert_bias": w["router_bias"],
            **{f"experts.{k}_proj": w[f"experts_{k}"][held]
               for k in ("gate", "up", "down")},
            **{f"shared_experts.{k}_proj": w[f"shared_{k}"]
               for k in ("gate", "up", "down")}})
        idx, wgt = mlp.gate.route(y)
        total = total + mlp.experts(y[None], idx, wgt)[0]
        if rank == 0:
            # the reference's own share, rank by rank, says the same
            part = dict(whole, ep_size=4, n_routed_experts=2)
            with jax.default_matmul_precision("highest"):
                ref_parts = sum(
                    mla_arch.routed_experts(
                        y, {**w, **{f"experts_{k}": w[f"experts_{k}"][
                            2 * r:2 * r + 2] for k in ("gate", "up", "down")}},
                        dict(part, ep_rank=r))
                    for r in range(4))
            shared = mlp.shared_experts(y[None])[0]
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(ref_parts + mla_arch.swiglu(
            y, w["shared_gate"], w["shared_up"], w["shared_down"])),
        np.asarray(want), atol=2e-5)


# -- (g) the refusals, by name -----------------------------------------------

@pytest.mark.parametrize("kw, what", [
    ({"paged": False}, "the contiguous cache"),
    ({"chunked": False}, "wave prefill"),
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8'"),
    ({"kv_cache_dtype": "mixed"}, "kv_cache_dtype='mixed'"),
    ({"preempt": "swap", "host_blocks": 8}, "preempt='swap'"),
    ({"preempt": "recompute"}, "preempt='recompute'"),
    ({"spec_decode": True}, "speculative decoding"),
    ({"int8_weights": True}, "int8_weights"),
    ({"mesh": "mp2dp2"}, "a mesh"),
])
def test_refused_layouts_are_named(seeded, kw, what):
    model, _ = seeded
    with pytest.raises(NotImplementedError) as e:
        _engine(model, **kw)
    assert "LatentMoeForCausalLM cannot be served with" in str(e.value)
    assert what in str(e.value)


@pytest.mark.parametrize("call", ["export_request", "import_request"])
def test_migration_is_refused_by_name(seeded, call):
    model, _ = seeded
    eng = _engine(model)
    arg = 0 if call == "export_request" else {"blocks": {}}
    with pytest.raises(NotImplementedError, match=call):
        getattr(eng, call)(arg)


def test_config_refuses_what_the_equations_do_not_cover():
    for over in ({"scoring_func": "softmax"}, {"n_group": 8},
                 {"rope_scaling": {"type": "yarn"}},
                 {"rope_interleave": False}, {"q_lora_rank": 0},
                 {"tie_word_embeddings": True}):
        with pytest.raises(NotImplementedError, match="only"):
            tiny_latent_moe_config(**over)
    with pytest.raises(ValueError, match="do not split"):
        tiny_latent_moe_config(ep_size=3)


def test_a_model_that_declares_nothing_gets_what_it_got():
    """llama: no declared entry, the K/V pool, the walk's counts and no
    series of the latent pool's."""
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    eng = ServingEngine(model, num_slots=2, max_length=64, paged=True,
                        block_len=8, chunked=True, prefill_chunk=8)
    assert eng._pool_entry is None
    assert eng._cache.shape[1] == 2
    assert "_kv_walk" not in vars(eng)          # the class's own method
    rid = eng.submit(_ids(12), max_new_tokens=3)
    eng.drain()
    assert len(eng.result(rid)) == 3
    spans = [ev for ev in obs.get_tracer().events()
             if ev["name"] == "serving.decode"
             and "rows_distinct" in ev.get("args", {})]
    mine = obs.snapshot().get("kv_cache.position_bytes", {"series": []})
    assert not any(r["labels"].get("engine") == eng._eid
                   for r in mine["series"])
    assert all(ev["args"].get("engine") != eng._eid for ev in spans)


def test_pool_entry_of_the_published_widths():
    """JoyAI-LLM-Flash as published: 576 values a position a layer, stored
    in 640 lanes; 40 layers x 640 x 2 B = 51,200 B a position in bf16."""
    from paddle_tpu.models import LatentMoeConfig
    c = LatentMoeConfig()
    assert (c.entry_values, c.entry_width, c.qk_head_dim) == (576, 640, 192)
    assert c.num_expert_layers == 39
    assert dataclasses.replace(c, ep_size=16).experts_held == (0, 16)
