"""Flash-decode Pallas kernel (ops/pallas/decode_attention.py), interpret
mode on CPU: parity vs cached_decode_attention's XLA math path across the
shapes the serving engine produces — scalar and per-row ``pos``, GQA group
sizes {1, 4}, s > 1 (prefill-into-occupied-slot), depths ending mid-KV-
chunk, bf16 — the in-kernel block walk's ragged cases with every block
outside a row's walk poisoned, plus the cached_decode_attention dispatch
contract (routing,
threshold, extra_mask fallback).  The real-TPU lane (tests/test_tpu_lane.py)
compiles the same kernel via Mosaic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.ops.attention import (cached_decode_attention,
                                      cached_decode_attention_reference,
                                      decode_attention_path,
                                      paged_decode_attention,
                                      paged_decode_attention_reference)
from paddle_tpu.ops.pallas.decode_attention import (
    decode_attention_pallas, group_blocks, paged_decode_attention_pallas,
    q_tiles)


def _qkv(b, s, hq, hkv, d, L, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, L, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, L, hkv, d)), dtype)
    return q, k, v


CASES = [
    # (b, s, hq, hkv, d, L, pos) — pos None means a per-row vector
    (2, 1, 8, 2, 64, 256, 77),        # GQA g=4, depth ends mid-chunk
    (2, 1, 4, 4, 32, 256, 100),       # g=1 (MHA)
    (1, 1, 8, 2, 64, 256, 0),         # first token
    (2, 1, 8, 2, 64, 256, 255),       # last slot live
    (1, 1, 8, 2, 64, 384, 127),       # depth ends exactly at a chunk edge
    (2, 1, 8, 2, 64, 256, None),      # per-row positions
    (2, 3, 8, 2, 64, 256, None),      # per-row, s>1 (prefill-into-slot)
    (3, 2, 4, 4, 16, 256, None),      # per-row, s>1, g=1
    # chunked-prefill shapes: s·G > 64 rows — the q-tiled grid walk
    (1, 96, 8, 2, 64, 384, 13),       # 6 q tiles of 16 tokens (g=4)
    (2, 40, 4, 4, 32, 256, None),     # ragged last tile (40 = 2·16 + 8)
    (1, 17, 8, 2, 64, 256, 100),      # rows 68: barely past one tile
    (2, 33, 8, 8, 32, 256, None),     # g=1, bq=64, ragged
]


@pytest.mark.parametrize("b,s,hq,hkv,d,L,pos", CASES)
def test_kernel_matches_xla_math_path(b, s, hq, hkv, d, L, pos):
    q, k, v = _qkv(b, s, hq, hkv, d, L, seed=b * 100 + s)
    if pos is None:
        pos = jnp.asarray([5, 130, 200][:b], jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, block_kv=128,
                                  interpret=True)
    want = cached_decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_bf16_fp32_accum():
    q, k, v = _qkv(2, 1, 8, 2, 64, 256, seed=7, dtype=jnp.bfloat16)
    pos = jnp.asarray([33, 199], jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, block_kv=128,
                                  interpret=True)
    assert got.dtype == jnp.bfloat16
    want = cached_decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_walk_reads_nothing_past_the_rows_own_depth():
    """The kernel needs no static ``live_len``: each row's walk stops at
    its own last block, so a cache whose tail past it is NaN reads as the
    XLA path trimmed to the batch's depth does (``live_len`` keeps its
    meaning there)."""
    q, k, v = _qkv(2, 1, 8, 2, 64, 512, seed=9)
    pos = jnp.asarray([10, 140], jnp.int32)
    ref = cached_decode_attention_reference(q, k, v, pos, live_len=160)
    for row, p in enumerate((10, 140)):         # NaN past each row's block
        k = k.at[row, (p // 128 + 1) * 128:].set(jnp.nan)
        v = v.at[row, (p // 128 + 1) * 128:].set(jnp.nan)
    got = decode_attention_pallas(q, k, v, pos, block_kv=128,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_scalar_pos_matches_vector_pos():
    q, k, v = _qkv(2, 1, 8, 2, 64, 256, seed=11)
    a = decode_attention_pallas(q, k, v, 77, block_kv=128, interpret=True)
    bvec = decode_attention_pallas(q, k, v, jnp.asarray([77, 77], jnp.int32),
                                   block_kv=128, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(bvec))


def test_shape_ineligibility_raises():
    q, k, v = _qkv(1, 1, 8, 2, 64, 200, seed=13)   # 200 has no 128-divisor
    with pytest.raises(NotImplementedError, match="128-aligned"):
        decode_attention_pallas(q, k, v, 5, interpret=True)
    # s*G > 64 no longer raises — it q-tiles (chunked prefill); the
    # remaining q-side limits are the whole-prefill length and the
    # per-tile GQA group size
    q, k, v = _qkv(1, 2049, 8, 2, 64, 4096, seed=13)
    with pytest.raises(NotImplementedError, match="whole-prefill-shaped"):
        decode_attention_pallas(q, k, v, 0, interpret=True)
    q, k, v = _qkv(1, 1, 128, 1, 32, 256, seed=13)  # G = 128 > 64
    with pytest.raises(NotImplementedError, match="GQA group size"):
        decode_attention_pallas(q, k, v, 5, interpret=True)


def test_chunked_prefill_counts_kernel_path():
    """The q-tiled walk is the chunked-prefill kernel mode: building it
    must count ops.kernel_path{op="chunked_prefill"} (ISSUE 5 routing
    visibility), while q_len-1 builds keep the decode op label."""
    from paddle_tpu import observability as obs

    reg = obs.default_registry()
    q, k, v = _qkv(1, 96, 8, 2, 64, 384, seed=3)
    decode_attention_pallas(q, k, v, 13, block_kv=128, interpret=True)
    fam = reg.get("ops.kernel_path")
    assert fam is not None
    assert fam.value(op="chunked_prefill", path="contiguous") >= 1
    q, k, v = _qkv(1, 1, 8, 2, 64, 256, seed=3)
    decode_attention_pallas(q, k, v, 5, block_kv=128, interpret=True)
    assert fam.value(op="decode_attention_kernel", path="contiguous") >= 1


def test_spec_verify_hint_relabels_kernel_path():
    """ISSUE 7 routing visibility: a verify-window build made under
    ``kernel_path_hint("spec_verify")`` — the serving engine's
    spec-decode trace — counts as op="spec_verify" at BOTH dispatch
    layers (path decision + kernel build), while the math stays exactly
    the q-tiled kernel's (parity vs the reference on the k+1 window
    shape, per-row depths)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops import _dispatch

    reg = obs.default_registry()
    b, k_draft = 2, 4
    q, k, v = _qkv(b, k_draft + 1, 8, 2, 64, 256, seed=11)
    pos = jnp.asarray([37, 130], jnp.int32)
    with _dispatch.kernel_path_hint("spec_verify"):
        got = decode_attention_pallas(q, k, v, pos, block_kv=128,
                                      interpret=True)
        decode_attention_path(b, k_draft + 1, 8, 2, 64, 256)
    want = cached_decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    fam = reg.get("ops.kernel_path")
    # the kernel build relabelled (k+1 window fits one q tile, so the
    # un-hinted label would have been decode_attention_kernel)
    assert fam.value(op="spec_verify", path="contiguous") >= 1
    # ...and the decode_attention_path decision relabelled too
    assert sum(c.value() for c in fam.children()
               if c.labels.get("op") == "spec_verify"
               and c.labels.get("path") in ("pallas_decode",
                                            "xla_math")) >= 1
    # outside the hint, labels revert
    decode_attention_path(b, 1, 8, 2, 64, 256)
    assert fam.value(op="decode_attention", path="xla_math",
                     cache="contiguous") >= 1


def test_spec_verify_dispatch_contract():
    """The verify window rides the chunked-prefill dispatch contract:
    q-depth k+1 is pallas-eligible wherever a chunk would be (long
    caches on Pallas backends), falls back below the min-len threshold,
    and is never rejected for being multi-token."""
    from paddle_tpu.ops import _dispatch as dsp

    old = flags.flag("decode_attention_min_len")
    flags.set_flags({"decode_attention_min_len": 4096})
    orig = dsp.use_pallas
    dsp.use_pallas = lambda: True
    try:
        path, reason = decode_attention_path(8, 5, 8, 2, 64, 8192)
        assert path == "pallas_decode", reason
        # paged layout too (one block == one chunk)
        path, _ = decode_attention_path(8, 5, 8, 2, 64, 8192,
                                        paged_block_len=128)
        assert path == "pallas_decode"
        # below threshold the XLA math path is the design, not a gap
        path, reason = decode_attention_path(8, 5, 8, 2, 64, 2048)
        assert path == "xla_math" and "min_len" in reason
    finally:
        dsp.use_pallas = orig
        flags.set_flags({"decode_attention_min_len": old})


# -- paged cache: block-table dereference ------------------------------------

LAYER = 1          # the layer the paged cases read, of a 3-layer pool


def _paged_pool(kc, vc, tables, num_pool, bl, layers=3, layer=LAYER):
    """Scatter each row's logical blocks into the physical slots the table
    names (the inverse of what the kernel/gather path computes) of layer
    ``layer`` of a STACKED pool ``(layers, 2, num_pool, bl, Hkv·D)``; the
    other layers hold noise, so reading the wrong layer, or V for K,
    cannot pass."""
    b, L, hkv, d = kc.shape
    pool = np.random.default_rng(99).normal(
        size=(layers, 2, num_pool, bl, hkv * d)).astype(kc.dtype)
    pool[layer] = 0
    for r in range(b):
        for j in range(L // bl):
            sl = slice(j * bl, (j + 1) * bl)
            pool[layer, 0, tables[r, j]] = kc[r, sl].reshape(bl, hkv * d)
            pool[layer, 1, tables[r, j]] = vc[r, sl].reshape(bl, hkv * d)
    return jnp.asarray(pool)


PAGED_CASES = [
    # (b, s, hq, hkv, d, mb, pos, tables) — bl = 128 always; tables are
    # out-of-order, shared across rows, and positions end mid-block
    (2, 1, 8, 2, 64, 3, [130, 77],
     [[5, 3, 1], [5, 6, 2]]),                  # shared block 5, OOO ids
    (2, 3, 8, 2, 64, 3, [130, 77],
     [[5, 3, 1], [5, 6, 2]]),                  # s>1 prefill-into-slot
    (1, 1, 4, 4, 32, 2, [255], [[7, 2]]),      # g=1, last slot live
    (3, 2, 8, 4, 64, 4, [40, 300, 511],
     [[9, 9, 9, 9], [1, 2, 3, 4], [4, 3, 2, 1]]),  # row 0 never leaves b9
    # chunked-prefill q over paged prefixes: s·G > 64 rows attending
    # out-of-order / shared block tables, positions mid-block — the
    # mixed serving step's kernel shape (ISSUE 5 oracle)
    (2, 96, 8, 2, 64, 4, [130, 40],
     [[5, 3, 1, 8], [5, 6, 2, 7]]),                # shared block 5
    (1, 70, 4, 4, 32, 3, [200], [[7, 2, 4]]),      # g=1, ragged tiles
]


@pytest.mark.parametrize("b,s,hq,hkv,d,mb,pos,tables", PAGED_CASES)
def test_paged_kernel_matches_contiguous_reference(b, s, hq, hkv, d, mb,
                                                   pos, tables):
    bl = 128
    L = mb * bl
    rng = np.random.default_rng(b * 10 + mb)
    kc = rng.normal(size=(b, L, hkv, d)).astype(np.float32)
    vc = rng.normal(size=(b, L, hkv, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    tables = np.asarray(tables, np.int32)
    # every (row, logical block) mapping to one physical block must agree
    # on its content: the first mapping owns it, later ones copy it —
    # covers cross-row sharing AND a row whose dead tail repeats a block
    owner = {}
    for r in range(b):
        for j in range(mb):
            key = int(tables[r, j])
            if key in owner:
                ro, jo = owner[key]
                kc[r, j * bl:(j + 1) * bl] = kc[ro, jo * bl:(jo + 1) * bl]
                vc[r, j * bl:(j + 1) * bl] = vc[ro, jo * bl:(jo + 1) * bl]
            else:
                owner[key] = (r, j)
    pos = jnp.asarray(pos, jnp.int32)
    want = cached_decode_attention_reference(q, jnp.asarray(kc),
                                             jnp.asarray(vc), pos)
    pool = _paged_pool(kc, vc, tables, num_pool=10, bl=bl)
    got = paged_decode_attention_pallas(q, pool, LAYER, pos,
                                        jnp.asarray(tables), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the XLA gather path is the same oracle through the table
    got_ref = paged_decode_attention_reference(q, pool, LAYER, pos,
                                               jnp.asarray(tables))
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -- the walk: each row's live blocks and nothing else ------------------------

GB = group_blocks(128)         # blocks a copy group: the kernel's G
RAGGED = {
    # name: (s, hq, hkv, d, mb, window, positions, int8)
    # depths on both sides of a block's and of a group's edge, an empty row
    # and a full table, in one batch
    "depths": (1, 8, 2, 64, 2 * GB + 1, None,
               [0, 127, 128, GB * 128 - 1, GB * 128, GB * 128 + 1,
                (2 * GB + 1) * 128 - 1], False),
    # a window whose first block is past block 0 (rows 700 and 1100 deep),
    # beside a row shallower than the window
    "window-first-past-0": (1, 8, 2, 64, 9, 200, [700, 1100, 90], False),
    # a window inside ONE block: the walk is that block
    "window-one-block": (1, 8, 2, 64, 6, 64, [484, 127, 600], False),
    # the mixed step's prompt chunk over a prefix: 16 q tiles, each with
    # its own last block (and, windowed, its own first)
    "chunk-256": (256, 8, 2, 64, 6, None, [300], False),
    "chunk-256-window": (256, 8, 2, 64, 6, 160, [300], False),
    # a k+1 verify window over prefixes that end at a block's edge
    "verify-k+1": (5, 8, 2, 64, 5, None, [37, 125, 380], False),
    # the int8 pool: a scale a block and kv head, blocks of one group
    # scaled apart
    "int8": (1, 8, 2, 64, 2 * GB + 1, None, [5, 300, GB * 128 + 77], True),
    "int8-chunk": (40, 8, 2, 64, 4, None, [200], True),
}


def _ragged_case(name):
    """(q, clean pool, poisoned pool, scales, positions, clean tables,
    poisoned tables, window) of a RAGGED case.  Rows 0 and 1 share their
    first blocks (a common prefix).  Poisoned: the null block, every block
    no row owns, and a block of NaN that every table column outside its
    row's walk points at."""
    from paddle_tpu.ops.pallas.decode_attention import live_block_range
    s, hq, hkv, d, mb, window, pos, int8 = RAGGED[name]
    rng = np.random.default_rng(len(name))
    b, bl = len(pos), 128
    nblk = b * mb + 2
    tables = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    if b > 1:           # the shared prefix: row 1 reads row 0's blocks
        shared = min(int(pos[0]), int(pos[1])) // bl
        tables[1, :shared] = tables[0, :shared]
    pool = rng.normal(size=(3, 2, nblk, bl, hkv * d)).astype(np.float32)
    scales = None
    if int8:
        pool = np.clip(np.round(pool * 40), -127, 127)
        scales = rng.uniform(0.5, 2.0, (3, 2, nblk, hkv)).astype(np.float32)
    poisoned, bad = pool.copy(), tables.copy()
    nan = 0 if int8 else np.nan     # an int8 payload has no NaN: its SCALE
    poisoned[:, :, [0, nblk - 1]] = nan
    bq, nq = q_tiles(s, hq // hkv)
    for r in range(b):
        lo, hi = zip(*(live_block_range(
            np.int64(pos[r]), np.int64(qi), s=s, bq=bq, bk=bl, n_cols=mb,
            window=window, xp=np) for qi in range(nq)))
        keep = np.zeros(mb, bool)
        keep[min(lo):max(hi) + 1] = True
        bad[r, ~keep] = [0, nblk - 1][r % 2]
    owned = np.unique(bad[:, :])
    for blk in set(range(nblk)) - set(int(x) for x in owned):
        poisoned[:, :, blk] = nan
    bad_scales = None
    if int8:
        bad_scales = scales.copy()
        unowned = sorted(set(range(nblk)) - set(int(x) for x in owned)
                         | {0, nblk - 1})
        bad_scales[:, :, unowned] = np.nan
        pool, poisoned = pool.astype(np.int8), poisoned.astype(np.int8)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    return (q, jnp.asarray(pool), jnp.asarray(poisoned),
            (scales, bad_scales), jnp.asarray(pos, jnp.int32),
            jnp.asarray(tables), jnp.asarray(bad), window)


@pytest.mark.parametrize("poison", [False, True], ids=["clean", "poison"])
@pytest.mark.parametrize("name", list(RAGGED))
def test_ragged_walk_matches_reference(name, poison):
    """The cases a grid over table columns never told apart: every one
    against the XLA reference on the clean pool.  ``poison``: the null
    block, every unowned block and every table column outside a row's
    ``[first, last]`` hold NaN (the int8 pool: NaN scales) — the walk
    dereferences none of them, so the output does not change."""
    q, pool, poisoned, (sc, bad_sc), pos, tables, bad, window = \
        _ragged_case(name)
    kw = {} if window is None else {"window": window}
    want = paged_decode_attention_reference(
        q, pool, LAYER, pos, tables,
        **({} if sc is None else {"pool_scale": jnp.asarray(sc)}), **kw)
    assert np.isfinite(np.asarray(want)).all()
    if poison:
        pool, tables, sc = poisoned, bad, bad_sc
    got = paged_decode_attention_pallas(
        q, pool, LAYER, pos, tables, interpret=True,
        **({} if sc is None else {"pool_scale": jnp.asarray(sc)}), **kw)
    tol = 2e-3 if sc is not None else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_layers_share_one_kernel_body():
    """The layer is a scalar the kernel reads, not a constant of its body:
    a program that reads three layers holds ONE lowered kernel, called
    three times (sixteen bodies a program doubled the benchmark's set-up
    time), and each call still reads its own layer."""
    q, pool, _, _, pos, tables, _, _ = _ragged_case("depths")

    def three(q, pool, interpret=False):
        return [paged_decode_attention_pallas(q, pool, layer, pos, tables,
                                              interpret=interpret)
                for layer in range(3)]

    text = jax.jit(three).trace(q, pool).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    for layer, got in enumerate(three(q, pool, interpret=True)):
        want = paged_decode_attention_reference(q, pool, layer, pos, tables)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_padding_of_a_group_never_reaches_the_output():
    """A walk's last group is seldom full: its columns past ``last`` are not
    copied, and what the buffer holds there (here NaN: the TPU interpreter
    hands out uninitialised scratch so) is masked out of the scores and
    blanked out of V."""
    from jax.experimental.pallas import tpu as pltpu

    q, pool, _, _, pos, tables, _, _ = _ragged_case("depths")
    want = paged_decode_attention_reference(q, pool, LAYER, pos, tables)
    got = paged_decode_attention_pallas(
        q, pool, LAYER, pos, tables,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_rejects_unaligned_block_len():
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(2, 2, 4, 64, 2 * 32)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 1, 4, 32)), jnp.float32)
    with pytest.raises(NotImplementedError, match="128-aligned"):
        paged_decode_attention_pallas(q, pool, 1, 5, jnp.asarray([[1, 2]]),
                                      interpret=True)


def test_paged_live_len_trims_table_columns():
    bl, mb = 128, 4
    rng = np.random.default_rng(3)
    kc = rng.normal(size=(2, mb * bl, 2, 64)).astype(np.float32)
    vc = rng.normal(size=(2, mb * bl, 2, 64)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(2, 1, 8, 64)), jnp.float32)
    tables = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    pool = _paged_pool(kc, vc, tables, num_pool=9, bl=bl)
    pos = jnp.asarray([100, 200], jnp.int32)
    full = paged_decode_attention_reference(q, pool, LAYER, pos,
                                            jnp.asarray(tables))
    trimmed = paged_decode_attention_reference(
        q, pool, LAYER, pos, jnp.asarray(tables), live_len=256)
    np.testing.assert_allclose(np.asarray(trimmed), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


# -- cached_decode_attention dispatch contract -------------------------------

class TestDispatch:
    def setup_method(self, _):
        flags.set_flags({"pallas_interpret": True,
                         "decode_attention_min_len": 256})

    def teardown_method(self, _):
        flags.set_flags({"pallas_interpret": False,
                         "decode_attention_min_len": 4096})

    def test_routes_long_cache_to_kernel(self, monkeypatch):
        from paddle_tpu.ops.pallas import decode_attention as mod

        calls = []
        real = mod.decode_attention_pallas
        monkeypatch.setattr(
            mod, "decode_attention_pallas",
            lambda *a, **kw: calls.append(1) or real(*a, **kw))
        q, k, v = _qkv(2, 1, 8, 2, 64, 256, seed=17)
        pos = jnp.asarray([5, 130], jnp.int32)
        got = cached_decode_attention(q, k, v, pos)
        assert calls, "eligible shape did not route to the Pallas kernel"
        want = cached_decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_short_cache_stays_on_xla(self, monkeypatch):
        from paddle_tpu.ops.pallas import decode_attention as mod

        calls = []
        monkeypatch.setattr(mod, "decode_attention_pallas",
                            lambda *a, **kw: calls.append(1))
        q, k, v = _qkv(1, 1, 8, 2, 64, 128, seed=19)  # below min_len 256
        cached_decode_attention(q, k, v, 5)
        assert not calls
        assert decode_attention_path(1, 1, 8, 2, 64, 128)[0] == "xla_math"

    def test_chunk_shape_routes_to_kernel(self):
        """s·G > 64 is no longer prefill-shaped: a chunk-sized q over a
        long cache routes to the kernel (q-tiled); whole-prompt q beyond
        the chunk regime still falls back to XLA/flash territory."""
        assert decode_attention_path(1, 96, 8, 2, 64, 256)[0] \
            == "pallas_decode"
        path, why = decode_attention_path(1, 4096, 8, 2, 64, 8192)
        assert path == "xla_math" and "whole-prefill" in why

    def test_extra_mask_falls_back(self, monkeypatch):
        from paddle_tpu.ops.pallas import decode_attention as mod

        calls = []
        monkeypatch.setattr(mod, "decode_attention_pallas",
                            lambda *a, **kw: calls.append(1))
        q, k, v = _qkv(1, 1, 8, 2, 64, 256, seed=23)
        em = (jnp.arange(256) >= 4)[None]
        out = cached_decode_attention(q, k, v, 9, extra_mask=em)
        assert not calls
        want = cached_decode_attention_reference(q, k, v, 9, extra_mask=em)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want))

    def test_unaligned_length_falls_back_cleanly(self):
        # eligible by the cheap checks is impossible here (kv_len % 128
        # rejects first), so hit the in-kernel NotImplementedError via a
        # tight block cap: dispatcher must return the XLA answer
        flags.set_flags({"decode_attention_block_kv": 64})
        try:
            q, k, v = _qkv(1, 1, 8, 2, 64, 256, seed=29)
            out = cached_decode_attention(q, k, v, 40)
            want = cached_decode_attention_reference(q, k, v, 40)
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
        finally:
            flags.set_flags({"decode_attention_block_kv": 512})

    def test_jit_traced_positions(self):
        q, k, v = _qkv(2, 1, 8, 2, 64, 256, seed=31)
        pos = jnp.asarray([5, 130], jnp.int32)
        got = jax.jit(cached_decode_attention)(q, k, v, pos)
        want = cached_decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_paged_routes_to_kernel_and_matches(self, monkeypatch):
        from paddle_tpu.ops.pallas import decode_attention as mod

        calls = []
        real = mod.paged_decode_attention_pallas
        monkeypatch.setattr(
            mod, "paged_decode_attention_pallas",
            lambda *a, **kw: calls.append(1) or real(*a, **kw))
        bl, mb = 128, 2
        rng = np.random.default_rng(41)
        kc = rng.normal(size=(2, mb * bl, 2, 64)).astype(np.float32)
        vc = rng.normal(size=(2, mb * bl, 2, 64)).astype(np.float32)
        q = jnp.asarray(rng.normal(size=(2, 1, 8, 64)), jnp.float32)
        tables = np.asarray([[4, 2], [3, 1]], np.int32)
        pool = _paged_pool(kc, vc, tables, num_pool=5, bl=bl)
        pos = jnp.asarray([130, 77], jnp.int32)
        got = paged_decode_attention(q, pool, LAYER, pos,
                                     jnp.asarray(tables))
        assert calls, "eligible paged shape did not route to the kernel"
        want = cached_decode_attention_reference(q, jnp.asarray(kc),
                                                 jnp.asarray(vc), pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # routing decision is exposed to the bench
        assert decode_attention_path(2, 1, 8, 2, 64, mb * bl,
                                     paged_block_len=bl)[0] \
            == "pallas_decode"

    def test_paged_unaligned_block_len_takes_gather_path(self, monkeypatch):
        from paddle_tpu.ops.pallas import decode_attention as mod

        calls = []
        monkeypatch.setattr(mod, "paged_decode_attention_pallas",
                            lambda *a, **kw: calls.append(1))
        bl, mb = 64, 4                         # 64 % 128 != 0
        rng = np.random.default_rng(43)
        kc = rng.normal(size=(1, mb * bl, 2, 64)).astype(np.float32)
        vc = rng.normal(size=(1, mb * bl, 2, 64)).astype(np.float32)
        q = jnp.asarray(rng.normal(size=(1, 1, 8, 64)), jnp.float32)
        tables = np.asarray([[4, 3, 2, 1]], np.int32)
        pool = _paged_pool(kc, vc, tables, num_pool=5, bl=bl)
        got = paged_decode_attention(q, pool, LAYER, 100,
                                     jnp.asarray(tables))
        assert not calls
        want = cached_decode_attention_reference(q, jnp.asarray(kc),
                                                 jnp.asarray(vc), 100)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert decode_attention_path(1, 1, 8, 2, 64, mb * bl,
                                     paged_block_len=bl)[0] == "xla_math"

    def test_llama_paged_decode_step_through_kernel(self):
        """Model-level paged integration: a llama decode_step over the
        block pool (shuffled physical blocks) must reproduce the
        contiguous decode_step's logits, with the incremental attention
        running the flash-decode kernel."""
        import paddle_tpu as pt
        from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
        from paddle_tpu.models.generation import init_kv_cache
        from paddle_tpu.serving.kv_cache import init_paged_kv_cache

        pt.seed(5)
        lm = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
        lm.eval()
        ids = jnp.asarray(np.random.default_rng(6).integers(
            0, 256, (2, 7)), jnp.int32)
        cache = init_kv_cache(lm.config, 2, 128)
        _, cache = lm.decode_step(ids, cache, 0)
        positions = jnp.asarray([7, 5], jnp.int32)
        tok = jnp.asarray([[3], [9]], jnp.int32)
        logits_c, cache_c = lm.decode_step(tok, cache, positions)
        # pool the contiguous rows into shuffled physical blocks (one
        # 128-token block per row at this max_length)
        tables = np.asarray([[3], [1]], np.int32)
        pool = init_paged_kv_cache(lm.config, 5, 128)

        def fused(row):         # (L, 2, 128, Hkv, D) -> the pool's block
            return row.reshape(row.shape[:3] + (-1,))
        pool = pool.at[:, :, 3].set(fused(cache[:, :, 0]))
        pool = pool.at[:, :, 1].set(fused(cache[:, :, 1]))
        flags.set_flags({"decode_attention_min_len": 128})
        try:
            logits_p, pool = lm.decode_step(
                tok, pool, positions, block_tables=jnp.asarray(tables))
        finally:
            flags.set_flags({"decode_attention_min_len": 256})
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(logits_c),
                                   rtol=2e-4, atol=2e-4)
        # the paged write landed in each row's physical block
        np.testing.assert_allclose(np.asarray(pool[:, :, 3]),
                                   np.asarray(fused(cache_c[:, :, 0])),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(pool[:, :, 1]),
                                   np.asarray(fused(cache_c[:, :, 1])),
                                   rtol=2e-5, atol=2e-5)

    def test_llama_decode_step_through_kernel(self):
        """The serving shape end to end: a llama decode_step with a
        per-row position vector must produce the same logits whether the
        incremental attention runs the flash-decode kernel or the XLA
        math path (min_len flag is the only switch)."""
        import paddle_tpu as pt
        from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
        from paddle_tpu.models.generation import init_kv_cache

        pt.seed(5)
        lm = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
        lm.eval()
        ids = jnp.asarray(np.random.default_rng(6).integers(
            0, 256, (2, 7)), jnp.int32)
        cache = init_kv_cache(lm.config, 2, 128)   # 128-aligned cache
        _, cache = lm.decode_step(ids, cache, 0)
        positions = jnp.asarray([7, 5], jnp.int32)
        tok = jnp.asarray([[3], [9]], jnp.int32)
        try:
            flags.set_flags({"decode_attention_min_len": 128})
            assert decode_attention_path(
                2, 1, lm.config.num_attention_heads,
                lm.config.num_key_value_heads, lm.config.head_dim,
                128)[0] == "pallas_decode"
            logits_k, cache_k = lm.decode_step(tok, cache, positions)
            flags.set_flags({"decode_attention_min_len": 1 << 31})
            logits_x, cache_x = lm.decode_step(tok, cache, positions)
        finally:
            flags.set_flags({"decode_attention_min_len": 256})
        np.testing.assert_allclose(np.asarray(logits_k),
                                   np.asarray(logits_x),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(cache_k), np.asarray(cache_x),
                                   rtol=2e-5, atol=2e-5)
