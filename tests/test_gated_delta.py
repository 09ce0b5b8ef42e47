"""The gated delta rule (``ops/gated_delta.py``): the recurrence as the
oracle, the one-token step, the chunked (WY / UT) form, the serving leaf's
update through the XLA twins and through the Pallas kernels in interpret
mode.  Seeded, tiny, float32 on the CPU."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.ops import gated_delta as gd

H, DK, DV = 4, 16, 64      # two heads a lane group of 128


@contextlib.contextmanager
def interpret_mode(on):
    old = flags.flag("pallas_interpret")
    flags.set_flags({"pallas_interpret": on})
    try:
        yield
    finally:
        flags.set_flags({"pallas_interpret": old})


def _tokens(seed, t, h=H, dk=DK, dv=DV, lead=()):
    """Normalised q and k, v, decays from 0.2 to 0.999, β up to 2."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(*lead, t, h, dk))) * dk ** -0.5
    # neighbours' keys are correlated, as behind a short convolution
    k = rng.normal(size=(*lead, t, h, dk))
    k = unit(k + 0.7 * np.roll(k, 1, axis=-3))
    v = rng.normal(size=(*lead, t, h, dv))
    g = np.log(rng.uniform(0.2, 0.999, size=(*lead, t, h)))
    beta = rng.uniform(0.0, 2.0, size=(*lead, t, h))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _state(seed, lead=(), h=H, dk=DK, dv=DV):
    rng = np.random.default_rng([seed, 7])
    return jnp.asarray(rng.normal(size=(*lead, h, dk, dv)), jnp.float32)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(1.0, float(np.abs(b).max()))
    np.testing.assert_allclose(a, b, atol=tol * scale, rtol=0)


def test_layout_round_trip():
    s = _state(0, lead=(3,))
    leaf = gd.heads_to_lanes(s)
    assert leaf.shape == (3, DK, H * DV)
    np.testing.assert_array_equal(gd.lanes_to_heads(leaf, H), s)
    # head h's d_v columns lie side by side on the last axis
    np.testing.assert_array_equal(leaf[0, :, DV:2 * DV], s[0, 1])


@pytest.mark.parametrize("c", [1, 2, 8, 16, 32, 64, 128])
def test_unit_lower_inverse(c):
    rng = np.random.default_rng(c)
    a = np.tril(rng.normal(size=(3, c, c)) * 0.4, -1).astype(np.float32)
    got = gd.unit_lower_inverse(jnp.asarray(a))
    want = np.linalg.inv(np.eye(c) + a.astype(np.float64))
    close(got, want, 1e-4)


def test_unit_lower_inverse_refuses_other_sizes():
    with pytest.raises(ValueError, match="16"):
        gd.unit_lower_inverse(jnp.zeros((48, 48)))


def test_step_is_the_recurrence():
    q, k, v, g, beta = _tokens(1, 5)
    s = _state(1)
    want_o, want_s = gd.gated_delta_recurrence(q, k, v, g, beta, s)
    for t in range(5):
        o, s = gd.gated_delta_step(s, q[t], k[t], v[t], g[t], beta[t])
        close(o, want_o[t])
    close(s, want_s)


@pytest.mark.parametrize("t,chunk,carried", [
    (64, None, False), (256, None, True), (100, None, True),
    (8, None, True), (5, None, False), (150, 16, True), (64, 32, True)])
def test_chunked_is_the_recurrence(t, chunk, carried):
    q, k, v, g, beta = _tokens(t, t)
    s0 = _state(t) if carried else None
    want_o, want_s = gd.gated_delta_recurrence(q, k, v, g, beta, s0)
    o, s = gd.gated_delta_chunked(q, k, v, g, beta, s0, chunk=chunk)
    assert o.shape == want_o.shape
    close(o, want_o, 1e-4)
    close(s, want_s, 1e-4)


def test_sub_chunk():
    assert [gd.sub_chunk(t) for t in (1, 8, 9, 33, 64, 256)] == [
        8, 8, 16, 64, 64, 64]


@pytest.mark.parametrize("form", ["recurrence", "chunked"])
def test_an_invalid_token_is_an_exact_identity(form):
    """Padding after a valid prefix changes nothing, bit for bit: the state
    handed back is the state as of the last valid token."""
    q, k, v, g, beta = _tokens(3, 24)
    s0 = _state(3)
    valid = jnp.arange(24) < 13
    gm, bm = gd.mask_invalid(g, beta, valid)
    run = (gd.gated_delta_recurrence if form == "recurrence"
           else gd.gated_delta_chunked)
    o, s = run(q, k, v, gm, bm, s0)
    o13, s13 = run(q[:13], k[:13], v[:13], g[:13], beta[:13], s0)
    if form == "recurrence":
        np.testing.assert_array_equal(s, s13)
        np.testing.assert_array_equal(o[:13], o13)
    else:
        # one sub-chunk of 32 against one of 16: other products, same rule
        close(s, s13, 1e-5)
        close(o[:13], o13, 1e-5)
    # and with no valid token at all the state comes back as it went in
    g0, b0 = gd.mask_invalid(g, beta, jnp.zeros(24, bool))
    np.testing.assert_array_equal(run(q, k, v, g0, b0, s0)[1], s0)


# -- the serving leaf --------------------------------------------------------

LAYERS, ROWS = 3, 7


def _leaf(seed):
    return gd.heads_to_lanes(_state(seed, lead=(LAYERS, ROWS)))


def _want_rows(leaf, layer, first, toks, valid, fresh):
    """Row by row through the oracle."""
    q, k, v, g, beta = toks
    leaf = np.array(leaf)
    outs = []
    for r in range(q.shape[0]):
        n = int(np.asarray(valid[r]).sum())
        s0 = gd.lanes_to_heads(jnp.asarray(leaf[layer, first + r]), H)
        if fresh[r]:
            s0 = jnp.zeros_like(s0)
        o, s = gd.gated_delta_recurrence(q[r, :n], k[r, :n], v[r, :n],
                                         g[r, :n], beta[r, :n], s0)
        out = np.zeros(v.shape[1:], np.float32)
        out[:n] = o
        outs.append(out if n else np.zeros_like(out))
        if n:
            leaf[layer, first + r] = gd.heads_to_lanes(s)
    return np.stack(outs), leaf


CASES = {
    # name: (rows, positions, first, valid lengths a row, fresh rows)
    "step": (5, 1, 1, [1, 0, 1, 1, 0], [False, False, True, False, True]),
    "step_none_live": (4, 1, 2, [0, 0, 0, 0], [True, False, False, False]),
    "step_all_rows": (ROWS, 1, 0, [1] * ROWS, [False] * ROWS),
    "chunk": (1, 32, 4, [32], [False]),
    "chunk_padded_tail": (1, 32, 6, [19], [False]),
    "chunk_fresh": (1, 64, 0, [40], [True]),
    "chunk_stub_no_token": (1, 8, 6, [0], [True]),
    "chunk_odd_length": (1, 21, 2, [21], [False]),
    "rows_of_chunks": (3, 16, 2, [16, 0, 9], [False, True, True]),
}


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla_twin", "pallas_interpret"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_update_of_the_leaf(case, interpret):
    rows, s, first, lens, fresh = CASES[case]
    toks = _tokens(len(case), s, lead=(rows,))
    valid = jnp.arange(s)[None] < jnp.asarray(lens)[:, None]
    fresh = jnp.asarray(fresh)
    leaf = _leaf(rows)
    layer = 1
    want_o, want_leaf = _want_rows(leaf, layer, first, toks, valid,
                                   np.asarray(fresh))

    def run(leaf):
        return gd.gated_delta_update(leaf, layer, (first, rows), *toks,
                                     valid=valid, fresh=fresh)
    with interpret_mode(interpret):
        o, got = jax.jit(run)(leaf)
    close(got, want_leaf, 1e-4)
    live = np.asarray(valid)
    close(np.where(live[..., None, None], o, 0), want_o, 1e-4)
    # rows without a real token, the other rows and layers: bit for bit
    untouched = np.ones((LAYERS, ROWS), bool)
    untouched[layer, [first + r for r in range(rows) if lens[r]]] = False
    np.testing.assert_array_equal(np.asarray(got)[untouched],
                                  np.asarray(leaf)[untouched])
    assert not np.asarray(o)[~live.any(axis=1)].any()


def test_update_counts_its_path():
    from paddle_tpu import observability as obs

    def count(op, path):
        fam = obs.snapshot().get("ops.kernel_path", {"series": []})
        return sum(r["value"] for r in fam["series"]
                   if r["labels"].get("op") == op
                   and r["labels"].get("path") == path)
    toks = _tokens(0, 1, lead=(2,))
    before = count("gated_delta_step", "xla_math")
    gd.gated_delta_update(_leaf(0), 0, (0, 2), *toks)
    assert count("gated_delta_step", "xla_math") == before + 1
    with interpret_mode(True):
        before = count("gated_delta_chunk", "pallas")
        toks = _tokens(0, 16, lead=(1,))
        gd.gated_delta_update(_leaf(0), 0, (3, 1), *toks)
        assert count("gated_delta_chunk", "pallas") == before + 1
        # several rows of several positions: the chunk kernel walks one
        before = count("gated_delta_chunk", "xla_math")
        toks = _tokens(0, 16, lead=(2,))
        gd.gated_delta_update(_leaf(0), 0, (3, 2), *toks)
        assert count("gated_delta_chunk", "xla_math") == before + 1


def test_kernels_refuse_what_they_cannot_lay_out():
    from paddle_tpu.ops.pallas import gated_delta as pk
    assert pk.lane_group(30, 192) == 2
    assert pk.lane_group(4, 64) == 2
    assert pk.lane_group(4, 128) == 1
    with pytest.raises(NotImplementedError, match="lane tiles"):
        pk.lane_group(3, 192)
