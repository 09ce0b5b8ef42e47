"""Ring attention + Ulysses tests vs the full-sequence flash oracle.

Pattern (SURVEY.md §4): seq-sharded parallel attention must equal the
single-device full-sequence computation, forward and backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu.distributed as dist
from paddle_tpu.ops.attention import flash_attention_reference
from paddle_tpu.ops.ring_attention import (merge_attention,
                                           ring_attention_shard,
                                           ulysses_attention_shard)


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32))


def _sep_mesh(p):
    return Mesh(np.asarray(jax.devices()[:p]), ("sep",))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hkv", [4, 2])
def test_ring_matches_full(causal, hkv):
    b, s, h, d = 2, 64, 4, 16
    q, k, v = _rand((b, s, h, d), 0), _rand((b, s, hkv, d), 1), \
        _rand((b, s, hkv, d), 2)
    mesh = _sep_mesh(4)
    fn = jax.shard_map(
        lambda q_, k_, v_: ring_attention_shard(q_, k_, v_, "sep",
                                                causal=causal),
        mesh=mesh, in_specs=(P(None, "sep"),) * 3,
        out_specs=(P(None, "sep"), P(None, None, "sep")))
    out, lse = fn(q, k, v)
    ref, ref_lse = flash_attention_reference(q, k, v, causal=causal,
                                             return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.slow
def test_ring_grads_match_full():
    b, s, h, d = 1, 64, 2, 16
    q, k, v = _rand((b, s, h, d), 10), _rand((b, s, h, d), 11), \
        _rand((b, s, h, d), 12)
    w = _rand((b, s, h, d), 13)
    mesh = _sep_mesh(4)

    ring = jax.shard_map(
        lambda q_, k_, v_: ring_attention_shard(q_, k_, v_, "sep",
                                                causal=True)[0],
        mesh=mesh, in_specs=(P(None, "sep"),) * 3,
        out_specs=P(None, "sep"))

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) * w)

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention_reference(
            q, k, v, causal=True, return_lse=False) * w)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    b, s, h, d = 2, 64, 8, 16
    q, k, v = _rand((b, s, h, d), 20), _rand((b, s, h, d), 21), \
        _rand((b, s, h, d), 22)
    mesh = _sep_mesh(4)
    fn = jax.shard_map(
        lambda q_, k_, v_: ulysses_attention_shard(q_, k_, v_, "sep",
                                                   causal=causal)[0],
        mesh=mesh, in_specs=(P(None, "sep"),) * 3,
        out_specs=P(None, "sep"))
    out = fn(q, k, v)
    ref = flash_attention_reference(q, k, v, causal=causal, return_lse=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_merge_attention_identity():
    """Merging with a dead partial (lse = -inf) must be the identity."""
    from paddle_tpu.ops.attention import NEG_INF
    b, s, h, d = 1, 8, 2, 4
    out = _rand((b, s, h, d), 30)
    lse = _rand((b, h, s), 31)
    dead_o = jnp.zeros_like(out)
    dead_l = jnp.full((b, h, s), NEG_INF)
    m_out, m_lse = merge_attention(out, lse, dead_o, dead_l)
    np.testing.assert_allclose(np.asarray(m_out), np.asarray(out),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_lse), np.asarray(lse),
                               rtol=1e-6, atol=1e-6)


def test_context_parallel_attention_in_jit():
    """The model-facing wrapper: embedded shard_map under jit on the hybrid
    mesh, ring mode, vs the unsharded oracle."""
    hcg = dist.HybridCommunicateGroup(dp_degree=2, sep_degree=2,
                                      mp_degree=2)
    dist.set_hybrid_group(hcg)
    try:
        b, s, h, d = 2, 32, 4, 16
        q, k, v = _rand((b, s, h, d), 40), _rand((b, s, h, d), 41), \
            _rand((b, s, h, d), 42)

        @jax.jit
        def f(q, k, v):
            return dist.context_parallel_attention(q, k, v, causal=True,
                                                   mode="ring")

        out = f(q, k, v)
        ref = flash_attention_reference(q, k, v, causal=True,
                                        return_lse=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)
    finally:
        dist.set_hybrid_group(None)


def test_ulysses_lse_layout_matches_contract():
    """ulysses must return lse in the per-shard (B, H_local, S_local) layout
    (same contract as ring), not the all_to_all'd intermediate."""
    b, s, h, d = 1, 64, 8, 16
    q = _rand((b, s, h, d), 60)
    mesh = _sep_mesh(4)
    fn = jax.shard_map(
        lambda q_, k_, v_: ulysses_attention_shard(q_, k_, v_, "sep",
                                                   causal=True),
        mesh=mesh, in_specs=(P(None, "sep"),) * 3,
        out_specs=(P(None, "sep"), P(None, None, "sep")))
    out, lse = fn(q, q, q)
    assert lse.shape == (b, h, s)
    _, ref_lse = flash_attention_reference(q, q, q, causal=True,
                                           return_lse=True)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# varlen (packed sequences) × context parallelism — round-3 verdict #2
# ---------------------------------------------------------------------------

def _segments(b, s, n_docs, seed=0):
    """Random doc boundaries → (B, S) int32 non-decreasing segment ids."""
    rng = np.random.default_rng(seed)
    seg = np.zeros((b, s), np.int32)
    for i in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s), n_docs - 1,
                                  replace=False))
        seg[i] = np.searchsorted(cuts, np.arange(s), side="right")
    return jnp.asarray(seg)


def _masked_ref(q, k, v, seg, causal=True):
    from paddle_tpu.ops.attention import segment_mask
    mask = segment_mask(seg, seg)
    return flash_attention_reference(q, k, v, attn_mask=mask, causal=causal,
                                     return_lse=True)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_varlen_matches_packed_oracle(causal):
    """Segment ids rotate with the KV blocks; every hop masks cross-document
    pairs — result equals the single-device packed (masked) computation."""
    b, s, h, d = 2, 64, 4, 16
    q, k, v = _rand((b, s, h, d), 70), _rand((b, s, h, d), 71), \
        _rand((b, s, h, d), 72)
    seg = _segments(b, s, n_docs=4, seed=7)
    mesh = _sep_mesh(4)
    fn = jax.shard_map(
        lambda q_, k_, v_, s_: ring_attention_shard(
            q_, k_, v_, "sep", causal=causal, segment_ids=s_),
        mesh=mesh, in_specs=(P(None, "sep"),) * 3 + (P(None, "sep"),),
        out_specs=(P(None, "sep"), P(None, None, "sep")))
    out, lse = fn(q, k, v, seg)
    ref, ref_lse = _masked_ref(q, k, v, seg, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.slow
def test_ring_varlen_grads_match_packed_oracle():
    b, s, h, d = 1, 64, 2, 16
    q, k, v = _rand((b, s, h, d), 80), _rand((b, s, h, d), 81), \
        _rand((b, s, h, d), 82)
    w = _rand((b, s, h, d), 83)
    seg = _segments(b, s, n_docs=3, seed=9)
    mesh = _sep_mesh(4)

    ring = jax.shard_map(
        lambda q_, k_, v_, s_: ring_attention_shard(
            q_, k_, v_, "sep", causal=True, segment_ids=s_)[0],
        mesh=mesh, in_specs=(P(None, "sep"),) * 3 + (P(None, "sep"),),
        out_specs=P(None, "sep"))

    gr = jax.grad(lambda q_, k_, v_: jnp.sum(ring(q_, k_, v_, seg) * w),
                  argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda q_, k_, v_: jnp.sum(_masked_ref(q_, k_, v_, seg)[0] * w),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_varlen_matches_packed_oracle(causal):
    b, s, h, d = 2, 64, 8, 16
    q, k, v = _rand((b, s, h, d), 90), _rand((b, s, h, d), 91), \
        _rand((b, s, h, d), 92)
    seg = _segments(b, s, n_docs=4, seed=11)
    mesh = _sep_mesh(4)
    fn = jax.shard_map(
        lambda q_, k_, v_, s_: ulysses_attention_shard(
            q_, k_, v_, "sep", causal=causal, segment_ids=s_),
        mesh=mesh, in_specs=(P(None, "sep"),) * 3 + (P(None, "sep"),),
        out_specs=(P(None, "sep"), P(None, None, "sep")))
    out, lse = fn(q, k, v, seg)
    ref, ref_lse = _masked_ref(q, k, v, seg, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=3e-4, atol=3e-4)


def test_context_parallel_attention_varlen_in_jit():
    """Model-facing wrapper with segment_ids on the hybrid mesh."""
    hcg = dist.HybridCommunicateGroup(dp_degree=2, sep_degree=2,
                                      mp_degree=2)
    dist.set_hybrid_group(hcg)
    try:
        b, s, h, d = 2, 32, 4, 16
        q, k, v = _rand((b, s, h, d), 100), _rand((b, s, h, d), 101), \
            _rand((b, s, h, d), 102)
        seg = _segments(b, s, n_docs=3, seed=13)

        @jax.jit
        def f(q, k, v, seg):
            return dist.context_parallel_attention(
                q, k, v, causal=True, mode="ring", segment_ids=seg)

        out = f(q, k, v, seg)
        ref, _ = _masked_ref(q, k, v, seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)
    finally:
        dist.set_hybrid_group(None)
