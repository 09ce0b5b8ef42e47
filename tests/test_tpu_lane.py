"""Real-TPU test lane: everything here runs on the chip, not the fake CPU
mesh.

Run with ``PT_TPU_LANE=1 python -m pytest tests/ -m tpu -q`` (or
``python bench.py --selftest``) on an otherwise idle chip — a chip belongs
to one process at a time.  This is the reference's GPU-CI-lane equivalent
(SURVEY §4 CI-driver row) and the round-3 verdict's top ask: the CPU lane
runs Pallas in interpret mode and never exercises real lowerings, which let
``eig``'s missing TPU kernel ship as "implemented".  Here the Pallas kernels
compile via Mosaic and are checked against their XLA references at ENGINE
geometry (the parity cases ``chip_smoke.py`` also runs), every
TARGET_SURFACE op executes on-device, and train/decode take one real step.

With ``PT_TPU_LANE=1`` and no TPU the lane FAILS: a lane that skips
everything exits 0 and reads as a pass.

Numerical *semantics* stay covered by the CPU-lane OpTests; tolerances here
are loose where TPU matmul precision differs (bf16-ish defaults).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
import paddle_tpu as pt

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module", autouse=True)
def _require_device():
    if jax.default_backend() != "tpu":
        pytest.fail(f"PT_TPU_LANE=1 but jax's backend is "
                    f"{jax.default_backend()!r}: the TPU lane needs the "
                    f"chip", pytrace=False)


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32))


# ---------------------------------------------------------------------------
# Pallas flash attention — Mosaic-compiled, fwd + bwd
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, sq, skv, hq, hkv, d, causal) — block shapes, GQA, head_dim 256
    (1, 256, 256, 2, 2, 64, True),
    (1, 512, 1024, 2, 1, 64, True),    # multi q-block, GQA, Sq < Skv
    (2, 256, 512, 4, 2, 32, True),
    (1, 256, 256, 2, 2, 128, False),
    (1, 256, 256, 1, 1, 256, True),    # head_dim 256 (VMEM block scaling)
]


@pytest.mark.parametrize("b,sq,skv,hq,hkv,d,causal", FLASH_CASES)
def test_flash_fwd_on_chip(b, sq, skv, hq, hkv, d, causal):
    from paddle_tpu.ops.attention import flash_attention_reference
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

    q, k, v = (_rand((b, sq, hq, d), 0), _rand((b, skv, hkv, d), 1),
               _rand((b, skv, hkv, d), 2))
    out, lse = flash_attention_pallas(q, k, v, causal=causal)
    ref, ref_lse = flash_attention_reference(q, k, v, causal=causal,
                                             return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("b,sq,skv,hq,hkv,d,causal", [
    FLASH_CASES[0], FLASH_CASES[1], FLASH_CASES[4]])
def test_flash_bwd_on_chip(b, sq, skv, hq, hkv, d, causal):
    from paddle_tpu.ops.attention import flash_attention_reference
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

    q, k, v = (_rand((b, sq, hq, d), 10), _rand((b, skv, hkv, d), 11),
               _rand((b, skv, hkv, d), 12))
    w = _rand((b, sq, hq, d), 13)

    def loss_pallas(q, k, v):
        out, _ = flash_attention_pallas(q, k, v, causal=causal)
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        out, _ = flash_attention_reference(q, k, v, causal=causal,
                                           return_lse=True)
        return jnp.sum(out * w)

    got = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=5e-2, atol=5e-2,
            err_msg=f"d{name} mismatch on chip")


def test_flash_varlen_segment_ids_on_chip():
    """Packed-sequence masking inside the Mosaic-compiled kernel."""
    from paddle_tpu.ops.attention import flash_attention_reference
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

    b, s, h, d = 1, 512, 2, 64
    q, k, v = (_rand((b, s, h, d), 20), _rand((b, s, h, d), 21),
               _rand((b, s, h, d), 22))
    seg = jnp.asarray(
        np.repeat([0, 1, 2, 3], s // 4)[None, :], jnp.int32)
    out, _ = flash_attention_pallas(q, k, v, causal=True, segment_ids=seg)
    same = seg[:, :, None] == seg[:, None, :]          # (B, Sq, Skv)
    mask = same[:, None, :, :]                         # (B, 1, Sq, Skv)
    ref, _ = flash_attention_reference(q, k, v, attn_mask=mask, causal=True,
                                       return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# every Pallas form the engine reaches, at engine geometry (hq 32 / hkv 8 /
# d 128, L 8192, block_len 128) — the same functions chip_smoke.py calls;
# each asserts the tolerance written beside it there
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(chip_smoke.PARITY_CASES))
def test_kernel_parity_at_engine_geometry(name):
    chip_smoke.PARITY_CASES[name]()


# ---------------------------------------------------------------------------
# Pallas flash-decode (split-KV cached decode attention) — Mosaic-compiled
# ---------------------------------------------------------------------------

DECODE_ATTN_CASES = [
    # (b, s, hq, hkv, d, per_row) — GQA, head_dim 128/256, per-row pos
    (2, 1, 8, 2, 128, False),          # GQA g=4, scalar pos
    (2, 1, 8, 2, 128, True),           # per-row pos (serving slot batch)
    (1, 1, 4, 4, 256, True),           # MHA, head_dim 256
    (2, 3, 8, 2, 128, True),           # s>1: prefill-into-occupied-slot
]


@pytest.mark.parametrize("b,s,hq,hkv,d,per_row", DECODE_ATTN_CASES)
def test_flash_decode_kernel_on_chip(b, s, hq, hkv, d, per_row):
    """The scalar-prefetch clamped-index-map kernel must compile via
    Mosaic (the CPU lane only ever interprets it) and match the XLA math
    path over a live-prefix + dead-tail cache."""
    from paddle_tpu.ops.attention import cached_decode_attention_reference
    from paddle_tpu.ops.pallas.decode_attention import \
        decode_attention_pallas

    L = 1024
    q = _rand((b, s, hq, d), 40)
    k = _rand((b, L, hkv, d), 41)
    v = _rand((b, L, hkv, d), 42)
    pos = (jnp.asarray([137, 901][:b], jnp.int32) if per_row
           else jnp.int32(500))
    out = decode_attention_pallas(q, k, v, pos)
    ref = cached_decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_flash_decode_dispatch_routes_on_chip():
    """At kv_len >= FLAGS_decode_attention_min_len the public
    cached_decode_attention must take the kernel on the real backend and
    agree with the math path."""
    from paddle_tpu import flags
    from paddle_tpu.ops.attention import (cached_decode_attention,
                                          cached_decode_attention_reference,
                                          decode_attention_path)

    b, s, hq, hkv, d, L = 2, 1, 8, 2, 128, 4096
    assert decode_attention_path(b, s, hq, hkv, d, L)[0] == "pallas_decode"
    q = _rand((b, s, hq, d), 50)
    k = _rand((b, L, hkv, d), 51)
    v = _rand((b, L, hkv, d), 52)
    pos = jnp.asarray([63, 2900], jnp.int32)
    out = cached_decode_attention(q, k, v, pos)
    ref = cached_decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Pallas rms_norm — dispatch threshold boundary on-device
# ---------------------------------------------------------------------------

def test_rms_norm_threshold_boundary_on_chip():
    # the route is disabled by default (BENCH_OPS.json: XLA wins at every
    # shape) — the lane still pins the kernel's Mosaic numerics at an
    # explicit opt-in threshold
    from paddle_tpu import flags
    from paddle_tpu.ops.norms import rms_norm, rms_norm_reference

    thr = 8192
    flags.set_flags({"rms_norm_pallas_min_dim": thr})
    try:
        for dim in (thr, 512):  # Pallas path at the threshold, XLA below
            x = _rand((4, dim), 30)
            w = _rand((dim,), 31)
            got = rms_norm(x, w)
            want = rms_norm_reference(x, w)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-2, atol=1e-2,
                                       err_msg=f"rms_norm dim={dim}")
    finally:
        flags.set_flags({"rms_norm_pallas_min_dim": 1 << 31})


def test_rms_norm_pallas_grads_on_chip():
    from paddle_tpu import flags
    from paddle_tpu.ops.norms import rms_norm, rms_norm_reference

    thr = 8192
    flags.set_flags({"rms_norm_pallas_min_dim": thr})
    try:
        x = _rand((2, thr), 32)
        got = jax.grad(lambda a: jnp.sum(jnp.square(rms_norm(a))))(x)
        want = jax.grad(
            lambda a: jnp.sum(jnp.square(rms_norm_reference(a))))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)
    finally:
        flags.set_flags({"rms_norm_pallas_min_dim": 1 << 31})


# ---------------------------------------------------------------------------
# eig / eigvals — the round-3 crash, now host-dispatched
# ---------------------------------------------------------------------------

def test_eig_on_device_arrays():
    from paddle_tpu.tensor import linalg

    a = np.random.default_rng(3).normal(size=(4, 4)).astype(np.float32)
    x = jnp.asarray(a)  # lives on the TPU
    w, vecs = linalg.eig(x)
    want = np.sort_complex(np.linalg.eigvals(a.astype(np.float64)))
    np.testing.assert_allclose(np.sort_complex(np.asarray(w, np.complex128)),
                               want, rtol=1e-3, atol=1e-3)
    w2 = linalg.eigvals(x)
    np.testing.assert_allclose(np.sort_complex(np.asarray(w2, np.complex128)),
                               want, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# registry sweep — every TARGET_SURFACE op executes on the chip
# ---------------------------------------------------------------------------

def test_registry_sweep_on_chip():
    """Batched form (round-4 verdict #2): grouped jitted programs cut the
    sweep from ~30 min of per-op eager compiles to minutes; error
    attribution falls back per-op via bisection (see
    op_smoke.run_batched).  ``python bench.py`` embeds this same sweep's
    result in its driver-captured JSON (its ``--lane`` child)."""
    from paddle_tpu.framework import op_smoke

    failures = op_smoke.run_batched()
    assert not failures, (
        f"{len(failures)} registry ops fail on the real chip:\n"
        + "\n".join(f"  {k}: {v[:160]}" for k, v in sorted(failures.items())))


# ---------------------------------------------------------------------------
# train + decode smoke on-device
# ---------------------------------------------------------------------------

def test_llama_train_step_on_chip():
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
    from paddle_tpu.optimizer import AdamW

    hcg = dist.HybridCommunicateGroup(devices=jax.devices()[:1])
    dist.set_hybrid_group(hcg)
    try:
        pt.seed(7)
        model = LlamaForCausalLM(tiny_llama_config())
        opt = AdamW(learning_rate=1e-3)
        step, params, opt_state = dist.build_train_step(model, opt, hcg=hcg)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 256, (4, 17))
        batch = dist.shard_batch(
            {"input_ids": jnp.asarray(ids[:, :-1]),
             "labels": jnp.asarray(ids[:, 1:])}, hcg)
        loss1, params, opt_state = step(params, opt_state, batch,
                                        jax.random.key(0))
        loss2, params, opt_state = step(params, opt_state, batch,
                                        jax.random.key(1))
        assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    finally:
        dist.set_hybrid_group(None)


def test_llama_decode_on_chip():
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    pt.seed(11)
    lm = LlamaForCausalLM(tiny_llama_config())
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 6)))
    out = lm.generate(ids, max_new_tokens=4)
    assert out.shape == (2, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_prefill_flash_forced_on_chip():
    """Cached prefill (static pos=0) must take the real Mosaic kernel on
    the chip — flash_attention_force turns a silent fallback into an
    error — and match the all-reference generation exactly."""
    from paddle_tpu import flags
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    cfg = tiny_llama_config(hidden_size=256, intermediate_size=256,
                            num_attention_heads=4, num_key_value_heads=2,
                            max_position_embeddings=160)
    pt.seed(31)
    model = LlamaForCausalLM(cfg)
    model.eval()
    ids = jnp.asarray(np.random.default_rng(33).integers(
        0, cfg.vocab_size, (2, 128)), jnp.int32)
    ref = np.asarray(model.generate(ids, max_new_tokens=4))
    model._generate_jit_cache.clear()
    flags.set_flags({"flash_attention_force": True})
    try:
        out = np.asarray(model.generate(ids, max_new_tokens=4))
    finally:
        flags.set_flags({"flash_attention_force": False})
    np.testing.assert_array_equal(ref, out)
