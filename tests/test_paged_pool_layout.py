"""The paged pool is stored as the flash-decode kernel reads it —
``(L, 2, num_blocks, block_len, Hkv·D)`` — and the kernel is handed the
pool itself with a static layer index (ROADMAP S1, paged half).

Two properties: the kernel, on a stacked pool with ``L >= 2``, reads ITS
layer's K and V through a scattered block table (bf16 and int8, against
the XLA gather reference); and the engine's paged step programs, traced
with the Pallas path forced, give every ``pallas_call`` the pool's own
buffer and form no array the size of a layer's K or V anywhere — the
per-layer slice and relayout copies that were 40 % of the serving tick on
the chip (PERF.md section 6, PR 25)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.ops.attention import (cached_decode_attention_reference,
                                      paged_decode_attention_reference)
from paddle_tpu.ops.pallas.decode_attention import \
    paged_decode_attention_pallas
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import init_paged_kv_cache

BL = 128


def _rows_and_pool(dtype, layers=3, layer=1, seed=0):
    """Two rows' contiguous K/V (f32 values), and a stacked pool holding
    them at ``layer`` through a scattered table with a shared block; every
    other layer and every unused block is noise."""
    b, hkv, d, mb, npool = 2, 2, 64, 3, 9
    tables = np.asarray([[7, 2, 5], [7, 4, 1]], np.int32)  # block 7 shared
    rng = np.random.default_rng(seed)
    kc = rng.normal(size=(b, mb * BL, hkv, d)).astype(np.float32)
    vc = rng.normal(size=(b, mb * BL, hkv, d)).astype(np.float32)
    kc[1, :BL], vc[1, :BL] = kc[0, :BL], vc[0, :BL]
    pool = rng.normal(size=(layers, 2, npool, BL, hkv * d)).astype(np.float32)
    for r in range(b):
        for j in range(mb):
            sl = slice(j * BL, (j + 1) * BL)
            pool[layer, 0, tables[r, j]] = kc[r, sl].reshape(BL, hkv * d)
            pool[layer, 1, tables[r, j]] = vc[r, sl].reshape(BL, hkv * d)
    q = jnp.asarray(rng.normal(size=(b, 1, 8, d)), dtype)
    return q, kc, vc, pool, jnp.asarray(tables), jnp.asarray([300, 140],
                                                             jnp.int32)


@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_kernel_reads_its_layer_of_the_stacked_pool(cache):
    layer = 1
    q, kc, vc, pool, tables, pos = _rows_and_pool(jnp.bfloat16, layer=layer)
    if cache == "bf16":
        pool_d = jnp.asarray(pool, jnp.bfloat16)
        scale, tol = None, 2e-2             # bf16 operands on both sides
        # the contiguous rows the table stands for, in the same precision
        want_rows = cached_decode_attention_reference(
            q, jnp.asarray(kc, jnp.bfloat16), jnp.asarray(vc, jnp.bfloat16),
            pos)
    else:
        heads = pool.reshape(pool.shape[:4] + (2, 64))
        sc = np.abs(heads).max(axis=(3, 5)) / 127.0     # (L, 2, nb, Hkv)
        pool_d = jnp.asarray(np.clip(np.round(
            heads / sc[:, :, :, None, :, None]), -127, 127).astype(
                np.int8).reshape(pool.shape))
        scale, tol, want_rows = jnp.asarray(sc, jnp.float32), 2e-2, None
    got = paged_decode_attention_pallas(q, pool_d, layer, pos, tables,
                                        pool_scale=scale, interpret=True)
    want = paged_decode_attention_reference(q, pool_d, layer, pos, tables,
                                            pool_scale=scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    if want_rows is not None:
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want_rows, np.float32),
                                   rtol=tol, atol=tol)
    # another layer's blocks are other data: the layer index is read
    other = paged_decode_attention_pallas(q, pool_d, 0, pos, tables,
                                          pool_scale=scale, interpret=True)
    assert np.abs(np.asarray(other, np.float32)
                  - np.asarray(got, np.float32)).max() > 0.1


def test_pool_layout_is_the_kernels():
    cfg = tiny_llama_config()
    hd = cfg.num_key_value_heads * cfg.head_dim
    pool = init_paged_kv_cache(cfg, 5, 16)
    assert pool.shape == (cfg.num_hidden_layers, 2, 5, 16, hd)
    q8 = init_paged_kv_cache(cfg, 5, 16, quantized=True)
    assert q8["kv"].shape == pool.shape and q8["kv"].dtype == jnp.int8
    assert q8["scale"].shape == (cfg.num_hidden_layers, 2, 5,
                                 cfg.num_key_value_heads)


# -- the step programs: the pool goes to the kernel, nothing pool-sized is
# -- formed on the way ------------------------------------------------------

@pytest.fixture
def pallas_forced():
    old = {k: flags.flag(k) for k in ("pallas_interpret",
                                      "decode_attention_min_len",
                                      "graph_lint")}
    flags.set_flags({"pallas_interpret": True,
                     "decode_attention_min_len": 256, "graph_lint": "off"})
    yield
    flags.set_flags(old)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (pjit bodies, scans, custom calls), depth first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


# what would show a layer being cut out of the pool or re-laid-out
_COPIES = ("slice", "dynamic_slice", "squeeze", "reshape", "transpose",
           "gather", "copy", "convert_element_type", "concatenate")


@pytest.mark.parametrize("kw,calls_per_layer", [
    (dict(), 1),                                        # _step_impl_paged
    (dict(chunked=True, prefill_chunk=64), 2),    # _mixed_step_impl_paged
    (dict(kv_cache_dtype="int8"), 1),
], ids=["step", "mixed_step", "step_int8"])
def test_step_program_hands_the_kernel_the_pool(pallas_forced, kw,
                                                calls_per_layer):
    pt.seed(3)
    lm = LlamaForCausalLM(tiny_llama_config(max_position_embeddings=256,
                                            context_parallel="gspmd"))
    lm.eval()
    eng = ServingEngine(lm, num_slots=2, max_length=256, paged=True,
                        block_len=BL, num_blocks=33, **kw)
    pool = eng._cache["kv"] if eng.quantized else eng._cache
    layer_k = int(np.prod(pool.shape[2:]))      # one layer's K, in elements
    jaxpr = jax.make_jaxpr(eng._step_fn.python_fn)(*eng._lint_args()).jaxpr
    calls = 0
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            calls += 1
            kv = [v.aval.shape for v in eqn.invars
                  if int(np.prod(v.aval.shape)) >= layer_k]
            # K and V: the pool's own buffer, twice; nothing else pool-sized
            assert kv == [pool.shape, pool.shape], (name, kv)
            continue
        for out in eqn.outvars:
            shape = getattr(out.aval, "shape", ())
            if int(np.prod(shape)) < layer_k:
                continue
            # only the pool itself may be that large (the in-place
            # scatters and the jit boundaries that thread it through)
            assert shape == pool.shape, (name, shape)
            assert name not in _COPIES, (name, shape)
    assert calls == calls_per_layer * lm.config.num_hidden_layers
