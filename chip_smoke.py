"""chip_smoke.py — the quickest proof that the program starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of the one model the serving engine supports — Llama-3-8B
(hidden 4096, 32 q / 8 kv heads, head 128, FFN 14336) with the depth cut
to 4 layers and the vocabulary to 8192 rows, bf16, seeded random weights:

  * trainer — ``dist.build_train_step(model, AdamW, hcg, zero_stage=3)`` at
    batch 2 x seq 2048, fed by ``io.DataLoader(MMapTokenDataset)`` from a
    seeded token bin, the flash kernel forced; loss finite and falling;
  * kernels — each Pallas form the engine reaches, Mosaic-compiled at
    engine geometry, against the XLA reference already in the repo;
  * server — ``ServingEngine.submit/step/drain`` answers eight requests of
    mixed prompt lengths through the default constructor (contiguous cache,
    wave prefill) and through ``paged=True, chunked=True``; every request
    retires in full, one trace per step program, the Pallas paths counted;
    the compiled paged decode step holds no temporary of a layer's K+V;
  * with four or more devices, the train step under mp2 x sharding2 ZeRO-3
    and ``ServingEngine(mesh="mp2dp2")``, every device holding bytes.

It refuses to run without a TPU, runs in ONE process (a chip belongs to one
process at a time), stops at the first failed check, and prints as its last
line ``{"ok": true, "device": {...}}``.  Every time it prints is a fact
about this run on the named device, not a benchmark.  The legs are plain
functions; tests/test_chip_smoke.py runs them tiny on the CPU with Pallas
interpreted, and tests/test_tpu_lane.py runs the parity cases on the chip.
"""

import functools
import gc
import importlib.metadata
import json
import os
import sys
import tempfile
import time

import numpy as np


# ---------------------------------------------------------------------------
# kernel parity at engine geometry: Pallas (Mosaic) vs the repo's XLA
# references.  Each returns max |got - want| and asserts its tolerance.
# ---------------------------------------------------------------------------

# bf16 operands, f32 accumulate, bf16 result: both sides round O(1) outputs
# to 8 mantissa bits (eps 7.8e-3) and round softmax weights to bf16 at
# different points (the kernel before normalising, the reference after), so
# they may differ by a few bf16 ulps.
ATTN_TOL = 2e-2
# int8 cache: same arithmetic as above on both sides (the reference
# dequantises the same int8 payload under the same scales), so the same
# bound holds; quantisation error itself cancels.
INT8_KV_TOL = 2e-2
# int8 weights x bf16 activations, f32 accumulate over K, bf16 result of
# O(1) magnitude against an f32 composition: one bf16 rounding.
INT8_MATMUL_TOL = 2e-2
# flash backward: dq/dk/dv are sums over up to 2048 bf16-rounded products
# recomputed blockwise from the saved LSE; gradients of O(1) magnitude agree
# to a few bf16 ulps of their largest terms.
FLASH_GRAD_TOL = 5e-2


def _close(got, want, tol, what):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite kernel output")
    err = float(np.abs(got - want).max())
    if not np.allclose(got, want, rtol=tol, atol=tol):
        raise AssertionError(f"{what}: max |err| {err:.4g} outside "
                             f"rtol=atol={tol}")
    return err


def _decode_inputs(b, s, hq, hkv, d, kv_len, seed, dtype):
    """q, contiguous K/V, per-row positions at mixed depths (one row at the
    last slot the window allows, so the final KV chunk is live)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, kv_len, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, kv_len, hkv, d)), dtype)
    top = kv_len - s
    pos = jnp.asarray([top - (i * top) // max(b, 2) for i in range(b)],
                      jnp.int32)
    return q, k, v, pos


def _scatter_to_pool(k, v, block_len, seed, layers=2):
    """Contiguous (B, L, Hkv, D) rows -> the LAST layer of a stacked pool
    (layers, 2, B*L/bl + 1, bl, Hkv*D) in the layout the engine stores,
    the rows' blocks at PERMUTED physical ids (block 0 stays the null
    block; the other layers stay zero), and the (B, L/bl) table that finds
    them."""
    import jax.numpy as jnp
    b, kv_len, hkv, d = k.shape
    nb = kv_len // block_len
    perm = np.random.default_rng(seed).permutation(b * nb) + 1
    tables = perm.reshape(b, nb).astype(np.int32)
    order = np.argsort(perm)                  # physical id -> logical block

    def blocks(x):
        x = x.reshape(b * nb, block_len, hkv * d)
        return jnp.concatenate([jnp.zeros_like(x[:1]), x[order]])

    layer = jnp.stack([blocks(k), blocks(v)])
    pool = jnp.concatenate(
        [jnp.zeros((layers - 1,) + layer.shape, layer.dtype), layer[None]])
    return pool, layers - 1, jnp.asarray(tables)


def parity_decode(*, paged=False, s=1, b=8, hq=32, hkv=8, d=128,
                  kv_len=8192, block_len=128, dtype="bfloat16",
                  interpret=False, window=None):
    """Flash-decode kernel vs ``cached_decode_attention_reference``:
    contiguous or paged (the stacked pool handed over whole), s=1 (steady
    decode), s=5 (spec-verify window, k+1) or s=256 (a q-tiled prefill
    chunk); with ``window`` the sliding-window form of both (rows deeper
    than the window start their block walk behind it)."""
    from paddle_tpu.ops.attention import cached_decode_attention_reference
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_pallas, paged_decode_attention_pallas)

    q, k, v, pos = _decode_inputs(b, s, hq, hkv, d, kv_len, 40 + s, dtype)
    win = {} if window is None else {"window": window}
    want = cached_decode_attention_reference(q, k, v, pos, **win)
    if paged:
        pool, layer, tables = _scatter_to_pool(k, v, block_len, 7)
        got = paged_decode_attention_pallas(q, pool, layer, pos, tables,
                                            interpret=interpret, **win)
    else:
        got = decode_attention_pallas(q, k, v, pos, interpret=interpret,
                                      **win)
    return _close(got, want, ATTN_TOL,
                  f"decode {'paged' if paged else 'contiguous'} s={s}"
                  f"{'' if window is None else f' window={window}'}")


def parity_moe_experts(*, rows=768, experts=32, k=3072, n=3072,
                       dtype="bfloat16", interpret=False):
    """The held experts' grouped matrix product (Pallas) vs
    ``jax.lax.ragged_dot`` over the rows that belong to a group: uneven
    groups, two experts that no pair chose, and a tail of rows routed to
    experts held elsewhere (a sixth of the rows), which are multiplied with
    nothing."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.moe import _grouped_matmul_fn

    rng = np.random.default_rng(5)
    sizes = rng.multinomial(rows - rows // 6, np.ones(experts) / experts)
    sizes[[1, experts - 2]] = 0
    held = int(sizes.sum())
    xs = jnp.asarray(rng.normal(size=(rows, k)), dtype)
    w = jnp.asarray(rng.normal(size=(experts, k, n)) * k ** -0.5, dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    got = _grouped_matmul_fn(rows, k, n, pallas=True)(xs, w, gs)
    want = jax.lax.ragged_dot(xs, w, gs,
                              preferred_element_type=jnp.float32)
    del interpret       # the dispatcher reads FLAGS_pallas_interpret / CPU
    return _close(got[:held], want[:held], ATTN_TOL,
                  f"moe_experts rows={rows} experts={experts}")


def parity_decode_int8_paged(*, b=8, hq=32, hkv=8, d=128, kv_len=8192,
                             block_len=128, dtype="bfloat16",
                             interpret=False):
    """int8-KV flash-decode (scales in SMEM) vs the XLA gather + dequant
    reference over the SAME int8 pool and scales."""
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import paged_decode_attention_reference
    from paddle_tpu.ops.pallas.decode_attention import \
        paged_decode_attention_pallas

    q, k, v, pos = _decode_inputs(b, 1, hq, hkv, d, kv_len, 60, "float32")
    q = q.astype(dtype)
    pool, layer, tables = _scatter_to_pool(k, v, block_len, 9)
    # per-block-per-kv-head absmax / 127, heads apart for the reduction
    heads = pool.reshape(pool.shape[:4] + (hkv, d))
    sc = jnp.maximum(jnp.max(jnp.abs(heads), axis=(3, 5)) / 127.0, 1e-8)
    pool8 = jnp.clip(jnp.round(heads / sc[:, :, :, None, :, None]),
                     -127, 127).astype(jnp.int8).reshape(pool.shape)
    sc = sc.astype(jnp.float32)
    want = paged_decode_attention_reference(q, pool8, layer, pos, tables,
                                            pool_scale=sc)
    got = paged_decode_attention_pallas(q, pool8, layer, pos, tables,
                                        pool_scale=sc, interpret=interpret)
    return _close(got, want, INT8_KV_TOL, "decode int8-KV paged s=1")


def parity_int8_matmul(*, rows=8, k=4096, n=14336, dtype="bfloat16",
                       interpret=False):
    """Weight-only int8 matmul kernel vs the f32 ``x @ (w8 * scale)``
    composition."""
    import jax.numpy as jnp
    from paddle_tpu.nn.quant import weight_quantize
    from paddle_tpu.ops.pallas.int8_matmul import int8_matmul_pallas

    rng = np.random.default_rng(70)
    x = jnp.asarray(rng.normal(size=(rows, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), jnp.float32)
    w8, scale = weight_quantize(w)
    want = jnp.dot(x.astype(jnp.float32),
                   w8.astype(jnp.float32) * scale.astype(jnp.float32),
                   precision="highest")
    got = int8_matmul_pallas(x, w8, scale, interpret=interpret)
    return _close(got, want, INT8_MATMUL_TOL, f"int8_matmul {rows}x{k}x{n}")


def parity_flash(*, b=1, s=2048, hq=32, hkv=8, d=128, dtype="bfloat16",
                 interpret=False):
    """Flash attention forward + backward (causal, GQA) vs
    ``flash_attention_reference``."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import flash_attention_reference
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

    rng = np.random.default_rng(80)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    w = jnp.asarray(rng.normal(size=(b, s, hq, d)), dtype)

    def loss(attn):
        def f(q, k, v):
            out = attn(q, k, v)[0]
            return jnp.sum((out * w).astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out), grads = loss(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, interpret=interpret))(q, k, v)
    (_, ref), ref_grads = loss(lambda q, k, v: flash_attention_reference(
        q, k, v, causal=True, return_lse=True))(q, k, v)
    err = _close(out, ref, ATTN_TOL, f"flash fwd s={s}")
    for g, r, name in zip(grads, ref_grads, "qkv"):
        err = max(err, _close(g, r, FLASH_GRAD_TOL, f"flash d{name} s={s}"))
    return err


PARITY_CASES = {
    "decode_contiguous_s1": parity_decode,
    "decode_paged_s1": functools.partial(parity_decode, paged=True),
    "decode_paged_chunk256": functools.partial(parity_decode, paged=True,
                                               s=256, b=1),
    "decode_paged_spec_window5": functools.partial(parity_decode, paged=True,
                                                   s=5),
    "decode_paged_int8_kv": parity_decode_int8_paged,
    # the AFMoE cell's geometry: 48 q / 8 kv heads, depth 6k, window 4096
    "decode_paged_window_s1": functools.partial(
        parity_decode, paged=True, hq=48, kv_len=6144, window=4096),
    "decode_paged_window_chunk256": functools.partial(
        parity_decode, paged=True, s=256, b=1, hq=48, kv_len=6144,
        window=4096),
    "moe_experts_gmm": parity_moe_experts,
    "int8_matmul": parity_int8_matmul,
    "flash_fwd_bwd": parity_flash,
}


# ---------------------------------------------------------------------------
# the two main paths
# ---------------------------------------------------------------------------

FALLBACK_PATHS = ("xla_math", "xla_reference", "xla_dequant")
# rms_norm counts xla_reference by design: its Pallas route is disabled by
# default (XLA won at every measured shape, BENCH_OPS.json; ROADMAP D7)
XLA_BY_DESIGN = ("rms_norm",)


def kernel_paths():
    """``ops.kernel_path`` as {"op/path[/cache]": count} — counted at trace
    time, so it reads "compiled programs that took this path"."""
    from paddle_tpu import observability as obs
    fam = obs.snapshot().get("ops.kernel_path", {"series": []})
    out = {}
    for row in fam["series"]:
        lab = row["labels"]
        key = "/".join(lab[k] for k in ("op", "path", "cache") if k in lab)
        out[key] = out.get(key, 0) + int(row["value"])
    return out


class CompileLog:
    """Wall seconds jax spent producing each executable (compile, or load
    from the persistent cache), by jitted-function name."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name="?", **_):
        if event == self.EVENT:
            self.rows.append((str(fun_name), float(secs)))

    def drain(self, floor=1.0):
        """{name: seconds} for programs at or over ``floor`` seconds, plus
        the total — and forget them."""
        rows, self.rows = self.rows, []
        out = {}
        for name, secs in rows:
            if secs >= floor:
                out[name] = round(out.get(name, 0.0) + secs, 1)
        out["total"] = round(sum(s for _, s in rows), 1)
        return out


def build_model(config):
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    pt.seed(0)
    return LlamaForCausalLM(config)


def build_afmoe_model(config):
    """An AFMoE model built to be loaded (no initializer runs), then given
    seeded weights one array at a time."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import nn
    from paddle_tpu.models import AfmoeForCausalLM
    with nn.abstract_parameters():
        model = AfmoeForCausalLM(config)
    key = jax.random.key(0)
    for i, (_, p) in enumerate(model.named_parameters()):
        if not isinstance(p.value, jax.ShapeDtypeStruct):
            continue
        z = jax.random.normal(jax.random.fold_in(key, i), p.shape,
                              jnp.float32)
        p.value = ((1.0 + 0.1 * z) if len(p.shape) == 1 else 0.02 * z
                   ).astype(p.value.dtype)
    return model


def smoke_prompts(vocab, lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, n).astype(np.int32) for n in lengths]


def serve_leg(model, prompts, new_tokens, *, expect_paths=(),
              forbid_fallbacks=True, **engine_kw):
    """Serve ``prompts`` twice through ONE engine built with
    ``engine_kw``: the first round pays every compile, the second is warm.
    Checks: every request retires with ``new_tokens`` ids inside the
    vocabulary, both rounds; one trace of the step program; every key of
    ``expect_paths`` counted in ``ops.kernel_path`` and (unless
    ``forbid_fallbacks`` is off) no XLA-fallback path counted."""
    import jax

    from paddle_tpu import observability as obs
    from paddle_tpu.serving import ServingEngine

    model.eval()
    obs.reset()
    vocab = model.config.vocab_size
    eng = ServingEngine(model, **engine_kw)
    rounds = []
    for _ in range(2):
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        tick_s = []
        while any(len(eng.result(r)) < new_tokens for r in rids):
            t1 = time.perf_counter()
            eng.step()
            tick_s.append(time.perf_counter() - t1)
            if len(tick_s) > 64 * (len(prompts) + new_tokens):
                raise AssertionError(f"engine did not retire {rids}")
        out = dict(eng.drain())
        wall = time.perf_counter() - t0
        toks = [out[r] for r in rids]
        for r, t in zip(rids, toks):
            if len(t) != new_tokens:
                raise AssertionError(
                    f"request {r}: {len(t)} tokens, asked {new_tokens}")
            if min(t) < 0 or max(t) >= vocab:
                raise AssertionError(f"request {r}: id outside [0, {vocab})")
        rounds.append({"wall_s": round(wall, 3), "tick_s": tick_s,
                       "tokens": toks})
    if eng.step_traces != 1:
        raise AssertionError(f"step_traces {eng.step_traces} != 1")
    paths = kernel_paths()
    for want in expect_paths:
        if not paths.get(want):
            raise AssertionError(f"ops.kernel_path has no {want!r}: {paths}")
    if forbid_fallbacks:
        fell = {k: n for k, n in paths.items()
                if k.split("/")[1] in FALLBACK_PATHS
                and k.split("/")[0] not in XLA_BY_DESIGN}
        if fell:
            raise AssertionError(f"a kernel gave way to XLA: {fell}")
    cold, warm = rounds
    devices = (list(eng.mesh.devices.flat) if eng.mesh is not None
               else jax.devices()[:1])
    return {"requests": len(prompts), "new_tokens": new_tokens,
            "bytes_in_use": bytes_in_use(devices),
            "step_traces": eng.step_traces,
            "prefill_traces": eng.prefill_traces,
            "cold_wall_s": cold["wall_s"], "warm_wall_s": warm["wall_s"],
            "ticks": len(warm["tick_s"]),
            # step() wall, host included; the median tick is a pure decode
            # tick, the slowest carries the 4096-bucket prefill wave
            "warm_tick_ms": {"p50": round(1e3 * float(
                                 np.median(warm["tick_s"])), 2),
                             "max": round(1e3 * max(warm["tick_s"]), 2)},
            "rounds_agree": agreeing_share(cold["tokens"], warm["tokens"]),
            "kernel_paths": paths, "tokens": warm["tokens"]}


def paged_step_temporaries(model, **engine_kw):
    """Compile the paged decode step (``_step_impl_paged``, pool donated) of
    ``ServingEngine(model, paged=True, **engine_kw)`` at that geometry and
    read ``memory_analysis()``: the pool is stored as the flash-decode
    kernel reads it and handed over whole, so the program may hold no
    temporary the size of a layer's K and V — the check that found the
    per-layer relayout copy (ROADMAP S1).  Fails at a layer's K+V or more."""
    import jax

    from paddle_tpu.serving import ServingEngine

    model.eval()
    eng = ServingEngine(model, paged=True, **engine_kw)
    compiled = jax.jit(eng._step_fn.python_fn, donate_argnums=(1,)).lower(
        *eng._lint_args()).compile()
    temp = int(compiled.memory_analysis().temp_size_in_bytes)
    pool = eng._cache
    layer_kv = int(pool.nbytes // pool.shape[0])
    if temp >= layer_kv:
        raise AssertionError(
            f"paged decode step holds {temp} bytes of temporaries; a "
            f"layer's K+V is {layer_kv}: something copies the pool")
    return {"temp_bytes": temp, "layer_kv_bytes": layer_kv,
            "pool_shape": list(pool.shape)}


def agreeing_share(a, b):
    """Share of generated positions at which two runs of the same requests
    produced the same token, counted up to each request's first divergence
    (after it the contexts differ and nothing is comparable)."""
    same = total = 0
    for x, y in zip(a, b):
        n = next((i for i, (p, q) in enumerate(zip(x, y)) if p != q),
                 min(len(x), len(y)))
        same += n
        total += max(len(x), len(y))
    return round(same / max(1, total), 3)


def train_leg(model, *, batch, seq, steps, devices=None,
              learning_rate=1e-4, **degrees):
    """``steps`` ZeRO-3 optimizer steps of ``model`` (on the first device,
    or on ``devices`` meshed by ``degrees``) on ONE batch drawn through
    ``io.DataLoader(MMapTokenDataset)`` from a seeded token bin, repeated.
    Checks: every loss finite, the last lower than the first."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.distributed as dist
    from paddle_tpu.io import DataLoader, MMapTokenDataset
    from paddle_tpu.optimizer import AdamW

    model.train()
    hcg = dist.HybridCommunicateGroup(
        devices=list(devices if devices is not None else jax.devices()[:1]),
        **degrees)
    dist.set_hybrid_group(hcg)
    try:
        step, params, opt_state = dist.build_train_step(
            model, AdamW(learning_rate=learning_rate, weight_decay=0.01),
            hcg=hcg, zero_stage=3)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "tokens.bin")
            np.random.RandomState(0).randint(
                0, min(model.config.vocab_size, 65535),
                8 * batch * (seq + 1)).astype(np.uint16).tofile(path)
            # a loader that cannot build raises here with g++'s output
            ds = MMapTokenDataset(path, seq_len=seq + 1, stride=seq + 1)
            try:
                batches = iter(DataLoader(ds, batch_size=batch, shuffle=True,
                                          num_workers=2, prefetch_factor=1))
                ids = np.asarray(next(batches))
                batches.close()
            finally:
                ds.close()
        if ids.shape != (batch, seq + 1):
            raise AssertionError(f"loader batch shape {ids.shape}")
        fed = dist.shard_batch({"input_ids": jnp.asarray(ids[:, :-1]),
                                "labels": jnp.asarray(ids[:, 1:])}, hcg)
        key = jax.random.key(0)
        losses, walls = [], []
        for i in range(steps):
            t0 = time.perf_counter()
            loss, params, opt_state = step(params, opt_state, fed,
                                           jax.random.fold_in(key, i))
            losses.append(float(loss))          # host fetch = barrier
            walls.append(time.perf_counter() - t0)
        used = bytes_in_use(hcg.mesh.devices.flat)
    finally:
        dist.set_hybrid_group(None)
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return {"batch": batch, "seq": seq, "steps": steps,
            "mesh": {a: n for a, n in dict(hcg.mesh.shape).items() if n > 1},
            "bytes_in_use": used,
            "losses": [round(x, 4) for x in losses],
            "first_step_s": round(walls[0], 2),
            "steady_step_s": round(min(walls[1:]), 4),
            "kernel_paths": kernel_paths()}


def bytes_in_use(devices):
    """``memory_stats()["bytes_in_use"]`` per device (0 where the backend
    reports none, as the CPU's does) — read while a leg's state is live."""
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devices]


def every_device_holds_bytes(facts, n):
    used = facts["bytes_in_use"]
    if len(used) != n or not all(used):
        raise AssertionError(f"want bytes on each of {n} devices: {used}")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

# prompt lengths: prefill buckets 128 / 512 / 4096 (all flash-eligible),
# one prompt past 2048; grouped so each wave shares a bucket
PROMPT_LENGTHS = (100, 120, 90, 128, 300, 400, 500, 2500)
NEW_TOKENS = 32


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: needs a TPU; jax.devices()[0] is "
            f"{dev.platform}:{dev.device_kind}. Nothing was run.\n")
        return 2

    import jaxlib

    from paddle_tpu import flags, observability as obs
    from paddle_tpu.models import llama3_8b_config
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    tag = f"[{dev.device_kind} x{device['count']}]"

    def say(name, facts):
        print(f"{tag} {name}: {json.dumps(facts)}", flush=True)

    say("start", {"device": device, "jax": jax.__version__,
                  "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                  "compile_cache": cache_dir,
                  "cache_entries_at_start":
                      len(os.listdir(cache_dir))
                      if os.path.isdir(cache_dir) else 0})
    compiles = CompileLog()
    flags.set_flags({"retrace_watchdog": "raise",
                     "flash_attention_force": True})
    config = llama3_8b_config(num_hidden_layers=4, vocab_size=8192,
                              max_position_embeddings=8192)

    # -- trainer first: its 14 B/param of state wants the chip empty -------
    obs.reset()
    facts = train_leg(build_model(config), batch=2, seq=2048, steps=5)
    if not facts["kernel_paths"].get("flash_attention/pallas"):
        raise AssertionError(f"train step: {facts['kernel_paths']}")
    say("train zero3", dict(facts, compile_s=compiles.drain()))
    gc.collect()

    # -- kernels at engine geometry ---------------------------------------
    for name, case in PARITY_CASES.items():
        t0 = time.perf_counter()
        err = case()
        say(f"parity {name}", {"max_abs_err": round(err, 5),
                               "wall_s": round(time.perf_counter() - t0, 1)})
    compiles.drain()
    gc.collect()

    # -- server, both layouts ----------------------------------------------
    model = build_model(config)
    prompts = smoke_prompts(config.vocab_size, PROMPT_LENGTHS)
    wave = serve_leg(
        model, prompts, NEW_TOKENS, num_slots=8, max_length=8192,
        expect_paths=("decode_attention/pallas_decode/contiguous",
                      "decode_attention_kernel/contiguous",
                      "flash_attention/pallas"))
    wave_tokens = wave.pop("tokens")
    say("serve contiguous+wave", dict(wave, compile_s=compiles.drain()))
    gc.collect()
    paged = serve_leg(
        model, prompts, NEW_TOKENS, num_slots=8, max_length=8192,
        paged=True, chunked=True,
        expect_paths=("decode_attention/pallas_decode/paged",
                      "decode_attention_kernel/paged",
                      "chunked_prefill/paged"))
    paged_tokens = paged.pop("tokens")
    say("serve paged+chunked", dict(paged, compile_s=compiles.drain()))
    say("layouts agree", {"share_of_tokens_before_first_divergence":
                          agreeing_share(wave_tokens, paged_tokens)})
    gc.collect()
    say("paged step temporaries",
        dict(paged_step_temporaries(model, num_slots=8, max_length=8192),
             compile_s=compiles.drain()))
    del model
    gc.collect()

    # -- the second architecture the engine serves: AFMoE at its published
    # widths (Trinity-Large: 3072 wide, 48 q / 8 kv heads of 128, experts
    # of 3072), one dense and two expert layers (a window layer and a
    # global one), 8 of 64 experts held; the window cut to 1024 so that the
    # 2500-token prompt is served past it
    from paddle_tpu.models import AfmoeConfig
    afmoe = build_afmoe_model(AfmoeConfig(
        vocab_size=8192, num_hidden_layers=3, num_dense_layers=1,
        layer_types=("sliding_attention", "sliding_attention",
                     "full_attention"),
        num_experts=64, ep_size=8, sliding_window=1024,
        max_position_embeddings=8192, dtype="bfloat16"))
    moe = serve_leg(
        afmoe, prompts, NEW_TOKENS, num_slots=8, max_length=8192,
        paged=True, chunked=True,
        expect_paths=("decode_attention/pallas_decode/paged",
                      "decode_attention_kernel/paged",
                      "chunked_prefill/paged", "moe_experts/pallas_gmm"))
    moe.pop("tokens")
    say("serve afmoe paged+chunked", dict(moe, compile_s=compiles.drain()))
    del afmoe
    gc.collect()

    # -- four chips: the sharded paths on real devices ---------------------
    if jax.device_count() >= 4:
        four = jax.devices()[:4]
        # off: under a mesh flash_attention takes the XLA reference by
        # design (ops/attention.py) and the force flag would make that fatal
        flags.set_flags({"flash_attention_force": False})
        obs.reset()
        facts = train_leg(build_model(config), batch=2, seq=2048, steps=5,
                          devices=four, mp_degree=2, sharding_degree=2)
        say("train mp2 x sharding2 zero3",
            dict(facts, compile_s=compiles.drain()))
        every_device_holds_bytes(facts, 4)
        gc.collect()
        mesh = serve_leg(build_model(config), prompts, NEW_TOKENS,
                         num_slots=8, max_length=8192, mesh="mp2dp2",
                         forbid_fallbacks=False)
        mesh_tokens = mesh.pop("tokens")
        say("serve mesh mp2dp2",
            dict(mesh, agrees_with_one_chip=agreeing_share(wave_tokens,
                                                           mesh_tokens),
                 compile_s=compiles.drain()))
        every_device_holds_bytes(mesh, 4)
    else:
        say("four-chip leg", {"ran": False,
                              "why": f"{jax.device_count()} device(s)"})

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
