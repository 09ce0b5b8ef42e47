"""Weight-only-int8 matmul Pallas TPU kernel — the serving bandwidth op.

TPU-native equivalent of the reference's fast-dequant weight-only GEMM
(upstream layout: paddle/phi/kernels/fusion/cutlass/ — the
weight_only_linear int8 path behind paddle.nn.quant).

Why a kernel when XLA can express ``x @ (w8.astype(bf16) * scale)``:
measured on the decode bench (BENCH_DECODE.json ``int8_decode``), XLA
hoists that dequantised weight out of the decode scan as a loop-invariant
bf16 buffer — per-step HBM traffic stays bf16 and int8 buys nothing.
Inside this kernel there is no hoistable intermediate: the int8 tile is
converted to bf16 *in VMEM* right before the MXU contraction, so HBM only
ever streams int8 bytes — half the weight traffic of a bf16 matmul, which
is the whole bill for batch≤8 decode.

Layout: ``out[B, N] = (x[B, K] @ w8[K, N]) * scale[N]`` — the
per-out-channel scale commutes with the contraction, so it is applied
ONCE to the f32 accumulator at the final K step (cheaper than scaling
tiles, and exactly equivalent for per-column scales).

Grid: (N blocks, K blocks), K minor — each out block accumulates over
the K walk in an f32 VMEM scratch that persists across the inner
dimension; Pallas double-buffers the streaming w8 tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import limits as _limits
from .._dispatch import kernel_name


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, k_steps: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 → bf16 happens HERE, in VMEM: HBM streamed only int8 bytes
    wb = w_ref[...].astype(jnp.bfloat16)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], wb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...]
                      * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _pick(dim: int, cap: int) -> int:
    b = 128
    while b * 2 <= cap and dim % (b * 2) == 0:
        b *= 2
    return b


def int8_matmul_pallas(x, w8, scale, block_k: int = 0, block_n: int = 0,
                       interpret: bool = False):
    """``(x @ w8) * scale`` with in-kernel dequant.

    x: (..., K) floating; w8: (K, N) int8; scale: (N,) — from
    nn/quant.py's ``weight_quantize``.  Returns (..., N) in x.dtype.
    Raises NotImplementedError for unsupported shapes (callers fall back
    to the XLA composition).
    """
    k, n = w8.shape
    if w8.dtype != jnp.int8:
        raise NotImplementedError(f"weight dtype {w8.dtype} != int8")
    if x.shape[-1] != k or scale.shape != (n,):
        raise ValueError(f"shape mismatch: x {x.shape}, w8 {w8.shape}, "
                         f"scale {scale.shape}")
    if k % 128 or n % 128:
        raise NotImplementedError(
            f"int8 matmul kernel needs K, N % 128 == 0, got {k}, {n}")
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    if rows == 0:
        raise NotImplementedError("empty batch")
    x2 = x.reshape(rows, k)
    # MXU sublane: pad the (tiny, serving-sized) row count up to 8
    rows_p = max(8, -(-rows // 8) * 8)
    if rows_p != rows:
        x2 = jnp.pad(x2, ((0, rows_p - rows), (0, 0)))
    if rows_p > _limits.MAX_GEMM_ROWS:
        raise NotImplementedError(
            f"decode-shaped kernel: row count {rows} > "
            f"{_limits.MAX_GEMM_ROWS} (training-size GEMMs belong to "
            f"XLA's own int8 handling)")
    bk = block_k or _pick(k, 2048)
    bn = block_n or _pick(n, 512)
    k_steps = k // bk

    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=(n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((rows_p, bk), lambda ni, ki: (0, ki)),
            pl.BlockSpec((bk, bn), lambda ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((rows_p, bn), lambda ni, ki: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((rows_p, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows_p, bn), jnp.float32)],
        interpret=interpret,
        name=kernel_name("int8_matmul"),
    )(x2, w8, scale.reshape(1, n))
    return out[:rows].reshape(x.shape[:-1] + (n,))
