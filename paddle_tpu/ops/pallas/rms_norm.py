"""Row-resident RMSNorm Pallas TPU kernel.

TPU-native equivalent of the reference's fused rms_norm CUDA kernel
(upstream layout: paddle/phi/kernels/fusion/gpu/fused_rms_norm*).

Why a kernel at all when XLA fuses elementwise chains: a *standalone*
rms_norm lowers in XLA to a reduce pass plus a broadcast-multiply pass —
two HBM reads of ``x`` and one write.  This kernel keeps a block of rows
resident in VMEM and does the reduction + scale in one visit: one read,
one write, ~1.5x less HBM traffic.  That only matters when the op is
HBM-bound and NOT already fused into a neighbouring matmul — i.e. long
rows at layer boundaries — which is why the dispatcher
(paddle_tpu/ops/norms.py) routes only row sizes ≥ its threshold here and
leaves everything else to XLA.

Forward only by design: under ``jax.grad`` the cotangent path falls back
to the XLA reference implementation via ``jax.custom_vjp`` so training
numerics are owned by one code path; the kernel serves inference/serving
and the forward half of training steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._dispatch import kernel_name


def _kernel(x_ref, w_ref, o_ref, *, epsilon: float):
    xf = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + epsilon)
    if w_ref is not None:
        y = y * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _kernel_nw(x_ref, o_ref, *, epsilon: float):
    _kernel(x_ref, None, o_ref, epsilon=epsilon)


def _pick_block_rows(rows: int, d: int) -> int:
    """Largest power-of-two row block that divides ``rows`` and keeps the
    block under ~2 MB fp32 — with Pallas double-buffering the in/out blocks
    plus the fp32 upcast temp, that stays well inside the 16 MB VMEM."""
    budget = max(8, (2 * 1024 * 1024) // (4 * d))
    br = 1
    while br * 2 <= min(rows, 512, budget) and rows % (br * 2) == 0:
        br *= 2
    return br


def rms_norm_pallas(x, weight=None, epsilon: float = 1e-6,
                    interpret: bool = False):
    """x: (..., D) → same shape/dtype; weight: (D,) or None.

    Raises NotImplementedError for shapes the kernel does not handle
    (caller falls back to the XLA path).
    """
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    if rows == 0 or d % 128:
        raise NotImplementedError(
            f"rms_norm kernel needs last dim % 128 == 0, got {d}")
    if rows % 8:
        raise NotImplementedError(
            f"rms_norm kernel needs row count % 8 == 0, got {rows}")
    x2 = x.reshape(rows, d)
    br = _pick_block_rows(rows, d)

    in_specs = [pl.BlockSpec((br, d), lambda i: (i, 0))]
    args = [x2]
    if weight is not None:
        if weight.shape != (d,):
            raise NotImplementedError(
                f"weight shape {weight.shape} != ({d},)")
        in_specs.append(pl.BlockSpec((1, d), lambda i: (0, 0)))
        args.append(weight.reshape(1, d))
        kern = functools.partial(_kernel, epsilon=epsilon)
    else:
        kern = functools.partial(_kernel_nw, epsilon=epsilon)

    out = pl.pallas_call(
        kern,
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
        name=kernel_name("rms_norm"),
    )(*args)
    return out.reshape(x.shape)
