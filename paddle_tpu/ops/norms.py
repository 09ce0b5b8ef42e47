"""RMSNorm — XLA reference implementation + long-row Pallas TPU kernel.

Equivalent of the reference's fused rms_norm CUDA kernel
(upstream layout: paddle/phi/kernels/fusion/gpu/fused_rms_norm* /
paddle.incubate.nn.functional.fused_rms_norm).  Inside a transformer block
XLA fuses the norm into its matmul neighbours and there is nothing to win;
the Pallas kernel (pallas/rms_norm.py) targeted the *standalone long-row*
case.  Gradients always take the XLA reference path (one owner for
training numerics); the kernel covers forward/inference.

Measurement history — an honesty correction (round 4): the round-3
docstring claimed up to 1.73x over XLA from a per-call timing loop.  The
checked-in harness (``python bench.py --op rms_norm`` → BENCH_OPS.json)
re-measured with dispatch latency excluded (in-graph chained
iterations, two-point differencing — see bench._time_compiled) and found
**XLA as fast or faster at every shape** (Pallas at 0.46–0.73x on the
shapes too large for VMEM residency effects).  The 1.73x was dispatch
latency, not kernel time.  Accordingly ``FLAGS_rms_norm_pallas_min_dim``
now defaults to disabled; the kernel remains as an opt-in reference and
the Mosaic testbed the TPU lane exercises (tests/test_tpu_lane.py pins
its numerics on-chip at an explicit threshold).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import flags
from . import _dispatch


def rms_norm_reference(x, weight=None, epsilon: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(ms + epsilon)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_pallas_diffable(x, weight, epsilon, interpret):
    from .pallas.rms_norm import rms_norm_pallas
    return rms_norm_pallas(x, weight, epsilon, interpret=interpret)


def _rms_fwd(x, weight, epsilon, interpret):
    return _rms_pallas_diffable(x, weight, epsilon, interpret), (x, weight)


def _rms_bwd(epsilon, interpret, res, g):
    x, weight = res
    if weight is None:
        _, vjp = jax.vjp(lambda x_: rms_norm_reference(x_, None, epsilon), x)
        return vjp(g) + (None,)
    _, vjp = jax.vjp(
        lambda x_, w_: rms_norm_reference(x_, w_, epsilon), x, weight)
    return vjp(g)


_rms_pallas_diffable.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x, weight=None, epsilon: float = 1e-6):
    """Public entry (parity: fused_rms_norm).  Routes long rows to the
    Pallas kernel on TPU; everything else to the XLA reference.  Every
    routing decision is counted into ``ops.kernel_path{op="rms_norm"}``
    at trace time, like the attention/matmul dispatchers."""
    if (_dispatch.use_pallas()
            and x.shape[-1] >= flags.flag("rms_norm_pallas_min_dim")):
        try:
            out = _rms_pallas_diffable(x, weight, epsilon,
                                       _dispatch.pallas_interpret())
            _dispatch.count_kernel_path("rms_norm", "pallas")
            return out
        except NotImplementedError:
            pass
    _dispatch.count_kernel_path("rms_norm", "xla_reference")
    return rms_norm_reference(x, weight, epsilon)
