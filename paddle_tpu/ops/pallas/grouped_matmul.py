"""Grouped matrix product Pallas TPU kernel — the routed experts' hot path.

``(xs (M, K), w (E, K, N), group_sizes (E,)) -> (M, N)``: rows
``[Σ sizes[:e], Σ sizes[:e+1])`` of ``xs`` times ``w[e]``.  The rows are the
(token, expert) pairs of a dropless expert layer, sorted by expert
(``distributed/moe.py`` :class:`HeldExpertsMoE`); rows behind the last
group (pairs routed to experts another chip holds) are multiplied with
nothing and their output is left as the buffer was.

The scheme is megablox's (``jax.experimental.pallas.ops.tpu.megablox``,
whose ``make_group_metadata`` this module imports): the host-side-free
"which (row tile, group) pairs hold any row" table rides in as scalar
prefetch, the grid's middle dimension walks ONLY those pairs (its bound is
the traced number of them), and the weight operand's index map picks
``w[group]`` — so an expert that no pair chose is never DMA'd, and the
call's weight traffic is the touched experts' (plus one more read for each
expert whose rows straddle a row-tile boundary).  What this module adds is
what the library call cannot take: a kernel NAME under the engine's
``program_part`` (the device trace then says which program part a grouped
product served; the name is set at the ``pallas_call``, like every
kernel's here), tiles chosen for decode-shaped row counts, and a geometry
the static kernel pre-flight can read (``static_analysis.moe_experts_spec``).

Decode-shaped means few rows an expert (2–4 at the serving cell's batch):
the product is bound by the weights' HBM stream, a (tk, tn) weight tile is
the unit of DMA, and ``tm`` only has to be large enough that the held
pairs fall into one or two row tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import limits as _limits

TILE_ROWS = 128     # tm: rows of one grid step


def pick_tiles(k: int, n: int):
    """(tk, tn) of one weight tile: the largest lane-aligned divisors of
    ``k`` and ``n`` whose bf16 tile stays within ``GMM_TILE_VALUES`` values
    (double-buffered by Pallas; see ``limits.py`` for the reading)."""
    def divisors(x):
        return [d for d in range(x, 0, -_limits.LANES)
                if x % d == 0 and d % _limits.LANES == 0] or [x]
    best = None
    for tk in divisors(k):
        for tn in divisors(n):
            if tk * tn <= _limits.GMM_TILE_VALUES and (
                    best is None or tk * tn > best[0] * best[1]):
                best = (tk, tn)
    return best or (divisors(k)[-1], divisors(n)[-1])


def _kernel(meta_ref, x_ref, w_ref, o_ref, acc, *, tm, tiles_k):
    group_offsets, group_ids, m_tile_ids = meta_ref
    gi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == tiles_k - 1)
    def _store():
        # this step's group owns rows [start, end) of the tile only: its
        # neighbours in the same row tile store theirs in their own steps
        g = group_ids[gi]
        rows = (jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
                + m_tile_ids[gi] * tm)
        mine = (rows >= group_offsets[g]) & (rows < group_offsets[g + 1])
        o_ref[...] = jnp.where(mine, acc[...],
                               o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def grouped_matmul_pallas(xs, w, group_sizes, *, tiling=None,
                          interpret: bool = False):
    """The grouped product above.  ``M`` must be a multiple of the row
    tile (``tiling[0]``, default :data:`TILE_ROWS`); ``tiling`` is
    ``(tm, tk, tn)`` with ``tk | K`` and ``tn | N``.  The result has
    ``xs``'s dtype; rows of no group hold whatever the buffer held."""
    from .. import _dispatch as _disp

    m, k = xs.shape
    e, k2, n = w.shape
    if k2 != k or group_sizes.shape != (e,):
        raise ValueError(f"grouped matmul of {xs.shape} x {w.shape} over "
                         f"{group_sizes.shape} groups")
    tm, tk, tn = tiling or (TILE_ROWS, *pick_tiles(k, n))
    if m % tm or k % tk or n % tn:
        raise NotImplementedError(
            f"grouped matmul ({m}, {k}) x ({k}, {n}) does not tile by "
            f"({tm}, {tk}, {tn})")
    return _gmm_call(xs, w, group_sizes, tiles=(tm, tk, tn),
                     interpret=interpret,
                     name=_disp.kernel_name("moe_experts"))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret", "name"))
def _gmm_call(xs, w, group_sizes, *, tiles, interpret, name):
    """The group metadata and the ``pallas_call``, jitted on their own (as
    ``decode_attention._flash_call`` is): a model's expert layers differ
    only in their weights' VALUES, so they share one trace of the kernel
    body and one lowering of it in every program that calls them.  Traced
    a call site (3 an expert layer, in each of the mixed step program's two
    passes) they were half of that program's tracing and lowering: 6.8 ->
    3.7 s for 8 of SDAR's 48 layers on a CPU host (PR 44)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata
    (m, k), (e, _, n) = xs.shape, w.shape
    tm, tk, tn = tiles
    tiles_k, tiles_n = k // tk, n // tn
    meta, num_active = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=e,
        visit_empty_groups=False)

    def x_idx(ni, gi, ki, meta_ref):
        return meta_ref[2][gi], ki

    def w_idx(ni, gi, ki, meta_ref):
        return meta_ref[1][gi], ki, ni

    def o_idx(ni, gi, ki, meta_ref):
        return meta_ref[2][gi], ni

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles_n, num_active, tiles_k),
            in_specs=[pl.BlockSpec((tm, tk), x_idx),
                      pl.BlockSpec((None, tk, tn), w_idx)],
            out_specs=pl.BlockSpec((tm, tn), o_idx),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_limits.GMM_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(meta, xs, w)
