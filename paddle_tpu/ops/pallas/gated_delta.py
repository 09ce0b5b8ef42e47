"""The gated delta rule's two Pallas TPU kernels (``ops/gated_delta.py`` has
the rule, the oracle and the XLA twins).  Both take the serving leaf
``(layers, rows, d_k, H · d_v)`` float32 WHOLE and alias it to their output
(``input_output_aliases``): a call reads and writes the blocks of the rows
it advances, once, and the rest of the leaf stays where it is — no copy of
a state that is gigabytes.

**Heads lie side by side on the lane axis**, ``d_v`` lanes each, so a head
of 192 starts in the middle of a lane tile.  Both kernels therefore work on
LANE GROUPS of ``hg`` heads (the fewest whose lanes are whole tiles: 2 at
``d_v`` 192, 384 lanes) and never slice inside a tile: what is per head (a
column of k, a stack of W) is laid over the group's lanes by a select on
the lane's head.

**The step kernel** (one token a row; the serving engine's decode rows):
grid = the part's rows.  The rows that hold a real token are visited first,
in order (``order``, ``n`` ride in as scalar prefetch and pick the leaf's
block); the steps left over keep the last visited block's index, so nothing
is copied for them and they compute nothing: the traffic follows the
occupied rows and not the slots.  A step reads the row's ``S`` (d_k, H ·
d_v), and for each lane group: decays it, takes ``S̃ᵀk`` and ``Sᵀq`` as
sublane reductions on the VPU (one token a row leaves the MXU nothing to
do), adds the rank-1 update, writes it back.  With no real row at all the
first step copies its block through, which is what the aliased output then
holds.

**The chunk kernel** (one row of T positions): grid = (lane groups,
sub-chunks), the group's block of ``S`` resident in the output block while
its sub-chunks are walked.  What does not read ``S`` was computed over all
sub-chunks at once (``gated_delta.chunk_operands``); here the operands of
a group's heads come STACKED on rows (``w``, ``qd``, ``p``: hg · c rows;
``kdᵀ``: hg · d_k rows), one MXU product each against the group's lanes,
and the select keeps each head's rows on its own lanes.  float32 products
at ``HIGHEST`` precision: a single bfloat16 pass would round ``S`` as a
bfloat16 state does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._dispatch import kernel_name
from ..gated_delta import chunk_operands, sub_chunk
from . import limits as _limits

__all__ = ["gated_delta_step_pallas", "gated_delta_chunk_pallas",
           "lane_group", "VMEM_LIMIT"]

# the step kernel holds a row's S twice over (in and out), each twice
# (Pallas's double buffering): 4 x 2.2 MB at the published widths
VMEM_LIMIT = 32 * 1024 * 1024
_HI = jax.lax.Precision.HIGHEST


def lane_group(heads: int, dv: int) -> int:
    """The fewest heads whose ``d_v`` lanes are whole lane tiles; raises
    NotImplementedError where ``heads`` does not split into such groups."""
    hg = next((m for m in range(1, heads + 1)
               if (m * dv) % _limits.LANES == 0), None)
    if hg is None or heads % hg:
        raise NotImplementedError(
            f"gated delta kernels: {heads} heads of d_v {dv} do not form "
            f"groups of whole lane tiles")
    return hg


def _check(leaf, q, v):
    """(d_v, heads a lane group) of a call the kernels can lay out; raises
    NotImplementedError otherwise (the caller falls back to the XLA twin)."""
    h, dk = q.shape[2:]
    if leaf.ndim != 4 or leaf.dtype != jnp.float32 or leaf.shape[2] != dk \
            or leaf.shape[3] != h * v.shape[3]:
        raise NotImplementedError(
            f"gated delta kernels: the leaf is (layers, rows, d_k, H·d_v) "
            f"float32, got {leaf.shape} {leaf.dtype} for q {q.shape}, v "
            f"{v.shape}")
    if dk % 8:
        raise NotImplementedError(
            f"gated delta kernels: d_k {dk} is no whole sublane tiles")
    return v.shape[3], lane_group(h, v.shape[3])


def _on_own_lanes(stacked, rows: int, head_of_lane):
    """``stacked`` (hg · rows, lanes): head j's ``rows`` rows kept on head
    j's lanes → (rows, lanes)."""
    out = stacked[:rows]
    for j in range(1, stacked.shape[0] // rows):
        out = jnp.where(head_of_lane == j,
                        stacked[j * rows:(j + 1) * rows], out)
    return out


# -- the step ----------------------------------------------------------------

def _step_kernel(meta_ref, order_ref, fresh_ref, s_ref, q_ref, k_ref,
                 vab_ref, o_ref, s_out_ref, *, hg: int, dv: int, groups: int):
    r = pl.program_id(0)
    n = meta_ref[0]
    dk = s_ref.shape[2]
    gw = hg * dv

    @pl.when(r < n)
    def _advance():
        fresh = fresh_ref[order_ref[r]] != 0
        head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (dk, gw), 1) // dv

        def over_lanes(ref, p):
            # column p·hg + j of ref (d_k, H), on head j's lanes of the group
            cols = [jnp.broadcast_to(ref[0, :, p * hg + j:p * hg + j + 1],
                                     (dk, gw)) for j in range(hg)]
            out = cols[0]
            for j in range(1, hg):
                out = jnp.where(head_of_lane == j, cols[j], out)
            return out
        for p in range(groups):
            lanes = slice(p * gw, (p + 1) * gw)
            s = s_ref[0, 0, :, lanes]
            s = jnp.where(fresh, 0.0, s)
            kx, qx = over_lanes(k_ref, p), over_lanes(q_ref, p)
            v = vab_ref[0, 0:1, lanes]
            alpha = vab_ref[0, 1:2, lanes]
            beta = vab_ref[0, 2:3, lanes]
            s = s * alpha
            res = (v - jnp.sum(s * kx, axis=0, keepdims=True)) * beta
            s = s + kx * res
            s_out_ref[0, 0, :, lanes] = s
            o_ref[0, 0:1, lanes] = jnp.sum(s * qx, axis=0, keepdims=True)

    @pl.when((n == 0) & (r == 0))
    def _through():
        s_out_ref[...] = s_ref[...]


def gated_delta_step_pallas(leaf, layer, first, q, k, v, g, beta, live,
                            fresh, *, interpret: bool = False):
    """:func:`_step_call` under the kernel's name of the moment
    (``ops._dispatch.kernel_name``: the engine's program part)."""
    i32 = jnp.int32
    return _step_call(leaf, jnp.asarray(layer, i32), jnp.asarray(first, i32),
                      q, k, v, g, beta, live, fresh, interpret=interpret,
                      name=kernel_name("gated_delta_step"))


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _step_call(leaf, layer, first, q, k, v, g, beta, live, fresh, *,
               interpret, name):
    """Jitted on its own: a model's layers differ only in the VALUE of the
    layer scalar, so they share one trace of the kernel body and one
    lowering of it in every program that calls them.

    The step over the B rows ``leaf[layer, first : first + B]``: q, k
    (B, 1, H, d_k), v (B, 1, H, d_v), g, β (B, 1, H) float32 with invalid
    tokens identity steps; ``live`` bool (B,): the rows with a real token,
    the only ones visited; ``fresh`` bool (B,): start from zeros.  Returns
    (o (B, 1, H, d_v), the leaf); a row not visited has junk in o."""
    b, s, h, dk = q.shape
    if s != 1:
        raise NotImplementedError(f"the step kernel takes one position a "
                                  f"row, got {s}")
    dv, hg = _check(leaf, q, v)
    hv = h * dv
    i32 = jnp.int32
    # the live rows first, in order; then the rest
    order = jnp.argsort(~live, stable=True).astype(i32)
    n = live.sum(dtype=i32)
    meta = jnp.stack([n, first, layer])

    def lanes(x):                                   # (B, H) → (B, H·d_v)
        return jnp.repeat(x, dv, axis=-1)
    vab = jnp.stack([v[:, 0].reshape(b, hv), lanes(jnp.exp(g[:, 0])),
                     lanes(beta[:, 0])], axis=1)               # (B, 3, HV)
    qt = jnp.swapaxes(q[:, 0], 1, 2)                            # (B, dk, H)
    kt = jnp.swapaxes(k[:, 0], 1, 2)

    def row(r, meta_ref, order_ref):
        # a step past the live rows keeps the last live row's blocks
        return order_ref[jnp.maximum(jnp.minimum(r, meta_ref[0] - 1), 0)]

    def leaf_idx(r, meta_ref, order_ref, fresh_ref):
        return (meta_ref[2], meta_ref[1] + row(r, meta_ref, order_ref), 0, 0)

    def row_idx(r, meta_ref, order_ref, fresh_ref):
        return (row(r, meta_ref, order_ref), 0, 0)

    o, leaf = pl.pallas_call(
        functools.partial(_step_kernel, hg=hg, dv=dv, groups=h // hg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, 1, dk, hv), leaf_idx),
                      pl.BlockSpec((1, dk, h), row_idx),
                      pl.BlockSpec((1, dk, h), row_idx),
                      pl.BlockSpec((1, 3, hv), row_idx)],
            out_specs=[pl.BlockSpec((1, 1, hv), row_idx),
                       pl.BlockSpec((1, 1, dk, hv), leaf_idx)]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, hv), jnp.float32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(meta, order, fresh.astype(i32), leaf, qt, kt, vab)
    return o.reshape(b, 1, h, dv), leaf


# -- the chunk ---------------------------------------------------------------

def _chunk_kernel(meta_ref, s_ref, w_ref, qd_ref, p_ref, kdt_ref, u_ref,
                  d_ref, o_ref, s_out_ref, *, hg: int, dv: int, c: int):
    n = pl.program_id(1)
    dk = s_ref.shape[2]
    gw = hg * dv

    @pl.when(n == 0)
    def _load():
        s_out_ref[0, 0] = jnp.where(meta_ref[1] != 0, 0.0, s_ref[0, 0])
    s = s_out_ref[0, 0]
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)

    def own(stacked, rows):
        head_of_lane = jax.lax.broadcasted_iota(
            jnp.int32, (rows, gw), 1) // dv
        return _on_own_lanes(stacked, rows, head_of_lane)
    vp = u_ref[0] - own(dot(w_ref[0, 0], s), c)
    o_ref[0] = own(dot(qd_ref[0, 0], s), c) + own(dot(p_ref[0, 0], vp), c)
    s_out_ref[0, 0] = s * d_ref[0] + own(dot(kdt_ref[0, 0], vp), dk)


def gated_delta_chunk_pallas(leaf, layer, first, q, k, v, g, beta, live,
                             fresh, *, interpret: bool = False):
    """:func:`_chunk_call` under the kernel's name of the moment."""
    i32 = jnp.int32
    return _chunk_call(leaf, jnp.asarray(layer, i32),
                       jnp.asarray(first, i32), q, k, v, g, beta, live,
                       fresh, interpret=interpret,
                       name=kernel_name("gated_delta_chunk"))


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _chunk_call(leaf, layer, first, q, k, v, g, beta, live, fresh, *,
                interpret, name):
    """Jitted on its own, as :func:`_step_call` is, the operands that do
    not read the state (``chunk_operands``) with it.

    The chunked form over the ONE row ``leaf[layer, first]``: q, k (1,
    T, H, d_k), v (1, T, H, d_v), g, β (1, T, H) float32 with invalid
    tokens identity steps; ``fresh`` bool (1,): start from zeros.  Returns
    (o (1, T, H, d_v), the leaf)."""
    b, t, h, dk = q.shape
    if b != 1:
        raise NotImplementedError(f"the chunk kernel walks one row, got {b}")
    dv, hg = _check(leaf, q, v)
    groups, gw, hv = h // hg, hg * dv, h * dv
    c = sub_chunk(t)
    ops = chunk_operands(q[0], k[0], v[0], g[0], beta[0], c)
    nc = ops["d"].shape[0]

    def stacked(x):
        # (N, H, rows, cols) → (N, groups, hg · rows, cols)
        return x.reshape(nc, groups, hg * x.shape[2], x.shape[3])

    def on_lanes(x):
        # (N, H, c, d_v) → (N, c, H · d_v)
        return jnp.moveaxis(x, 1, 2).reshape(nc, x.shape[2], hv)
    kdt = stacked(jnp.swapaxes(ops["kd"], 2, 3))        # rows: hg · d_k
    d = jnp.repeat(ops["d"], dv, axis=-1)[:, None]      # (N, 1, H · d_v)
    meta = jnp.stack([first, fresh[0].astype(jnp.int32), layer])

    def leaf_idx(p, n, meta_ref):
        return (meta_ref[2], meta_ref[0], 0, p)

    def head_rows(p, n, meta_ref):
        return (n, p, 0, 0)

    def lane_rows(p, n, meta_ref):
        return (n, 0, p)

    o, leaf = pl.pallas_call(
        functools.partial(_chunk_kernel, hg=hg, dv=dv, c=c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, nc),
            in_specs=[pl.BlockSpec((1, 1, dk, gw), leaf_idx),
                      pl.BlockSpec((1, 1, hg * c, dk), head_rows),
                      pl.BlockSpec((1, 1, hg * c, dk), head_rows),
                      pl.BlockSpec((1, 1, hg * c, c), head_rows),
                      pl.BlockSpec((1, 1, hg * dk, c), head_rows),
                      pl.BlockSpec((1, c, gw), lane_rows),
                      pl.BlockSpec((1, 1, gw), lane_rows)],
            out_specs=[pl.BlockSpec((1, c, gw), lane_rows),
                       pl.BlockSpec((1, 1, dk, gw), leaf_idx)]),
        out_shape=[jax.ShapeDtypeStruct((nc, c, hv), jnp.float32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(meta, leaf, stacked(ops["w"]), stacked(ops["qd"]), stacked(ops["p"]),
      kdt, on_lanes(ops["u"]), d)
    return o.reshape(1, nc * c, h, dv)[:, :t], leaf
