"""Mixture-of-Experts with expert parallelism.

TPU-native equivalent of the reference's MoE stack (upstream layout:
python/paddle/incubate/distributed/models/moe/ — ``MoELayer``, gates in
gate/ (``GShardGate``, ``SwitchGate``, ``NaiveGate``), dispatch via the
global_scatter/global_gather alltoall ops in
paddle/fluid/operators/collective/).

Design: the GShard/Switch capacity formulation as dense einsums — the
canonical TPU MoE (GShard paper):

  * gate: softmax router; top-k choice; per-expert **capacity**
    C = ceil(capacity_factor * tokens * k / E); tokens over capacity are
    dropped (contribute zero, like the reference's drop policy);
  * dispatch: one-hot (tokens, E, C) mask → ``einsum`` gather into
    (E, C, D) expert batches; combine: weighted scatter back;
  * experts: **stacked** parameters with a leading expert dim sharded over
    the EP mesh axes (dp×sharding — the reference derives its MoE group the
    same way); XLA lowers the dispatch/combine einsums to the exact
    all_to_all pair the reference codes as global_scatter/global_gather;
  * aux losses in fp32: GShard load-balancing loss and the router z-loss.

Memory envelope of the dense dispatch: it materialises TWO fp32
``(T, E, C)`` tensors (dispatch + combine), i.e. ``2 * 4 * T * E * C``
bytes with ``C = ceil(cf * T * k / E)`` — effectively ``8 * cf * k * T²``
bytes, *quadratic in tokens* and independent of E.  Worked example:
T = 8192 tokens, E = 64 experts, k = 2, cf = 1.25 → C = 320 and the two
one-hots cost 8192·64·320·4 B × 2 ≈ **1.34 GB**, dwarfing the (T, D)
activations (8192·4096·2 B = 64 MB at D = 4096).  For long sequences use
``dispatch_mode="index"`` — the reference's global_scatter/global_gather is
index-based too: O(T·k) int32 routing metadata plus the (E, C, D) expert
batches, no (T, E, C) tensors at all.

Everything is jit-traceable — static shapes, no data-dependent control flow.

**Dropless, and told which experts it holds** (:class:`HeldExpertsMoE`,
routed by :class:`SigmoidTopKGate`): the layer expert parallelism needs on
every chip.  The router scores ALL experts; the layer holds the weights of
one contiguous range of them and computes their part of the result — one
grouped matrix product a projection over the (token, expert) pairs routed
to a held expert, sorted by expert.  There is no ``(E, capacity, …)``
buffer and no token is dropped, whatever the load; the parts of all ranks
add up to the uncut layer.  On one chip the layer runs without its
exchange.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import flags as _flags
from ..nn import functional as F
from ..tensor.math import einsum
from ..nn import initializer as I
from ..nn.layer import Layer
from ..ops import _dispatch
from .fleet.mp_layers import constrain

__all__ = ["Gate", "SwitchGate", "GShardGate", "MoELayer",
           "SigmoidTopKGate", "SoftmaxTopKGate", "HeldExpertsMoE",
           "held_experts_kernel_specs", "expert_load"]

EP_AXES = ("dp", "sharding")  # expert dim rides the combined dp×sharding axes

# Eval calls with tokens·top_k ≤ this many slots per expert get a no-drop
# capacity (see MoELayer._capacity); larger eval batches keep the
# factor-based capacity, so the decode-parity guarantee is scoped to
# decode-shaped batches.
EVAL_NO_DROP_SLOTS = 64


class Gate(Layer):
    """Router base (parity: BaseGate).  Subclasses set ``top_k``."""

    top_k = 1

    def __init__(self, hidden_size: int, num_experts: int, dtype=None):
        super().__init__()
        self.num_experts = num_experts
        self.weight = self.create_parameter(
            (hidden_size, num_experts), dtype=dtype,
            initializer=I.Normal(std=0.02), attr_name="weight")

    def logits(self, x):
        # router math in fp32 (the reference's gate casts up too)
        return (x.astype(jnp.float32) @ self.weight.astype(jnp.float32))


class SwitchGate(Gate):
    """Top-1 routing (parity: SwitchGate; Switch Transformer)."""

    top_k = 1


class GShardGate(Gate):
    """Top-2 routing (parity: GShardGate)."""

    top_k = 2


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


class MoELayer(Layer):
    """Expert-parallel MoE block (parity: MoELayer).

    ``expert_fn(params_pytree, x)`` applies ONE expert; parameters are
    created stacked (leading dim = num_experts) via ``expert_param_specs``.
    The default expert is the SwiGLU FFN (LlamaMLP shape).

    Returns ``(out, aux_loss)``; ``aux_loss`` = load-balance + z-loss,
    already scaled by their coefficients.
    """

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, gate: Optional[Gate] = None,
                 top_k: Optional[int] = None,
                 capacity_factor: float = 1.25,
                 eval_capacity_factor: Optional[float] = None,
                 aux_loss_coef: float = 0.01, z_loss_coef: float = 1e-3,
                 dispatch_mode: Optional[str] = None, dtype=None):
        super().__init__()
        if dispatch_mode not in (None, "dense", "index"):
            raise ValueError(
                f"dispatch_mode must be 'dense' or 'index', got "
                f"{dispatch_mode!r}")
        self.dispatch_mode = dispatch_mode  # None → FLAGS_moe_dispatch
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        self.gate = gate if gate is not None else GShardGate(
            hidden_size, num_experts, dtype=dtype)
        self.top_k = top_k if top_k is not None else type(self.gate).top_k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = (eval_capacity_factor
                                     if eval_capacity_factor is not None
                                     else capacity_factor)
        self.aux_loss_coef = aux_loss_coef
        self.z_loss_coef = z_loss_coef
        e = num_experts
        init = I.Normal(std=0.02)
        # stacked SwiGLU experts, expert dim on the EP axes
        self.gate_proj = self.create_parameter(
            (e, hidden_size, intermediate_size), dtype=dtype,
            initializer=init, sharding=P(EP_AXES), attr_name="gate_proj")
        self.up_proj = self.create_parameter(
            (e, hidden_size, intermediate_size), dtype=dtype,
            initializer=init, sharding=P(EP_AXES), attr_name="up_proj")
        self.down_proj = self.create_parameter(
            (e, intermediate_size, hidden_size), dtype=dtype,
            initializer=init, sharding=P(EP_AXES), attr_name="down_proj")

    # -- routing ------------------------------------------------------------

    def _capacity(self, tokens: int) -> int:
        f = (self.capacity_factor if self.training
             else self.eval_capacity_factor)
        c = max(4, int(math.ceil(tokens * self.top_k * f
                                 / self.num_experts)))
        if (not self.training
                and tokens * self.top_k <= EVAL_NO_DROP_SLOTS
                * self.num_experts):
            # Decode-shaped eval calls (T = batch at single-token steps)
            # recompute capacity from the tiny T, so capacity-based dropping
            # would differ from the prefill/full-forward routing of the same
            # tokens (round-3 advisor).  For these small shapes a no-drop
            # capacity (C >= T·k even if every token picks one expert) costs
            # almost nothing, so greedy-decode parity does not hinge on a
            # generous eval_capacity_factor.  Big eval forwards (and decode
            # batches past the EVAL_NO_DROP_SLOTS threshold) keep the
            # factor-based capacity — no-drop there would blow up the
            # (E, C, …) dispatch buffers.
            c = max(c, tokens * self.top_k)
        return c

    def _topk_choices(self, logits):
        """Shared routing core.  (T, E) logits → per-choice lists
        ``idx`` (T,) int32, ``pos`` (T,) int32 (position within the chosen
        expert's capacity buffer, first-come-first-served in token order,
        counting all k choices in priority order), ``gate`` (T,) fp32 —
        plus the capacity C and the scaled aux loss."""
        t, e = logits.shape
        c = self._capacity(t)
        probs = jax.nn.softmax(logits, axis=-1)          # (T, E) fp32

        idxs, poss, gates = [], [], []
        top1_mask = None
        prior = jnp.zeros((1, e), jnp.float32)
        remaining = probs
        for _ in range(self.top_k):
            idx = jnp.argmax(remaining, axis=-1)          # (T,)
            mask = _one_hot(idx, e)                       # (T, E)
            pos = (jnp.cumsum(mask, axis=0) - mask) + prior  # (T, E)
            prior = prior + mask.sum(0, keepdims=True)
            idxs.append(idx.astype(jnp.int32))
            poss.append(jnp.sum(pos * mask, -1).astype(jnp.int32))
            gates.append((probs * mask).sum(-1))          # (T,)
            if top1_mask is None:
                top1_mask = mask
            remaining = remaining * (1.0 - mask)

        # aux losses (fp32): GShard load-balance + z-loss
        me = probs.mean(axis=0)                            # (E,)
        ce = top1_mask.mean(axis=0)                        # top-1 fraction
        l_aux = (me * ce).sum() * e * self.aux_loss_coef
        l_z = (jax.nn.logsumexp(logits, axis=-1) ** 2).mean() \
            * self.z_loss_coef
        return c, idxs, poss, gates, l_aux + l_z

    def _route(self, logits):
        """(T, E) logits → dispatch (T, E, C), combine (T, E, C), aux."""
        t, e = logits.shape
        c, idxs, poss, gates, aux = self._topk_choices(logits)

        disp = jnp.zeros((t, e, c), jnp.float32)
        combine = jnp.zeros((t, e, c), jnp.float32)
        for k in range(self.top_k):
            keep = (poss[k] < c).astype(jnp.float32)       # under capacity
            d_k = (keep[:, None, None] * _one_hot(idxs[k], e)[:, :, None]
                   * _one_hot(poss[k], c)[:, None, :])     # (T, E, C)
            disp = disp + d_k
            combine = combine + d_k * gates[k][:, None, None]

        if self.top_k > 1:
            # normalise combine weights over the kept choices (GShard renorm)
            denom = combine.sum(axis=(1, 2), keepdims=True)
            combine = combine / jnp.maximum(denom, 1e-9)
        # top-1 keeps the raw gate probability (Switch Transformer): scaling
        # by p is what keeps the router differentiable through the task loss
        return disp, combine, aux

    # -- forward ------------------------------------------------------------

    def _expert(self, x):
        """Apply all experts: x (E, C, D) → (E, C, D)."""
        g = einsum("ecd,edf->ecf", x, self.gate_proj)
        u = einsum("ecd,edf->ecf", x, self.up_proj)
        return einsum("ecf,efd->ecd", F.swiglu(g, u), self.down_proj)

    def _forward_dense(self, xt):
        logits = self.gate.logits(xt)                      # (T, E) fp32
        disp, combine, aux = self._route(logits)
        # dispatch: (T,E,C) × (T,D) → (E,C,D); XLA emits the alltoall when
        # T is batch-sharded and E is expert-sharded
        xe = einsum("tec,td->ecd", disp.astype(xt.dtype), xt)
        xe = constrain(xe, EP_AXES, None, None)
        ye = self._expert(xe)
        ye = constrain(ye, EP_AXES, None, None)
        return einsum("tec,ecd->td", combine.astype(xt.dtype), ye), aux

    def _forward_index(self, xt):
        """Index-based dispatch (parity: the reference's global_scatter /
        global_gather, which exchange tokens by index, not by one-hot).

        Routing metadata is O(T·k) int32 — each kept (token, choice) pair
        becomes a flat slot ``expert*C + pos`` — and the expert batches are
        built with a scatter-add and read back with a gather, so nothing of
        shape (T, E, C) is ever materialised.  Numerically identical to the
        dense path (parity-tested)."""
        t, e = xt.shape[0], self.num_experts
        logits = self.gate.logits(xt)                      # (T, E) fp32
        c, idxs, poss, gates, aux = self._topk_choices(logits)

        # one scratch row past the real slots absorbs dropped tokens
        xe_pad = jnp.zeros((e * c + 1, xt.shape[-1]), xt.dtype)
        keeps = []
        for k in range(self.top_k):
            keep = poss[k] < c                             # (T,) bool
            slot = jnp.where(keep, idxs[k] * c + poss[k], e * c)
            keeps.append((keep, slot))
            xe_pad = xe_pad.at[slot].add(xt)
        ye = self._expert(constrain(xe_pad[:e * c].reshape(e, c, -1),
                                    EP_AXES, None, None))
        ye_flat = constrain(ye, EP_AXES, None, None).reshape(e * c, -1)

        out = jnp.zeros_like(xt)
        denom = jnp.zeros((t,), jnp.float32)
        for k, (keep, slot) in enumerate(keeps):
            w = gates[k] * keep                            # (T,) fp32
            out = out + (ye_flat[jnp.minimum(slot, e * c - 1)]
                         * w[:, None].astype(xt.dtype))
            denom = denom + w
        if self.top_k > 1:                                 # GShard renorm
            out = out / jnp.maximum(denom, 1e-9)[:, None].astype(xt.dtype)
        return out, aux

    def forward(self, x):
        """x: (..., D) → (out (..., D), aux_loss scalar)."""
        shape = x.shape
        xt = x.reshape(-1, shape[-1])                      # (T, D)
        mode = self.dispatch_mode or _flags.flag("moe_dispatch")
        if mode not in ("dense", "index"):
            raise ValueError(
                f"FLAGS_moe_dispatch must be 'dense' or 'index', got "
                f"{mode!r}")
        fwd = self._forward_index if mode == "index" else self._forward_dense
        out, aux = fwd(xt)
        return out.reshape(shape), aux


# ---------------------------------------------------------------------------
# dropless routed experts, one expert-parallel rank's share
# ---------------------------------------------------------------------------

class SigmoidTopKGate(Gate):
    """Sigmoid router with a selection bias (the aux-loss-free balancing
    term).  Scores are ``sigmoid(x·W)`` in float32 over ALL experts; the
    ``top_k`` experts are those of the largest ``scores + expert_bias``;
    their WEIGHTS are the unbiased scores, divided by their sum where
    ``route_norm`` (``norm_eps`` guards the division: the families differ
    in it) and scaled by ``route_scale``: the bias moves the selection and
    never the weight."""

    def __init__(self, hidden_size: int, num_experts: int, top_k: int,
                 route_scale: float = 1.0, route_norm: bool = True,
                 norm_eps: float = 1e-20, dtype=None):
        super().__init__(hidden_size, num_experts, dtype=dtype)
        self.top_k = int(top_k)
        self.route_scale = float(route_scale)
        self.route_norm = bool(route_norm)
        self.norm_eps = float(norm_eps)
        self.expert_bias = self.create_parameter(
            (num_experts,), dtype="float32", initializer=I.Constant(0.0),
            attr_name="expert_bias")

    def logits(self, x):
        # a choice near a tie flips on a rounding: every pass of the f32
        # product, where the base class leaves the backend's default
        return jnp.dot(x.astype(jnp.float32), self.weight.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)

    def route(self, x):
        """x (T, D) → (idx (T, top_k) int32 over ALL experts, weights
        (T, top_k) float32)."""
        scores = jax.nn.sigmoid(self.logits(x))                  # (T, E)
        idx = jax.lax.top_k(
            scores + self.expert_bias.astype(jnp.float32), self.top_k)[1]
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if self.route_norm:
            w = w / (w.sum(-1, keepdims=True) + self.norm_eps)
        return idx.astype(jnp.int32), w * self.route_scale


class SoftmaxTopKGate(Gate):
    """Softmax router: ``p = softmax(x·W)`` in float32 over ALL experts,
    the ``top_k`` largest, and where ``norm_topk_prob`` their weights
    divided by their sum (the chosen probabilities then add up to 1;
    without it they are the softmax's own).  No bias, no scale.  The
    logits are :class:`SigmoidTopKGate`'s (every pass of the f32 product)
    and so is ``route``'s contract."""

    def __init__(self, hidden_size: int, num_experts: int, top_k: int,
                 norm_topk_prob: bool = True, dtype=None):
        super().__init__(hidden_size, num_experts, dtype=dtype)
        self.top_k = int(top_k)
        self.norm_topk_prob = bool(norm_topk_prob)

    logits = SigmoidTopKGate.logits

    def route(self, x):
        """x (T, D) → (idx (T, top_k) int32 over ALL experts, weights
        (T, top_k) float32)."""
        w, idx = jax.lax.top_k(jax.nn.softmax(self.logits(x), axis=-1),
                               self.top_k)
        if self.norm_topk_prob:
            w = w / w.sum(-1, keepdims=True)
        return idx.astype(jnp.int32), w


_LOAD = threading.local()


@contextlib.contextmanager
def expert_load():
    """Collect, at TRACE time, the load every :class:`HeldExpertsMoE`
    called inside sees: yields a list that fills with one int32
    ``(held + 1,)`` vector a layer call — the (token, expert) pairs routed
    to each held expert, then the pairs routed to experts held elsewhere.
    It only collects: what a layer computes does not depend on it.  The
    serving step programs stack the list and return it beside the sampled
    tokens.  Thread-local, like ``ops._dispatch.program_part``."""
    prev = getattr(_LOAD, "sink", None)
    _LOAD.sink = sink = []
    try:
        yield sink
    finally:
        _LOAD.sink = prev


def grouped_kernel_takes(k: int, n: int) -> bool:
    """The shape half of the grouped product's dispatch: the kernel's weight
    tiles are lane-aligned cuts of (k, n)."""
    from ..ops.pallas.limits import LANES
    return k % LANES == 0 and n % LANES == 0


def _grouped_matmul_fn(rows: int, k: int, n: int,
                       pallas: Optional[bool] = None):
    """The grouped matrix product ``(xs (rows, K), w (E, K, N), group_sizes
    (E,)) -> (rows, N)``: rows ``[Σ sizes[:e], Σ sizes[:e+1])`` times
    ``w[e]``, rows behind the last group left undefined.  On a Pallas
    backend the grouped-matmul kernel (``ops/pallas/grouped_matmul.py``),
    which walks only the row tiles of non-empty groups, so an expert no
    pair chose costs no weight traffic; elsewhere, and for matrices that are
    not lane-aligned, ``jax.lax.ragged_dot`` (``pallas`` overrides the
    backend's choice: the parity cases).
    Counted as ``ops.kernel_path{op="moe_experts"}``."""
    if not ((_dispatch.use_pallas() if pallas is None else pallas)
            and grouped_kernel_takes(k, n)):
        _dispatch.count_kernel_path("moe_experts", "xla_reference")
        return jax.lax.ragged_dot
    from ..ops.pallas.grouped_matmul import TILE_ROWS, grouped_matmul_pallas
    _dispatch.count_kernel_path("moe_experts", "pallas_gmm")
    pad = -rows % TILE_ROWS

    def grouped(xs, w, group_sizes):
        if pad:
            xs = jnp.pad(xs, ((0, pad), (0, 0)))
        out = grouped_matmul_pallas(
            xs, w, group_sizes, interpret=_dispatch.pallas_interpret())
        return out[:rows] if pad else out
    return grouped


class HeldExpertsMoE(Layer):
    """Dropless routed experts, one expert-parallel rank's share.

    The router (its own layer) chooses over all ``num_experts``; this layer
    holds the stacked SwiGLU weights of the experts ``[held[0], held[1])``
    and, given the tokens and the router's choice, returns Σ over the
    chosen experts THAT ARE HELD of ``w_e · E_e(x)``.  What the absent
    experts would add is left out: the expert-parallel exchange (or, on one
    chip, nothing) supplies it.

    The product is grouped: the ``T·k`` (token, expert) pairs are sorted by
    held expert (pairs of absent experts sort behind every group, where no
    product is taken of them), their tokens' rows gathered once IN, and one
    grouped matmul a projection (:func:`_grouped_matmul_fn`) applies each
    expert's matrices to its own contiguous run of rows — work and weight
    traffic follow the pairs actually routed here, and no pair is ever
    dropped.  The result's rows are gathered once BACK into (choice, token)
    order and summed over the ``k`` choices in float32.  Nothing is
    scattered: the group sizes are a count over a one-hot of the pairs'
    slots, and a pair's place in the sorted order (the stable sort's
    inverse) is its group's start plus its running count within its slot —
    a TPU scatter goes an update at a time, a gather of the same rows costs
    a quarter.  Counted as ``ops.kernel_path{op="moe_combine",
    path="gather_sum"}``."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, top_k: int,
                 held: Optional[Tuple[int, int]] = None, dtype=None):
        super().__init__()
        lo, hi = (0, num_experts) if held is None else map(int, held)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(
                f"held experts [{lo}, {hi}) are no range of the router's "
                f"{num_experts}")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (lo, hi)
        n = hi - lo
        init = I.Normal(std=0.02)
        self.gate_proj = self.create_parameter(
            (n, hidden_size, intermediate_size), dtype=dtype,
            initializer=init, sharding=P(EP_AXES), attr_name="gate_proj")
        self.up_proj = self.create_parameter(
            (n, hidden_size, intermediate_size), dtype=dtype,
            initializer=init, sharding=P(EP_AXES), attr_name="up_proj")
        self.down_proj = self.create_parameter(
            (n, intermediate_size, hidden_size), dtype=dtype,
            initializer=init, sharding=P(EP_AXES), attr_name="down_proj")

    def forward(self, x, idx, w, valid=None):
        """x (..., D), the router's ``idx``/``w`` (T, top_k) over all
        experts → this share's part of the routed result (..., D).
        ``valid`` (bool, any shape that flattens to the T tokens; None:
        all) marks the REAL tokens: the rest are padding (an idle slot's
        row, a prompt chunk's tail), which is routed to no expert — it
        reads no expert's weights, its rows of the result are zero and it
        is counted nowhere."""
        shape = x.shape
        xt = x.reshape(-1, shape[-1])                            # (T, D)
        lo, hi = self.held
        t, k, n = xt.shape[0], idx.shape[1], hi - lo
        with jax.named_scope("ffn.route"):
            # global expert id -> slot in the held stack, n where absent
            slot = jnp.where((idx >= lo) & (idx < hi), idx - lo, n)  # (T, k)
            real = t
            if valid is not None:
                ok = jnp.asarray(valid).reshape(-1, 1)
                slot = jnp.where(ok, slot, n)
                real = ok.sum(dtype=jnp.int32)
            held = slot < n
            slot = slot.reshape(-1)                              # (T·k,)
            # sorted by held expert: order[i] is the pair at place i,
            # dest[p] the place of pair p = t·k + j — the stable sort's
            # inverse, by counting: the pairs of the slots before its own,
            # plus the pairs of its own slot up to itself
            order = jnp.argsort(slot, stable=True)
            onehot = slot[:, None] == jnp.arange(n + 1)          # (T·k, n+1)
            counts = onehot.sum(0, dtype=jnp.int32)
            before = jnp.cumsum(counts) - counts
            upto = jnp.cumsum(onehot, 0, dtype=jnp.int32)
            dest = jnp.where(onehot, before + upto - 1, 0).sum(1)
            group_sizes = counts[:n]
            sink = getattr(_LOAD, "sink", None)
            if sink is not None:
                # pairs a held expert, then the REAL tokens' pairs held
                # elsewhere (padding sorts to slot n too, and counts nowhere)
                sink.append(jnp.concatenate(
                    [group_sizes, (real * k - group_sizes.sum())[None]]))
        with jax.named_scope("ffn.experts"):
            d, f = self.gate_proj.shape[1:]
            into, out_of = (_grouped_matmul_fn(t * k, d, f),
                            _grouped_matmul_fn(t * k, f, d))
            _dispatch.count_kernel_path("moe_combine", "gather_sum")
            xs = xt[order // k]                                  # (T·k, D)
            g = into(xs, self.gate_proj, group_sizes)
            u = into(xs, self.up_proj, group_sizes)
            ys = out_of(F.swiglu(g, u), self.down_proj, group_sizes)
            # back in (choice, token) order — the k choices are k slabs of
            # whole (T, D) tiles, so their sum is plain adds — weighed and
            # summed.  The where stays: the rows of absent experts lie
            # behind the last group, where the product leaves whatever its
            # output buffer held (a NaN times a zero weight is a NaN)
            back = ys[dest.reshape(t, k).T.reshape(-1)].reshape(k, t, -1)
            out = jnp.where(held.T[..., None], back.astype(jnp.float32)
                            * w.T[..., None], 0.0).sum(0)
        return out.astype(x.dtype).reshape(shape)


def held_experts_kernel_specs(config, token_rows):
    """Pre-flight specs of the kernels only a :class:`HeldExpertsMoE`
    model's step programs build: the grouped products (in and out
    projection), per pass of the weights over ``token_rows`` tokens.
    ``config``: any with ``experts_held``, ``num_experts_per_tok``,
    ``hidden_size`` and ``moe_intermediate_size``."""
    from ..static_analysis import moe_experts_spec
    c = config
    lo, hi = c.experts_held
    h, fm = c.hidden_size, c.moe_intermediate_size
    return [moe_experts_spec(rows * c.num_experts_per_tok, hi - lo, k, n,
                             variant=f"tokens={rows},{k}x{n}")
            for rows in token_rows
            for k, n in sorted({(h, fm), (fm, h)})
            if grouped_kernel_takes(k, n)]
