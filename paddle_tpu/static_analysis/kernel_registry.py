"""Static kernel geometry registry — every Pallas entry point as data.

The graph-lint layer (ISSUE 6/8) stops at the jaxpr: a ``pallas_call``
is one opaque eqn, so the kernels the serving stack rides — the q-tiled
flash-decode kernel whose body walks each row's live blocks, the paged
block-table dereference, the int8 scale tables — were validated only
by running them.  This module re-expresses each kernel's GEOMETRY as a
:class:`KernelSpec`: the grid, every BlockSpec's block shape and index
map (rewritten over closed integer intervals, :class:`Iv`), the blocks a
kernel body copies by hand out of an operand left in HBM (the same
way), the scalar-prefetch operands with their DECLARED value ranges,
the VMEM scratch, and the derived tile dims.  ``kernel_rules.py`` walks a spec
WITHOUT compiling anything: VMEM footprint, index-map bounds over the
full grid domain, alignment/tiling, and the streamed-bytes model.

The builders mirror the kernels LINE FOR LINE — ``bq``/``tile_p``/
the copy group come from the kernel's own functions, the 128-lane and row-cap
gates import :mod:`paddle_tpu.ops.pallas.limits` (the same constants
the kernels and the dispatch rules read), and the block-picking helpers
(``_pick_block_kv``, ``_block_sizes``, ``_pick``, ``_pick_block_rows``)
are imported from the kernel modules themselves, so the spec cannot
drift from the kernel without a test catching it
(tests/test_kernel_preflight.py cross-checks the q-tiled paged decode
footprint against a hand-computed tile sum).

Interval soundness: every index-map operation used here (+, - const,
* positive const, // positive const, elementwise min) is monotone on
non-negative operands, so pushing interval ENDPOINTS through the map
yields exact bounds of the map's range over the domain — no widening,
no false positives on the committed kernels.
"""

from __future__ import annotations

import dataclasses
import types
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ops.pallas import limits as _limits

DTYPE_BYTES = {"float32": 4, "int32": 4, "bfloat16": 2, "float16": 2,
               "int8": 1, "bool": 1}


class KernelSpecError(ValueError):
    """A shape the registry cannot express as a KernelSpec at all —
    mirrors the kernel's own structural NotImplementedError gates (the
    dispatch-agreement sweep uses :func:`decode_kernel_rejects` to
    compare these against the dispatch decision)."""


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Iv:
    """Closed integer interval [lo, hi] — the abstract value the bounds
    checker pushes through BlockSpec index maps."""

    lo: int
    hi: int

    def __post_init__(self):
        if self.lo > self.hi:
            raise ValueError(f"empty interval [{self.lo}, {self.hi}]")

    @staticmethod
    def const(v: int) -> "Iv":
        return Iv(int(v), int(v))

    def __add__(self, o):
        o = iv(o)
        return Iv(self.lo + o.lo, self.hi + o.hi)

    __radd__ = __add__

    def __sub__(self, o: int):
        return Iv(self.lo - int(o), self.hi - int(o))

    def __mul__(self, o: int):
        if int(o) < 0:
            raise ValueError("interval * negative is not monotone")
        return Iv(self.lo * int(o), self.hi * int(o))

    __rmul__ = __mul__

    def __floordiv__(self, o: int):
        if int(o) <= 0:
            raise ValueError("interval // non-positive")
        return Iv(self.lo // int(o), self.hi // int(o))


def iv(v) -> Iv:
    return v if isinstance(v, Iv) else Iv(int(v), int(v))


def iv_min(a, b) -> Iv:
    """min is monotone in both args: [min(lo), min(hi)] is exact."""
    a, b = iv(a), iv(b)
    return Iv(min(a.lo, b.lo), min(a.hi, b.hi))


def iv_max(a, b) -> Iv:
    """max is monotone in both args, like min."""
    a, b = iv(a), iv(b)
    return Iv(max(a.lo, b.lo), max(a.hi, b.hi))


# what ``ops.pallas.decode_attention.live_block_range`` asks of its ``xp``:
# the kernel's own bounds, evaluated over intervals
_IV_OPS = types.SimpleNamespace(minimum=iv_min, maximum=iv_max,
                                zeros_like=lambda _: Iv.const(0))


# ---------------------------------------------------------------------------
# spec dataclasses
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScalarOperand:
    """A scalar-prefetch operand with its DECLARED value range —
    the bounds-domain assumption the serving engine upholds
    (BASELINE.md "Kernel pre-flight conventions"): block-table entries
    in [0, num_blocks), per-row pos in [0, max_length - s]."""

    name: str
    shape: Tuple[int, ...]
    lo: int
    hi: int
    kv_stream: bool = False   # counts toward kv_streamed_bytes (4 B each)


class ScalarEnv:
    """Interval environment over a spec's scalar operands.  ``lookup``
    records every (operand, index-interval) access so the bounds rule
    can check indices against the operand's shape; the returned
    interval is the operand's declared VALUE range (pinned per-run for
    the clamp corner checks)."""

    def __init__(self, scalars: Sequence[ScalarOperand], pins=None):
        self._sc = {s.name: s for s in scalars}
        self._pins = dict(pins or {})
        self.accesses: List[Tuple[str, Tuple[Iv, ...]]] = []

    def lookup(self, name: str, *idx) -> Iv:
        sc = self._sc[name]
        self.accesses.append((name, tuple(iv(i) for i in idx)))
        pin = self._pins.get(name)
        return iv(pin) if pin is not None else Iv(sc.lo, sc.hi)


@dataclasses.dataclass(frozen=True)
class ClampCheck:
    """Declares that an operand's dereference of ``table`` is the
    dead-tail clamp: with the row position pinned to ``p`` and the
    q-tile grid axis ``pin_axis`` pinned to ``q``, the table COLUMN it
    touches must top out at exactly ``expected(p, q)`` — the last
    live block.  Higher = unclamped (the dead tail streams, and its
    null-filled entries alias block 0 into live rows); lower =
    over-clamped (live KV silently truncated).  ``expected_first``, where
    given, holds the smallest column the same way: the first block of
    the walk (0, or a sliding window's first block) — lower reads what no
    query sees and may be another request's by now, higher truncates."""

    table: str
    pin_scalar: str
    pin_axis: int
    expected: Callable[[int, int], int]
    expected_first: Optional[Callable[[int, int], int]] = None


@dataclasses.dataclass(frozen=True)
class BlockOperand:
    """One BlockSpec'd operand (input or output) of a kernel call.

    ``index_map`` takes ``(grid_ivs, ScalarEnv)`` — the grid indices as
    intervals — and returns one interval per block dim, in BLOCK units
    (exactly what the real index map returns per grid step).
    ``streamed`` operands are DMA'd per grid step and double-buffered
    by Pallas (x2 in the VMEM model); ``fetches`` is the number of
    DISTINCT block fetches per kernel call for the streamed-bytes model
    (None = one per grid step; the dead-tail clamp's DMA elision makes
    the decode KV operands' count smaller); ``sublane_padded`` marks
    blocks the kernel explicitly pads to the sublane tile (the decode
    q tiles), exempting them from the sublane lint.  ``manual`` marks
    an operand Pallas does not pipeline at all: it stays in HBM and the
    kernel BODY copies blocks of ``block_shape`` out of it into VMEM
    scratch the spec lists under ``scratch`` (so it adds no VMEM of its
    own); its ``index_map`` returns every block index those copies take
    over one grid step's whole loop, and ``fetches`` counts them."""

    name: str
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    dtype: str
    index_map: Callable
    streamed: bool = True
    sublane_padded: bool = False
    fetches: Optional[int] = None
    kv_stream: bool = False
    clamp: Optional[ClampCheck] = None
    manual: bool = False

    def block_bytes(self) -> int:
        n = 1
        for d in self.block_shape:
            n *= int(d)
        return n * DTYPE_BYTES[self.dtype]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Everything the static analyzer needs about one kernel call."""

    op: str
    variant: str
    grid: Tuple[int, ...]
    operands: Tuple[BlockOperand, ...]
    scratch: Tuple[Tuple[Tuple[int, ...], str], ...] = ()
    scalars: Tuple[ScalarOperand, ...] = ()
    dims: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def path(self) -> str:
        return f"{self.op}[{self.variant}]"


# ---------------------------------------------------------------------------
# estimators (BASELINE.md "Kernel pre-flight conventions")
# ---------------------------------------------------------------------------

def vmem_footprint(spec: KernelSpec) -> int:
    """Per-grid-step VMEM bytes: every block-shaped operand tile
    (streamed operands x2 for Pallas's DMA double-buffering) plus the
    scratch — accumulators, which persist across the grid walk, and the
    buffers a kernel body copies its ``manual`` operands into."""
    total = 0
    for op in spec.operands:
        if not op.manual:
            total += op.block_bytes() * (2 if op.streamed else 1)
    for shape, dtype in spec.scratch:
        n = 1
        for d in shape:
            n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def _grid_size(spec: KernelSpec) -> int:
    n = 1
    for g in spec.grid:
        n *= int(g)
    return n


def streamed_bytes(spec: KernelSpec) -> int:
    """HBM bytes one kernel call moves: per operand, distinct block
    fetches x block bytes.  ``fetches`` is what the kernel really copies
    (the flash-decode body copies each q tile's live blocks and no
    other); operands without it fetch once per grid step."""
    total = 0
    grid_n = _grid_size(spec)
    for op in spec.operands:
        n = grid_n if op.fetches is None else int(op.fetches)
        total += n * op.block_bytes()
    return total


def kv_streamed_bytes(spec: KernelSpec) -> int:
    """Cache-side streamed bytes only (KV blocks + their scale tables) —
    the quantity the committed int8_serving <=0.55x claim bounds."""
    total = 0
    grid_n = _grid_size(spec)
    for op in spec.operands:
        if not op.kv_stream:
            continue
        n = grid_n if op.fetches is None else int(op.fetches)
        total += n * op.block_bytes()
    for sc in spec.scalars:     # prefetched whole, once per call
        if sc.kv_stream:
            n = 4
            for d in sc.shape:
                n *= int(d)
            total += n
    return total


# ---------------------------------------------------------------------------
# decode_attention_pallas (ops/pallas/decode_attention.py)
# ---------------------------------------------------------------------------

def decode_kernel_rejects(b: int, s: int, hq: int, hkv: int, d: int,
                          kv_len: int, *, paged_block_len=None,
                          quantized: bool = False, n_granules=None,
                          block_kv=None) -> Optional[str]:
    """Mirror of ``decode_attention_pallas``'s NotImplementedError
    gates, in declaration form: the reason the kernel would refuse this
    shape, or None.  The dispatch-agreement lint sweeps this against
    ``ops.attention.decode_shape_gate`` — both derive from
    ops/pallas/limits.py, so a drift is a lint error, not a runtime
    NotImplementedError on the serving hot path."""
    if paged_block_len is not None and paged_block_len % _limits.LANES:
        return f"paged block_len {paged_block_len} is not 128-aligned"
    if hkv == 0 or hq % hkv:
        return f"q heads ({hq}) must be a multiple of kv heads ({hkv})"
    if hq // hkv > _limits.MAX_Q_ROWS:
        return f"GQA group size {hq // hkv} > {_limits.MAX_Q_ROWS}"
    if s > _limits.MAX_Q_LEN:
        return f"q_len {s} > {_limits.MAX_Q_LEN}"
    if d > _limits.MAX_HEAD_DIM:
        return f"head_dim {d} > {_limits.MAX_HEAD_DIM}"
    if paged_block_len is None:
        if quantized:
            ng = int(n_granules or 1)
            bk = kv_len // ng
            if bk * ng != kv_len or bk % _limits.LANES:
                return (f"int8 scale granule {kv_len}/{ng} is not a "
                        f"128-aligned divisor of the cache length")
            n_cols = ng
        else:
            from ..ops.pallas.decode_attention import _pick_block_kv
            if block_kv is None:
                from .. import flags as _flags
                block_kv = int(_flags.flag("decode_attention_block_kv"))
            if not _pick_block_kv(kv_len, int(block_kv)):
                return (f"max_length {kv_len} has no 128-aligned chunk "
                        f"divisor <= {block_kv}")
    elif quantized:
        n_cols = kv_len // paged_block_len
    if quantized and b * n_cols * hkv > _limits.MAX_SCALE_TABLE:
        return (f"int8 scale table {b}x{n_cols}x{hkv} > "
                f"{_limits.MAX_SCALE_TABLE} SMEM entries")
    return None


def decode_attention_spec(b: int, s: int, hq: int, hkv: int, d: int, *,
                          kv_len: Optional[int] = None,
                          block_len: Optional[int] = None,
                          max_blocks: Optional[int] = None,
                          num_blocks: Optional[int] = None,
                          block_kv: Optional[int] = None,
                          quantized: bool = False,
                          n_granules: Optional[int] = None,
                          q_dtype: str = "bfloat16",
                          variant: Optional[str] = None,
                          num_layers: int = 1,
                          window: Optional[int] = None) -> KernelSpec:
    """KernelSpec for one ``decode_attention_pallas`` /
    ``paged_decode_attention_pallas`` call.

    Contiguous layout: pass ``kv_len`` (the cache max_length; the K/V
    operands are the identity-table view ``(b*chunks, bk, hkv*d)``).
    Paged: pass ``block_len`` + ``max_blocks`` (+ ``num_blocks``, default
    the serving engine's ``num_slots*max_blocks + 1`` null-block pool);
    the K and V operands are then THE pool
    ``(num_layers, 2, num_blocks, bk, hkv*d)`` itself, and the layer a
    scalar-prefetch value in ``[0, num_layers)``: one spec for every
    layer's call, as they share one kernel body.
    ``quantized`` adds the two f32 scale tables as scalar-prefetch
    operands; contiguous int8 pins
    the KV chunk to the scale granule (``n_granules`` — the
    init_kv_cache layout).  Alignment/granule violations are RECORDED
    in ``dims`` for the rules to flag (the kernel would raise at call
    time; the pre-flight's job is to say so beforehand) — only shapes
    with no expressible geometry raise :class:`KernelSpecError`.
    ``window`` is the sliding window of a window layer's call: the block
    walk starts at the window's first block, and the streamed-bytes
    model counts the blocks inside the window only.

    K and V are ``manual`` operands: the kernel leaves them in HBM and
    its body copies the blocks of each ``(row, q tile)``'s walk —
    ``ops.pallas.decode_attention.live_block_range``, imported here —
    into the two double-buffered group buffers listed first under
    ``scratch`` (2 buffers x G blocks, for K and for V).  The
    :class:`ClampCheck` holds both ends: at pinned ``(pos, q tile)`` the
    largest and the smallest table column the body dereferences are the
    walk's last and first.

    Mesh-sharded callers (the shard_map fast path) must pass PER-SHARD
    geometry — ``hq/mp`` and ``hkv/mp`` heads — and tag ``variant``
    with an ``mpN-shard`` suffix: under ``shard_map`` each shard runs
    its own kernel instance, so whole-model head counts would overstate
    VMEM by the mp degree (BASELINE.md "Rejection-sampling accounting
    conventions")."""
    if hkv == 0 or hq % hkv:
        raise KernelSpecError(
            f"q heads ({hq}) must be a multiple of kv heads ({hkv})")
    g = hq // hkv
    if g > _limits.MAX_Q_ROWS:
        raise KernelSpecError(f"GQA group size {g} > {_limits.MAX_Q_ROWS}")
    if s > _limits.MAX_Q_LEN:
        raise KernelSpecError(f"q_len {s} > {_limits.MAX_Q_LEN}")
    if d > _limits.MAX_HEAD_DIM:
        raise KernelSpecError(f"head_dim {d} > {_limits.MAX_HEAD_DIM}")

    paged = block_len is not None
    lanes_128 = []
    dims: Dict[str, object] = {}
    if paged:
        if max_blocks is None:
            raise KernelSpecError("paged spec needs max_blocks")
        bk = int(block_len)
        kv_len = bk * int(max_blocks)
        chunks = int(max_blocks)
        n_pool = int(num_blocks or b * max_blocks + 1)
        lanes_128.append(("block_len", bk))
        dims["block_len"] = bk
    else:
        if kv_len is None:
            raise KernelSpecError("contiguous spec needs kv_len")
        kv_len = int(kv_len)
        if quantized:
            ng = int(n_granules or 1)
            bk = max(1, kv_len // ng)
            dims["scale_granule"] = bk
            dims["scale_granules"] = ng
            lanes_128.append(("scale_granule", bk))
        else:
            from ..ops.pallas.decode_attention import _pick_block_kv
            if block_kv is None:
                from .. import flags as _flags
                block_kv = int(_flags.flag("decode_attention_block_kv"))
            bk = _pick_block_kv(kv_len, int(block_kv))
            if not bk:
                raise KernelSpecError(
                    f"max_length {kv_len} has no 128-aligned chunk "
                    f"divisor <= {block_kv}")
        chunks = max(1, kv_len // bk)
        n_pool = b * chunks

    # the kernel's own tiling arithmetic and walk bounds, imported
    from ..ops.pallas.decode_attention import (group_blocks,
                                               live_block_range, q_tiles,
                                               stored_key_bytes)
    bq, nq = q_tiles(s, g)
    tile_p = max(8, -(-(bq * g) // 8) * 8)
    kv_dtype = "int8" if quantized else q_dtype
    gb = group_blocks(bk, key_bytes=stored_key_bytes(hkv * d, 2, kv_dtype))

    pos_hi = max(0, kv_len - s)
    scalars = (
        ScalarOperand("pos", (b,), 0, pos_hi),
        # every entry a valid pool index; columns outside a row's walk
        # are null-filled (block 0) — live rows must never dereference
        # them
        ScalarOperand("bt", (b, chunks), 0, max(0, n_pool - 1)),
        # which layer of the pool this call reads (0 for the contiguous
        # cache, whose operands are one layer's already)
        ScalarOperand("layer", (1,), 0, int(num_layers) - 1 if paged else 0),
    )
    if quantized:
        # the f32 scale tables, gathered per row by the wrapper, ride in
        # SMEM beside the block table (their VALUES never feed an index,
        # so the declared range is vacuous)
        scalars += (ScalarOperand("k_scale", (b * chunks * hkv,), 0, 0,
                                  kv_stream=True),
                    ScalarOperand("v_scale", (b * chunks * hkv,), 0, 0,
                                  kv_stream=True))

    def expected_walk(p: int, q: int):
        # the kernel's own bounds at a pinned (pos, q tile): the first and
        # last table column holding a key some query of the tile may see
        first, last = live_block_range(
            np.int64(p), np.int64(q), s=s, bq=bq, bk=bk, n_cols=chunks,
            window=window, xp=np)
        return int(first), int(last)

    def q_idx(grid_ivs, sc):
        bi, qi = grid_ivs
        return (bi, Iv.const(0), qi, Iv.const(0))

    def walk_block(grid_ivs, sc):
        # every block id the body's copies take at grid step (bi, qi): the
        # body reads ``bt[bi, min(first + j·gb + i, last)]`` for group j
        # of its loop and block i of the group — k = j·gb + i runs from 0
        # to at most one group past the table's width, and the min keeps
        # the column at or under ``last``.  (The copies a step issues for
        # the NEXT step are that step's first group: the same set.)
        bi, qi = grid_ivs
        first, last = live_block_range(
            sc.lookup("pos", bi), qi, s=s, bq=bq, bk=bk, n_cols=chunks,
            window=window, xp=_IV_OPS)
        col = iv_min(first + Iv(0, chunks + gb - 2), last)
        return sc.lookup("bt", bi, col)

    if paged:
        # the kernel's operand is the stacked pool: (layer, K|V, block)
        kv_block = (1, 1, 1, bk, hkv * d)
        kv_array = (int(num_layers), 2, n_pool, bk, hkv * d)

        def kv_idx(which):
            return lambda grid_ivs, sc: (
                sc.lookup("layer", 0), Iv.const(which),
                walk_block(grid_ivs, sc), Iv.const(0), Iv.const(0))
    else:
        kv_block = (1, bk, hkv * d)
        kv_array = (n_pool, bk, hkv * d)

        def kv_idx(which):
            return lambda grid_ivs, sc: (
                walk_block(grid_ivs, sc), Iv.const(0), Iv.const(0))

    clamp = ClampCheck(table="bt", pin_scalar="pos", pin_axis=1,
                       expected=lambda p, q: expected_walk(p, q)[1],
                       expected_first=lambda p, q: expected_walk(p, q)[0])
    # streamed-bytes model: per (bi, qi) the body copies the blocks of
    # the tile's walk and nothing else (a group's columns past ``last``
    # are not copied); the worst case (pos at its declared max) is the
    # committed per-step bound
    kv_fetches = b * sum(last - first + 1 for first, last in
                         (expected_walk(pos_hi, q) for q in range(nq)))
    q_fetches = b * nq

    q_block = (1, hkv, tile_p, d)
    q_array = (b, hkv, nq * tile_p, d)
    operands = [
        BlockOperand("q", q_block, q_array, q_dtype, q_idx,
                     sublane_padded=True, fetches=q_fetches),
        BlockOperand("k", kv_block, kv_array, kv_dtype, kv_idx(0),
                     fetches=kv_fetches, kv_stream=True, clamp=clamp,
                     manual=True),
        BlockOperand("v", kv_block, kv_array, kv_dtype, kv_idx(1),
                     fetches=kv_fetches, kv_stream=True, clamp=clamp,
                     manual=True),
    ]
    operands.append(
        BlockOperand("out", q_block, q_array, q_dtype, q_idx,
                     sublane_padded=True, fetches=q_fetches))

    # two buffers of one group of K and of V blocks, then the accumulators
    scratch = (((2, gb * bk, hkv * d), kv_dtype),
               ((2, gb * bk, hkv * d), kv_dtype),
               ((hkv, tile_p, d), "float32"),
               ((hkv, tile_p, _limits.LANES), "float32"),
               ((hkv, tile_p, _limits.LANES), "float32"))

    dims.update({
        "b": b, "s": s, "g": g, "hkv": hkv, "d": d, "bq": bq, "nq": nq,
        "tile_p": tile_p, "bk": bk, "gb": gb, "chunks": chunks,
        "kv_len": kv_len, "paged": paged, "quantized": quantized,
        "window": window, "lane_slice": (d, hkv),
        "lanes_128": tuple(lanes_128),
    })
    spec = KernelSpec(
        op="decode_attention", grid=(b, nq),
        variant=variant or (f"{'paged' if paged else 'contiguous'}"
                            f"{'+int8' if quantized else ''},s={s}"),
        operands=tuple(operands), scratch=scratch, scalars=scalars,
        dims=dims)
    # the quantized variants' streamed-bytes claim rides the bf16 twin:
    # same fetch pattern, bf16 payload, no scale rows
    kvb = kv_streamed_bytes(spec)
    bf16 = kv_fetches * 2 * bk * hkv * d * DTYPE_BYTES["bfloat16"]
    dims["kv_streamed_bytes"] = kvb
    dims["kv_streamed_bytes_bf16_equiv"] = bf16
    return spec


# ---------------------------------------------------------------------------
# flash_attention forward (ops/pallas/flash_attention.py)
# ---------------------------------------------------------------------------

def flash_attention_spec(b: int, hq: int, hkv: int, sq: int, skv: int,
                         d: int, *, dtype: str = "bfloat16",
                         variant: Optional[str] = None) -> KernelSpec:
    """KernelSpec for the flash-attention forward kernel (the prefill
    path): grid ``(b, hq, sq//bq, skv//bk)``, GQA folded into the K/V
    index maps (``h // g`` — grouped KV is never broadcast in HBM)."""
    if hkv == 0 or hq % hkv:
        raise KernelSpecError(
            f"q heads ({hq}) must be a multiple of kv heads ({hkv})")
    g = hq // hkv
    from ..ops.pallas.flash_attention import _block_sizes
    bq, bk = _block_sizes(sq, skv, d)
    if sq % bq or skv % bk:
        raise KernelSpecError(
            f"flash kernel needs seq divisible by block ({sq}%{bq}, "
            f"{skv}%{bk})")

    def q_idx(grid_ivs, sc):
        b_, h, qi, ki = grid_ivs
        return (b_, h, qi, Iv.const(0))

    def kv_idx(grid_ivs, sc):
        b_, h, qi, ki = grid_ivs
        return (b_, h // g, ki, Iv.const(0))

    operands = (
        BlockOperand("q", (1, 1, bq, d), (b, hq, sq, d), dtype, q_idx),
        BlockOperand("k", (1, 1, bk, d), (b, hkv, skv, d), dtype, kv_idx,
                     kv_stream=True),
        BlockOperand("v", (1, 1, bk, d), (b, hkv, skv, d), dtype, kv_idx,
                     kv_stream=True),
        BlockOperand("out", (1, 1, bq, d), (b, hq, sq, d), dtype, q_idx),
        BlockOperand("lse", (1, 1, bq, _limits.LANES),
                     (b, hq, sq, _limits.LANES), "float32", q_idx),
    )
    scratch = (((bq, d), "float32"),
               ((bq, _limits.LANES), "float32"),
               ((bq, _limits.LANES), "float32"))
    dims = {"b": b, "g": g, "hkv": hkv, "d": d, "bq": bq, "bk": bk,
            "lanes_128": (("block_kv", bk),),
            "sublanes_8": (("block_q", bq),)}
    return KernelSpec(
        op="flash_attention", variant=variant or f"fwd,sq={sq},skv={skv}",
        grid=(b, hq, sq // bq, skv // bk), operands=operands,
        scratch=scratch, dims=dims)


# ---------------------------------------------------------------------------
# int8_matmul (ops/pallas/int8_matmul.py)
# ---------------------------------------------------------------------------

def int8_matmul_spec(rows: int, k: int, n: int, *,
                     x_dtype: str = "bfloat16",
                     block_k: Optional[int] = None,
                     block_n: Optional[int] = None,
                     variant: Optional[str] = None) -> KernelSpec:
    """KernelSpec for the weight-only-int8 GEMM: grid (N blocks,
    K blocks) with the f32 accumulator persisting over the K walk."""
    rows_p = max(8, -(-rows // 8) * 8)
    if rows_p > _limits.MAX_GEMM_ROWS:
        raise KernelSpecError(
            f"decode-shaped kernel: row count {rows} > "
            f"{_limits.MAX_GEMM_ROWS}")
    from ..ops.pallas.int8_matmul import _pick
    bk = int(block_k or _pick(k, 2048))
    bn = int(block_n or _pick(n, 512))

    def x_idx(grid_ivs, sc):
        ni, ki = grid_ivs
        return (Iv.const(0), ki)

    def w_idx(grid_ivs, sc):
        ni, ki = grid_ivs
        return (ki, ni)

    def n_idx(grid_ivs, sc):
        ni, ki = grid_ivs
        return (Iv.const(0), ni)

    operands = (
        BlockOperand("x", (rows_p, bk), (rows_p, k), x_dtype, x_idx),
        BlockOperand("w8", (bk, bn), (k, n), "int8", w_idx),
        BlockOperand("scale", (1, bn), (1, n), "float32", n_idx),
        BlockOperand("out", (rows_p, bn), (rows_p, n), x_dtype, n_idx),
    )
    dims = {"rows": rows, "rows_p": rows_p, "k": k, "n": n,
            "bk": bk, "bn": bn, "lanes_128": (("K", k), ("N", n))}
    return KernelSpec(
        op="int8_matmul", variant=variant or f"rows={rows},k={k},n={n}",
        grid=(max(1, n // bn), max(1, k // bk)), operands=operands,
        scratch=(((rows_p, bn), "float32"),), dims=dims)


# ---------------------------------------------------------------------------
# grouped_matmul_pallas (ops/pallas/grouped_matmul.py)
# ---------------------------------------------------------------------------

def moe_experts_spec(rows: int, experts: int, k: int, n: int, *,
                     dtype: str = "bfloat16",
                     variant: Optional[str] = None) -> KernelSpec:
    """KernelSpec for one grouped matrix product of the held experts:
    ``rows`` (token, expert) pairs sorted by expert against ``experts``
    stacked ``(k, n)`` matrices.  The grid's middle dimension walks the
    (row tile, group) pairs that hold a row — at most ``row tiles +
    experts - 1`` of them — through two scalar-prefetch tables, so the
    weight operand's worst case is every expert read once and one more
    read per row-tile boundary; the tiling is the kernel's own."""
    from ..ops.pallas.grouped_matmul import TILE_ROWS, pick_tiles
    tm = TILE_ROWS
    tk, tn = pick_tiles(k, n)
    rows_p = -(-rows // tm) * tm
    tiles_m = rows_p // tm
    steps = tiles_m + experts - 1
    scalars = (ScalarOperand("group_ids", (steps,), 0, experts - 1),
               ScalarOperand("m_tile_ids", (steps,), 0, tiles_m - 1))

    def x_idx(grid_ivs, sc):
        ni, gi, ki = grid_ivs
        return (sc.lookup("m_tile_ids", gi), ki)

    def w_idx(grid_ivs, sc):
        ni, gi, ki = grid_ivs
        return (sc.lookup("group_ids", gi), ki, ni)

    def o_idx(grid_ivs, sc):
        ni, gi, ki = grid_ivs
        return (sc.lookup("m_tile_ids", gi), ni)

    tiles = (k // tk) * (n // tn)
    operands = (
        BlockOperand("x", (tm, tk), (rows_p, k), dtype, x_idx,
                     fetches=steps * tiles),
        BlockOperand("w", (1, tk, tn), (experts, k, n), dtype, w_idx,
                     fetches=steps * tiles),
        BlockOperand("out", (tm, tn), (rows_p, n), dtype, o_idx,
                     fetches=steps * (n // tn)),
    )
    dims = {"rows": rows, "rows_p": rows_p, "experts": experts, "k": k,
            "n": n, "tm": tm, "tk": tk, "tn": tn,
            "lanes_128": (("K", k), ("N", n), ("tk", tk), ("tn", tn))}
    return KernelSpec(
        op="moe_experts", grid=(n // tn, steps, k // tk),
        variant=variant or f"rows={rows},experts={experts},k={k},n={n}",
        operands=operands, scratch=(((tm, tn), "float32"),),
        scalars=scalars, dims=dims)


# ---------------------------------------------------------------------------
# rms_norm (ops/pallas/rms_norm.py)
# ---------------------------------------------------------------------------

def rms_norm_spec(rows: int, d: int, *, dtype: str = "bfloat16",
                  weight: bool = True,
                  variant: Optional[str] = None) -> KernelSpec:
    """KernelSpec for the row-resident RMSNorm kernel: 1-D grid over
    row blocks; the weight row's constant index map means Pallas elides
    its re-fetch after the first step (fetches=1)."""
    from ..ops.pallas.rms_norm import _pick_block_rows
    br = _pick_block_rows(rows, d)

    def x_idx(grid_ivs, sc):
        (i,) = grid_ivs
        return (i, Iv.const(0))

    def w_idx(grid_ivs, sc):
        return (Iv.const(0), Iv.const(0))

    operands = [
        BlockOperand("x", (br, d), (rows, d), dtype, x_idx),
        BlockOperand("out", (br, d), (rows, d), dtype, x_idx),
    ]
    if weight:
        operands.insert(
            1, BlockOperand("weight", (1, d), (1, d), dtype, w_idx,
                            fetches=1))
    dims = {"rows": rows, "d": d, "br": br, "lanes_128": (("d", d),)}
    return KernelSpec(
        op="rms_norm", variant=variant or f"rows={rows},d={d}",
        grid=(max(1, rows // br),), operands=tuple(operands), dims=dims)


# ---------------------------------------------------------------------------
# the gated delta rule's kernels (ops/pallas/gated_delta.py)
# ---------------------------------------------------------------------------

def gated_delta_specs(layers: int, heads: int, dk: int, dv: int, *,
                      rows: int, chunk: int) -> List[KernelSpec]:
    """KernelSpecs of the two kernels over the serving leaf ``(layers, rows
    + 1, d_k, H·d_v)`` float32.  The STEP: one grid step a decode row, the
    row's whole ``S`` in and out (aliased), the row picked through the
    ``order`` table of the live rows first — a step past them keeps the
    last block, so the worst case is every row fetched once.  The CHUNK:
    grid (lane groups, sub-chunks), a group's block of ``S`` resident while
    its sub-chunks' stacked operands stream by."""
    from ..ops.gated_delta import sub_chunk
    from ..ops.pallas.gated_delta import lane_group
    hv, hg = heads * dv, lane_group(heads, dv)
    groups, gw, c = heads // hg, hg * dv, sub_chunk(chunk)
    nc = -(-chunk // c)
    leaf = (layers, rows + 1, dk, hv)
    f32 = "float32"

    def step_leaf(grid_ivs, sc):
        (r,) = grid_ivs
        return (Iv.const(layers - 1),
                sc.lookup("first") + sc.lookup("order", r),
                Iv.const(0), Iv.const(0))

    def step_row(grid_ivs, sc):
        (r,) = grid_ivs
        return (sc.lookup("order", r), Iv.const(0), Iv.const(0))

    step = KernelSpec(
        op="gated_delta_step", variant=f"rows={rows},H={heads},{dk}x{dv}",
        grid=(rows,),
        operands=(
            BlockOperand("S", (1, 1, dk, hv), leaf, f32, step_leaf),
            BlockOperand("q", (1, dk, heads), (rows, dk, heads), f32,
                         step_row, sublane_padded=True),
            BlockOperand("k", (1, dk, heads), (rows, dk, heads), f32,
                         step_row, sublane_padded=True),
            BlockOperand("v_alpha_beta", (1, 3, hv), (rows, 3, hv), f32,
                         step_row, sublane_padded=True),
            BlockOperand("o", (1, 1, hv), (rows, 1, hv), f32, step_row,
                         sublane_padded=True),
            BlockOperand("S_out", (1, 1, dk, hv), leaf, f32, step_leaf)),
        scalars=(ScalarOperand("order", (rows,), 0, rows - 1),
                 ScalarOperand("first", (), 0, 1)),
        dims={"rows": rows, "heads": heads, "dk": dk, "dv": dv,
              "lanes_128": (("H·dv", hv), ("group", gw))})

    def chunk_leaf(grid_ivs, sc):
        p, n = grid_ivs
        return (Iv.const(layers - 1), sc.lookup("row"), Iv.const(0), p)

    def head_rows(grid_ivs, sc):
        p, n = grid_ivs
        return (n, p, Iv.const(0), Iv.const(0))

    def lane_rows(grid_ivs, sc):
        p, n = grid_ivs
        return (n, Iv.const(0), p)

    def stacked(name, r, cols):
        return BlockOperand(name, (1, 1, hg * r, cols),
                            (nc, groups, hg * r, cols), f32, head_rows,
                            sublane_padded=True)
    walk = KernelSpec(
        op="gated_delta_chunk",
        variant=f"chunk={chunk},c={c},H={heads},{dk}x{dv}",
        grid=(groups, nc),
        operands=(
            BlockOperand("S", (1, 1, dk, gw), leaf, f32, chunk_leaf,
                         fetches=groups),
            stacked("w", c, dk), stacked("qd", c, dk), stacked("p", c, c),
            stacked("kdT", dk, c),
            BlockOperand("u", (1, c, gw), (nc, c, hv), f32, lane_rows),
            BlockOperand("d", (1, 1, gw), (nc, 1, hv), f32, lane_rows,
                         sublane_padded=True),
            BlockOperand("o", (1, c, gw), (nc, c, hv), f32, lane_rows),
            BlockOperand("S_out", (1, 1, dk, gw), leaf, f32, chunk_leaf,
                         fetches=groups)),
        scalars=(ScalarOperand("row", (), 0, rows),),
        dims={"chunk": chunk, "c": c, "heads": heads, "dk": dk, "dv": dv,
              "lanes_128": (("group", gw),)})
    return [step, walk]


# ---------------------------------------------------------------------------
# the registry sweep
# ---------------------------------------------------------------------------

def registered_kernel_specs() -> List[KernelSpec]:
    """One representative TPU-scale spec per registered kernel entry
    point — the shapes the committed benches measured (serving head
    geometry 32/8/128, kv_len 8192, 128-token paged blocks).  The CLI's
    ``--kernels`` sweep and the guard test require every one of these
    to pre-flight clean."""
    out = [
        decode_attention_spec(8, 1, 32, 8, 128, kv_len=8192,
                              variant="contiguous,decode"),
        decode_attention_spec(8, 1, 32, 8, 128, kv_len=8192,
                              quantized=True, n_granules=8192 // 128,
                              variant="contiguous+int8,decode"),
        decode_attention_spec(8, 1, 32, 8, 128, block_len=128,
                              max_blocks=64, num_layers=32,
                              variant="paged,decode"),
        decode_attention_spec(8, 1, 32, 8, 128, block_len=128,
                              max_blocks=64, num_layers=32, quantized=True,
                              variant="paged+int8,decode"),
        # the q-tiled modes: a chunked-prefill q chunk and the
        # speculative verify window, through the same kernel
        decode_attention_spec(1, 256, 32, 8, 128, block_len=128,
                              max_blocks=64,
                              variant="paged,chunked_prefill"),
        decode_attention_spec(8, 5, 32, 8, 128, block_len=128,
                              max_blocks=64, quantized=True,
                              variant="paged+int8,spec_verify"),
        # a window layer's calls: the block walk clamped from below too
        decode_attention_spec(8, 1, 48, 8, 128, block_len=128,
                              max_blocks=64, num_layers=5, window=4096,
                              variant="paged,decode,window"),
        decode_attention_spec(1, 256, 48, 8, 128, block_len=128,
                              max_blocks=64, num_layers=5, window=4096,
                              variant="paged,chunked_prefill,window"),
        # the held experts' grouped product over the pairs of a mixed
        # step's one pass: (decode rows + chunk positions) x top-k
        moe_experts_spec((192 + 256) * 4, 32, 3072, 3072),
        moe_experts_spec((320 + 256) * 4, 16, 2048, 1792),
        flash_attention_spec(1, 32, 8, 2048, 2048, 128),
        int8_matmul_spec(8, 4096, 4096),
        rms_norm_spec(256, 4096),
    ]
    return out
