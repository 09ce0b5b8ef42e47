"""Paged KV cache — block allocator + prefix cache for the serving engine.

The vLLM PagedAttention memory model, TPU-shaped: instead of one
contiguous ``(max_length, Hkv, D)`` cache row per slot (capacity paid at
worst-case length, identical system prompts stored once per request), the
device cache is ONE pooled array ``(L, 2, num_blocks, block_len, Hkv·D)``
of fixed-size KV blocks, and each slot owns a *block table* — the ordered
list of physical block ids that back its logical token positions.  Cache
cost becomes ``live tokens + shared prefixes`` instead of
``num_slots × max_length``.  A block is stored as the flash-decode
kernel reads it — ``block_len`` rows of all kv heads' features side by
side, one contiguous DMA — so the step programs hand the kernel the pool
itself and never slice or re-lay-out a layer of it; code that needs the
heads apart (per-head int8 scales, the XLA gather) reshapes the few
blocks it touched.

Division of labour:

  * **this module is pure host-side bookkeeping** — a free-list allocator,
    per-slot block chains, refcounts, a prefix trie, an eviction LRU, and
    the numpy block-table rows the engine uploads each tick.  Nothing here
    touches the device; the pool array itself is created by
    :func:`init_paged_kv_cache` and carried through the engine's jitted
    step exactly like the contiguous cache (the block table rides along as
    a tiny traced ``(num_slots, max_blocks)`` int32 input, so allocation
    changes never retrace);
  * the device-side dereference lives in the attention paths: the Pallas
    flash-decode kernel takes the whole pool, and the table as a second
    scalar-prefetch operand, and its KV-chunk index maps look layer, K/V
    and physical block up *before* each grid step
    (ops/pallas/decode_attention.py), and the XLA math path gathers
    ``pool[layer, k|v, block_table]`` into the contiguous layout
    (ops/attention.py).  Writes are batched scatters of ``Hkv·D`` rows to
    ``(physical_block, offset)`` pairs (models/llama.py ``decode``).

Conventions the device side relies on:

  * **block 0 is the null block** — never allocated to a request.  Block
    tables are zero-filled beyond a slot's allocated chain, so every table
    entry is always a valid physical index: reads of the dead tail land in
    scratch (and are masked by position anyway), and writes from prompt
    padding are steered to the null block instead of needing a dropped
    scatter.  Its contents are junk by design;
  * a slot's table covers positions ``[0, len(chain) · block_len)``; the
    engine guarantees the block holding position ``pos + s - 1`` is
    allocated before any step that reads or writes it (``ensure_capacity``
    runs on the host before dispatch);
  * full *prompt* blocks are immutable once written (generation appends at
    positions ≥ prompt length, which live in later blocks) — that is what
    makes them safely shareable and trie-cacheable without copies.

Prefix cache: full prompt blocks are registered in a chain-keyed trie
(``(parent_block_id, block tokens) -> block_id``, the vLLM hash-chain
scheme with exact keys instead of hashes).  A later request whose prompt
starts with the same token blocks *adopts* the existing chain — refcount
bump, zero recompute, zero new HBM — and its prefill runs only the
suffix.  Matching is capped at ``(plen - 1) // block_len`` blocks so at
least one real token always remains to produce the first logits.  Retired
chains whose blocks are trie-registered are kept (refcount 0) on an LRU
list and revived on later hits; allocation under pressure evicts the LRU
head, cascading the trie unregistration through its descendants so a
reused block id can never satisfy a stale lookup.

Copy-on-write: ``ensure_writable`` is the guard a writer calls before
mutating a block mid-chain — if the block is shared (refcount > 1) it is
swapped for a fresh private copy and the (src, dst) pair is returned so
the caller can issue the device copy.  In the current engine flow full
blocks are immutable and tail blocks are private, so this never fires;
it is the hook forking features (beam/speculative decode, n>1 sampling)
build on, and it is unit-tested at this layer.

Rollback: ``truncate_to`` is the inverse of ``ensure_capacity`` — the
speculative-decode engine writes a draft window ahead of the committed
position and, when verification rejects a suffix, rolls the chain back so
blocks that only held rejected tokens return to the pool (reservation
re-credited, shared blocks deref'd not freed, and every trie registration
at or past the cut cascade-invalidated so a stale block can never serve a
prefix hit afterwards).

Admission is reservation-based so mid-flight allocation cannot fail: a
request is admitted only if ``free + evictable - already-reserved`` covers
every block it could ever need (prompt + max_new_tokens, minus the shared
prefix); the reservation is consumed block-by-block as the sequence
deepens and released with the slot.  There is no fragmentation (any free
block serves any slot), so the check is exact.

Tiering (ISSUE 16): ``host_blocks > 0`` arms a second, host-RAM tier — a
:class:`HostTier` pool of pinned host buffers the same block geometry as
the device pool.  Two flows feed it, both pure host-side bookkeeping plus
one device copy the engine performs through the ``on_swap_out`` /
``on_swap_in`` hooks (exactly the ``on_demote`` pattern the mixed-mode
int8 demotion already uses):

  * **demote-on-evict**: when pool pressure would DROP the LRU head's
    content, the block instead demotes HBM→host — its payload moves to a
    host buffer and its full TOKEN PATH (the tuple of per-block token
    tuples from the prompt root) keys a host-side trie.  A later
    admission whose prompt walk runs off the end of the device trie
    continues into the host trie and PROMOTES each hit: a fresh device
    block is allocated from the request's reservation, the payload is
    copied back, and the block re-registers in the device trie — so the
    prefix cache's effective capacity is host-RAM-sized, not HBM-sized.
    Token paths key the host trie (not parent block ids) because the
    physical parent id dies at demotion; a path is in AT MOST ONE tier
    at a time, and unreachable host entries (an ancestor dropped from
    both tries) are cascade-freed exactly like the device trie's;
  * **swap-out** (preemption): :meth:`swap_out` tears down a victim
    slot's allocation — private blocks (refcount 1) move payload+dtype
    to PINNED host buffers recorded in a resume record, shared blocks
    keep this slot's reference so the chain survives other owners'
    releases — and :meth:`resume_swapped` rebuilds the chain later.
    Record entries are keyed by host id, never by token path: a swapped
    chain can NEVER serve a prefix hit until promoted back.  Pinned
    buffers are not evictable; demoted trie entries are (LRU), so swap
    capacity always wins over cached-prefix capacity.
"""

from __future__ import annotations

import itertools
from collections import OrderedDict, deque
from collections.abc import Mapping
from typing import Deque, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from .. import observability as _obs

__all__ = ["BlockManager", "HostTier", "NULL_BLOCK", "init_paged_kv_cache"]

NULL_BLOCK = 0          # physical block 0: pad/dummy scratch, never allocated
_ROOT = -1              # trie parent id of a prompt's first block

# pool instances share the default registry; the ``pool`` label keeps
# their series independent
_POOL_IDS = itertools.count()


class _StatsView(Mapping):
    """The historical ``BlockManager.stats`` dict, now a live read-through
    over the shared metrics registry — same keys, same int values, so
    ``m.stats["evictions"]`` keeps working while the counters flow into
    ``observability.snapshot()`` / Prometheus exposition like everything
    else."""

    _KEYS = ("prefix_lookups", "prefix_hit_blocks", "prefix_hit_tokens",
             "evictions", "cow_copies", "peak_blocks_in_use",
             "quantized_blocks", "host_demotions", "host_promotions",
             "swapped_out_blocks", "swapped_in_blocks",
             "exported_blocks", "imported_blocks")

    def __init__(self, mgr: "BlockManager"):
        self._mgr = mgr

    def __getitem__(self, key: str) -> int:
        if key == "peak_blocks_in_use":
            return self._mgr._peak
        if key == "quantized_blocks":
            return self._mgr.quantized_blocks()
        return int(self._mgr._counters[key].value())

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def __repr__(self):
        return repr(dict(self))


def init_paged_kv_cache(config, num_blocks: int, block_len: int, dtype=None,
                        quantized: bool = False,
                        num_layers: Optional[int] = None, entry=None):
    """Pooled paged cache: (L, 2, num_blocks, block_len, kv_heads·head_dim)
    — the contiguous cache's (B, max_len) plane re-cut into fixed blocks,
    each block in the layout the flash-decode kernel DMAs (heads fused
    into the last axis, head-major).  The block axis is axis 2.  ``L`` is
    ``num_layers``, the layers that hold K/V (None: every layer).

    ``quantized``: the int8 pool — a two-leaf pytree
    ``{"kv": int8 (L, 2, nb, bl, Hkv·D), "scale": f32 (L, 2, nb, Hkv)}``
    where ``scale[l, kv, b, h]`` is physical block ``b``'s
    per-kv-head symmetric dequant factor (absmax/127, running-max across
    scatter-time writes).  Zero scale == empty block (dequantizes to 0).
    The pytree threads through the engine's jitted step exactly like the
    plain array (same argnum, donated wholesale).

    ``entry`` (a model's declared ``models.parts.PoolEntry``):
    the pool holds ``entry.arrays`` arrays a layer of ``entry.width`` lanes
    a position instead — a latent model's ONE entry that is key and value
    at once, ``(L, 1, num_blocks, block_len, width)``.  Blocks, tables, the
    trie and copy-on-write address axis 2 and know no layout.
    """
    import jax.numpy as jnp

    shape = (config.num_hidden_layers if num_layers is None
             else int(num_layers),
             *((2, num_blocks, block_len,
                config.num_key_value_heads * config.head_dim)
               if entry is None else
               (int(entry.arrays), num_blocks, block_len, int(entry.width))))
    if quantized and entry is not None:
        raise NotImplementedError(
            "init_paged_kv_cache: a declared pool entry has no int8 form")
    if quantized:
        return {
            "kv": jnp.zeros(shape, jnp.int8),
            "scale": jnp.zeros(shape[:3] + (config.num_key_value_heads,),
                               jnp.float32),
        }
    return jnp.zeros(shape, dtype if dtype is not None else config.dtype)


class _SlotAlloc:
    __slots__ = ("chain", "reserved_left")

    def __init__(self, chain: List[int], reserved_left: int):
        self.chain = chain
        self.reserved_left = reserved_left


# a block's full token path from the prompt root: one tuple of tokens
# per block, root first — the tier-stable identity of its contents
_Path = Tuple[Tuple[int, ...], ...]


class HostTier:
    """Pinned host-RAM block pool — the HBM pool's second tier.

    Capacity is counted in blocks of the SAME geometry as the device
    pool; each live host id owns one block-shaped payload (a host numpy
    pytree the engine reads off / writes back to the device through the
    manager's ``on_swap_out`` / ``on_swap_in`` hooks).  The tier itself
    is a dumb id allocator + payload store: WHICH ids are evictable
    (demoted prefix-trie blocks) versus pinned (preemption swap records)
    is the :class:`BlockManager`'s call — it only ever reclaims trie
    ids, so this class never evicts on its own and ``alloc()`` on a full
    tier is a caller bug."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ids = itertools.count()
        self._live: Set[int] = set()
        self._payload: Dict[int, object] = {}

    @property
    def used(self) -> int:
        return len(self._live)

    def free_slots(self) -> int:
        return self.capacity - len(self._live)

    def alloc(self) -> int:
        if len(self._live) >= self.capacity:
            raise RuntimeError(
                "host tier full (BlockManager must make room before "
                "allocating)")
        hid = next(self._ids)
        self._live.add(hid)
        return hid

    def put(self, hid: int, payload) -> None:
        if hid not in self._live:
            raise KeyError(f"host id {hid} is not allocated")
        self._payload[hid] = payload

    def get(self, hid: int):
        return self._payload[hid]

    def free(self, hid: int) -> None:
        self._live.remove(hid)
        self._payload.pop(hid, None)


class BlockManager:
    """Host-side allocator for a pool of ``num_blocks`` KV blocks of
    ``block_len`` tokens (block 0 reserved as the null block).

    ``stats`` counters: ``prefix_lookups`` (admissions that consulted the
    trie), ``prefix_hit_blocks`` / ``prefix_hit_tokens`` (blocks/tokens
    adopted instead of recomputed), ``evictions`` (cached blocks reclaimed
    under pressure), ``cow_copies`` (ensure_writable copies), and
    ``peak_blocks_in_use`` (high-water mark of referenced blocks).
    """

    def __init__(self, num_blocks: int, block_len: int,
                 prefix_cache: bool = True, kv_dtype: str = "bf16",
                 host_blocks: int = 0):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the null block), "
                f"got {num_blocks}")
        if block_len < 1:
            raise ValueError(f"block_len must be >= 1, got {block_len}")
        if kv_dtype not in ("bf16", "int8", "mixed"):
            raise ValueError(
                f"kv_dtype must be bf16|int8|mixed, got {kv_dtype!r}")
        self.num_blocks = int(num_blocks)
        self.block_len = int(block_len)
        self.prefix_cache = bool(prefix_cache)
        self.kv_dtype = kv_dtype
        # per-block element dtype: 0 = the pool's native (bf16) dtype,
        # 1 = int8.  A pure-int8 pool is born all-1; ``mixed`` blocks are
        # born hot (0) and demote to 1 when they register as cold full
        # prefix blocks (``on_demote`` fires so the engine can rewrite
        # the device block); a freed block resets to the pool default.
        self._default_dtype = 1 if kv_dtype == "int8" else 0
        self._dtype = np.full(num_blocks, self._default_dtype, np.int8)
        # engine hook: called with the list of newly demoted physical
        # block ids (mixed mode only) so the device-side block rewrite —
        # a host-triggered quantize→dequantize pass — happens exactly
        # once per demotion, COW/refcount-safe because registration only
        # covers immutable full prompt blocks
        self.on_demote = None
        # host-tier hooks, same pattern as on_demote: the engine copies
        # device block contents off to / back from host payloads.  Fired
        # with [(device_bid, host_id)] pairs (swap-out / demote) or
        # [(host_id, device_bid)] pairs (swap-in / promote), always
        # BEFORE the device block id can be handed to a new owner, so
        # the copy is ordered against any later dispatch by host program
        # order.
        self.on_swap_out = None
        self.on_swap_in = None
        self._host: Optional[HostTier] = (
            HostTier(host_blocks) if host_blocks > 0 else None)
        # host-side trie: full token path -> (host id, element dtype).
        # OrderedDict insertion order IS the host LRU (oldest demotion
        # evicted first when swap records need the room).
        self._host_trie: "OrderedDict[_Path, Tuple[int, str]]" = (
            OrderedDict())
        # device block id -> its full token path while trie-registered
        # (what survives demotion as the host-trie key)
        self._block_path: Dict[int, _Path] = {}
        # bytes per block, per element dtype — set by the engine (the
        # manager has no model dims); feeds kv_cache.bytes_by_dtype
        self._block_nbytes: Dict[str, int] = {}
        self._free: Deque[int] = deque(range(1, num_blocks))
        # blocks newly appended to a chain since the last drain — an
        # int8 engine zeroes their device scale rows before dispatch
        # (a reused block's stale scale would otherwise inflate the
        # running-max quantization scale for its new tenant).  COW
        # destinations are excluded: the device copy carries the source
        # block's live scale with it.
        self._fresh: Set[int] = set()
        self._ref = np.zeros(num_blocks, np.int64)
        self._reserved = 0                       # admitted-but-unallocated
        self._slots: Dict[int, _SlotAlloc] = {}
        # chain-keyed trie: (parent block id, this block's tokens) -> id
        self._trie: Dict[Tuple[int, Tuple[int, ...]], int] = {}
        self._block_key: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        self._children: Dict[int, Set[int]] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # ref==0 cached
        # telemetry: counters + pool gauges in the shared registry
        # (labelled pool=<id>); ``stats`` stays the public readout as a
        # live Mapping view over them
        reg = _obs.default_registry()
        self._pid = str(next(_POOL_IDS))
        lbl = {"pool": self._pid}
        self._counters = {
            "prefix_lookups": reg.counter(
                "kv_cache.prefix_lookups",
                "admissions that consulted the prefix trie").labels(**lbl),
            "prefix_hit_blocks": reg.counter(
                "kv_cache.prefix_hit_blocks",
                "blocks adopted from the prefix cache instead of "
                "recomputed").labels(**lbl),
            "prefix_hit_tokens": reg.counter(
                "kv_cache.prefix_hit_tokens",
                "tokens adopted from the prefix cache").labels(**lbl),
            "evictions": reg.counter(
                "kv_cache.evictions",
                "cached blocks reclaimed under pool pressure").labels(
                    **lbl),
            "cow_copies": reg.counter(
                "kv_cache.cow_copies",
                "ensure_writable copy-on-write copies").labels(**lbl),
            "host_demotions": reg.counter(
                "kv_cache.host_demotions",
                "cold prefix blocks demoted HBM -> host instead of "
                "dropped under pool pressure").labels(**lbl),
            "host_promotions": reg.counter(
                "kv_cache.host_promotions",
                "host-tier prefix blocks promoted back to HBM on an "
                "admission hit").labels(**lbl),
            "swapped_out_blocks": reg.counter(
                "kv_cache.swapped_out_blocks",
                "private blocks moved to pinned host buffers by "
                "preemption swap-out").labels(**lbl),
            "swapped_in_blocks": reg.counter(
                "kv_cache.swapped_in_blocks",
                "pinned host blocks restored to HBM by preemption "
                "resume").labels(**lbl),
            "exported_blocks": reg.counter(
                "kv_cache.exported_blocks",
                "blocks serialized out of this pool for cross-worker "
                "migration (export_blocks)").labels(**lbl),
            "imported_blocks": reg.counter(
                "kv_cache.imported_blocks",
                "blocks materialized into this pool from a migration "
                "record (import_blocks)").labels(**lbl),
        }
        self._peak = 0
        self._g_peak = reg.gauge(
            "kv_cache.peak_blocks_in_use",
            "high-water mark of referenced blocks").labels(**lbl)
        self._g_in_use = reg.gauge(
            "kv_cache.blocks_in_use",
            "blocks referenced by at least one live chain").labels(**lbl)
        self._g_occ = reg.gauge(
            "kv_cache.pool_occupancy",
            "blocks_in_use / usable_blocks").labels(**lbl)
        self._g_free = reg.gauge(
            "kv_cache.free_blocks", "free-list length").labels(**lbl)
        self._g_cached = reg.gauge(
            "kv_cache.cached_blocks",
            "retired prefix blocks parked for future hits "
            "(evictable)").labels(**lbl)
        self._g_quant = reg.gauge(
            "kv_cache.quantized_blocks",
            "live (referenced or LRU-cached) blocks holding int8 "
            "content").labels(**lbl)
        self._g_host_used = reg.gauge(
            "kv_cache.host_blocks_used",
            "host-tier blocks live (demoted trie blocks + pinned swap "
            "records)").labels(**lbl)
        self._g_host_trie = reg.gauge(
            "kv_cache.host_trie_blocks",
            "host-tier blocks holding demoted (promotable, evictable) "
            "prefix-trie content").labels(**lbl)
        self._f_bytes = reg.gauge(
            "kv_cache.bytes_by_dtype",
            "live pool bytes per element dtype (payload + scale share; "
            "set once the engine provides per-block byte costs)")
        self._g_bytes = {
            "bf16": self._f_bytes.labels(dtype="bf16", **lbl),
            "int8": self._f_bytes.labels(dtype="int8", **lbl)}
        self._stats_view = _StatsView(self)
        self._refresh_gauges()

    @property
    def stats(self) -> Mapping:
        """Counter readout (``prefix_lookups``/``prefix_hit_blocks``/
        ``prefix_hit_tokens``/``evictions``/``cow_copies``/
        ``peak_blocks_in_use``) — a live view over the registry series."""
        return self._stats_view

    # -- accounting --------------------------------------------------------

    @property
    def usable_blocks(self) -> int:
        """Pool capacity a request can ever draw on (excludes the null
        block; includes blocks currently parked on the eviction LRU)."""
        return self.num_blocks - 1

    def blocks_in_use(self) -> int:
        """Blocks referenced by at least one live chain."""
        return int((self._ref > 0).sum())

    def cached_blocks(self) -> int:
        """Retired prefix blocks kept for future hits (evictable)."""
        return len(self._lru)

    def free_blocks(self) -> int:
        return len(self._free)

    def block_dtype(self, bid: int) -> str:
        """Element dtype of physical block ``bid``'s contents."""
        return "int8" if self._dtype[bid] else "bf16"

    def quantized_blocks(self) -> int:
        """Live (referenced or LRU-cached) blocks holding int8 content."""
        live = self._live_mask()
        return int((live & (self._dtype == 1)).sum())

    def set_block_nbytes(self, by_dtype: Dict[str, int]):
        """Engine-supplied per-block byte costs (payload + scale share)
        keyed by element dtype — arms the ``kv_cache.bytes_by_dtype``
        gauges (the manager itself has no model dimensions)."""
        self._block_nbytes = {k: int(v) for k, v in by_dtype.items()}
        self._refresh_gauges()

    def _live_mask(self) -> np.ndarray:
        live = self._ref > 0
        if self._lru:
            live[list(self._lru)] = True
        return live

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case blocks a request needs over its whole lifetime
        (positions 0 .. prompt_len + max_new_tokens - 1)."""
        return -(-(prompt_len + max_new_tokens) // self.block_len)

    def _available(self) -> int:
        return len(self._free) + len(self._lru) - self._reserved

    # -- admission ---------------------------------------------------------

    def admit(self, slot: int, prompt: Sequence[int], prompt_len: int,
              max_new_tokens: int, chunked: bool = False) -> Optional[int]:
        """Admit a request into ``slot``: match the prompt against the
        prefix trie, reserve every block the request could need, allocate
        the blocks covering positions ``[0, prompt_len]`` now, and
        register the prompt's full blocks for future sharing.

        Returns the number of prefix TOKENS adopted from the cache (the
        prefill may skip recomputing them), or ``None`` when the pool
        cannot cover the request yet (caller keeps it queued).  The match
        is capped at ``(prompt_len - 1) // block_len`` blocks so at least
        one token remains to produce the first sampled logits.

        ``chunked``: the chunked-prefill admission contract — the prompt
        will be written chunk by chunk over several ticks, so (a) no
        blocks beyond the adopted prefix are allocated now (the engine
        grows the chain per chunk via :meth:`ensure_capacity` — the
        reservation still covers the worst case, so growth cannot fail)
        and (b) the prompt is NOT registered in the trie yet: a block
        must never satisfy a prefix lookup before its contents are
        written (wave admission writes in the same scheduler call, so it
        registers immediately; chunked callers register incrementally
        via :meth:`register_prompt_upto` as chunks land on the device).
        """
        if slot in self._slots:
            raise ValueError(f"slot {slot} already has an allocation")
        bl = self.block_len
        prompt = [int(t) for t in prompt[:prompt_len]]
        matched: List[int] = []
        path: _Path = ()
        promo: List[Tuple[_Path, Tuple[int, ...], Tuple[int, str]]] = []
        if self.prefix_cache:
            self._counters["prefix_lookups"].inc()
            parent = _ROOT
            cap = (prompt_len - 1) // bl
            for b in range(cap):
                toks = tuple(prompt[b * bl:(b + 1) * bl])
                bid = self._trie.get((parent, toks))
                if bid is None:
                    break
                path = path + (toks,)
                matched.append(bid)
                parent = bid
            # the walk continues into the HOST tier: demoted blocks whose
            # full token path extends the device match are promotion
            # candidates (allocated below, from this request's own
            # reservation — they count as unmatched for admission math)
            if self._host is not None:
                for b in range(len(matched), cap):
                    toks = tuple(prompt[b * bl:(b + 1) * bl])
                    p = path + (toks,)
                    ent = self._host_trie.get(p)
                    if ent is None:
                        break
                    promo.append((p, toks, ent))
                    path = p
        m = len(matched)
        total = self.blocks_needed(prompt_len, max_new_tokens)
        need = total - m
        # a revived LRU block stops being evictable, so count the match
        # against availability too
        revive = sum(1 for bid in matched if self._ref[bid] == 0)
        if self._available() - revive < need:
            return None
        for bid in matched:                      # adopt the shared chain
            if self._ref[bid] == 0:
                self._lru.pop(bid, None)
            self._ref[bid] += 1
        st = _SlotAlloc(list(matched), need)
        self._slots[slot] = st
        self._reserved += need
        if promo:
            # promote host hits: fresh device blocks (reservation-funded,
            # so allocation cannot fail), payload copied back by the
            # engine's on_swap_in, re-registered in the device trie under
            # their original keys.  NOT _fresh: swap-in restores content
            # AND scale — the int8 engine's fresh-scale zeroing would
            # wipe the restored quantization scale.
            #
            # Claim the host entries FIRST: _append_block below may have
            # to evict (_evict_one), whose demotion path calls
            # _host_make_room / _host_drop_cascade — either could evict a
            # still-listed promo entry, freeing the very payload we are
            # about to copy back (and the later trie delete would then
            # KeyError).  Popped entries keep their host ids allocated,
            # so they are invisible to host eviction but their payloads
            # stay live until on_swap_in has read them.
            for p, _, _ in promo:
                del self._host_trie[p]
            pairs: List[Tuple[int, int]] = []
            parent = matched[-1] if matched else _ROOT
            for p, toks, (hid, dt) in promo:
                bid = self._append_block(st)
                self._fresh.discard(bid)
                self._dtype[bid] = 1 if dt == "int8" else 0
                key = (parent, toks)
                self._trie[key] = bid
                self._block_key[bid] = key
                self._block_path[bid] = p
                if parent != _ROOT:
                    self._children.setdefault(parent, set()).add(bid)
                pairs.append((hid, bid))
                parent = bid
            if self.on_swap_in is not None:
                self.on_swap_in(list(pairs))
            for hid, _ in pairs:
                self._host.free(hid)
            self._counters["host_promotions"].inc(len(pairs))
        m_blocks = m + len(promo)
        if not chunked:
            # blocks covering positions [0, prompt_len]: the prefill
            # writes the suffix and the first decode step writes position
            # prompt_len
            for _ in range(prompt_len // bl + 1 - m_blocks):
                self._append_block(st)
            if self.prefix_cache:
                self._register_prompt(st.chain, prompt, prompt_len)
        self._counters["prefix_hit_blocks"].inc(m_blocks)
        self._counters["prefix_hit_tokens"].inc(m_blocks * bl)
        self._note_peak()
        return m_blocks * bl

    def prefix_probe(self, prompt: Sequence[int],
                     prompt_len: Optional[int] = None) -> int:
        """READ-ONLY longest trie match for ``prompt``, in tokens — the
        dp replica router's placement probe (serving/router.py): which
        replica holds the warm blocks for this prompt?  No refcount
        changes, no LRU touches, no counter increments — admission via
        :meth:`admit` remains the only trie consumer with side effects.
        Capped exactly like admission (at least one token must remain
        to produce the first logits), so the probe never promises more
        than admit() would adopt."""
        if not self.prefix_cache:
            return 0
        n = int(prompt_len if prompt_len is not None else len(prompt))
        bl = self.block_len
        toks = [int(t) for t in prompt[:n]]
        parent = _ROOT
        m = 0
        for b in range((n - 1) // bl):
            bid = self._trie.get((parent, tuple(toks[b * bl:(b + 1) * bl])))
            if bid is None:
                break
            m += 1
            parent = bid
        return m * bl

    def register_prompt_upto(self, slot: int, prompt: Sequence[int],
                             upto: int):
        """Chunked-prefill trie registration: insert the prompt's full
        blocks whose every token is among the first ``upto`` WRITTEN
        tokens.  Idempotent — the engine calls it after each chunk's
        device step is dispatched (program order sequences any adopter's
        reads after the writes), so prefix hits become available chunk by
        chunk instead of all-or-nothing at retirement."""
        if not self.prefix_cache:
            return
        st = self._slots[slot]
        self._register_prompt(st.chain,
                              [int(t) for t in prompt[:upto]], int(upto))

    def _register_prompt(self, chain: List[int], prompt: List[int],
                         prompt_len: int):
        """Insert the prompt's FULL blocks into the trie.  Only blocks
        whose every position is a prompt token are registered — the block
        holding position ``prompt_len`` onward is still being written by
        decode and must stay private."""
        bl = self.block_len
        parent = _ROOT
        path: _Path = ()
        demoted: List[int] = []
        for b in range(prompt_len // bl):
            bid = chain[b]
            toks = tuple(prompt[b * bl:(b + 1) * bl])
            key = (parent, toks)
            path = path + (toks,)
            if key not in self._trie and bid not in self._block_key:
                self._trie[key] = bid
                self._block_key[bid] = key
                self._block_path[bid] = path
                if parent != _ROOT:
                    self._children.setdefault(parent, set()).add(bid)
                # one-tier rule: this path now has freshly written HBM
                # content, so a host-demoted copy of the same path is
                # redundant — drop it (content-identical by definition:
                # the path IS the content identity)
                if self._host is not None:
                    ent = self._host_trie.pop(path, None)
                    if ent is not None:
                        self._host.free(ent[0])
                # mixed pool: a block registering as a shareable FULL
                # prefix block is cold by definition (immutable from
                # here on) — demote it to int8 now; the engine's
                # on_demote device rewrite is refcount-safe because no
                # writer ever touches a registered full block again
                # (forks go through ensure_writable first)
                if self.kv_dtype == "mixed" and not self._dtype[bid]:
                    self._dtype[bid] = 1
                    demoted.append(bid)
            parent = self._trie.get(key, bid)
        if demoted:
            if self.on_demote is not None:
                self.on_demote(list(demoted))
            self._refresh_gauges()

    # -- growth / writes ---------------------------------------------------

    def _pop_block(self) -> int:
        if self._free:
            return self._free.popleft()
        return self._evict_one()

    def _append_block(self, st: _SlotAlloc) -> int:
        if st.reserved_left <= 0:
            raise RuntimeError(
                "block allocation beyond the slot's admission reservation "
                "(engine bug: reservation must cover prompt + max_new)")
        bid = self._pop_block()
        self._ref[bid] = 1
        self._fresh.add(bid)
        st.chain.append(bid)
        st.reserved_left -= 1
        self._reserved -= 1
        return bid

    def drain_fresh(self) -> List[int]:
        """Physical ids of blocks newly appended to chains since the last
        call (cleared on read).  The int8 engine zeroes these blocks'
        device scale rows before the next step dispatch — see
        ``_fresh``'s init comment for why reuse makes that necessary."""
        out = sorted(self._fresh)
        self._fresh.clear()
        return out

    def ensure_capacity(self, slot: int, pos: int) -> bool:
        """Grow ``slot``'s chain until it covers position ``pos``.
        Returns True when blocks were appended (table row changed)."""
        st = self._slots[slot]
        grew = False
        while len(st.chain) * self.block_len <= pos:
            self._append_block(st)
            grew = True
        if grew:
            self._note_peak()
        return grew

    def ensure_writable(self, slot: int,
                        logical_block: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write guard: make ``slot``'s ``logical_block`` private.
        Returns ``(src, dst)`` physical ids when a copy is needed (caller
        must copy the device block src -> dst), else None.  The fresh
        block comes from the free/evictable pool — COW is not covered by
        the admission reservation (it cannot occur in the append-only
        engine flow; forking callers must size the pool for it)."""
        st = self._slots[slot]
        src = st.chain[logical_block]
        if self._ref[src] <= 1:
            return None
        dst = self._pop_block()
        self._ref[src] -= 1
        self._ref[dst] = 1
        st.chain[logical_block] = dst
        self._counters["cow_copies"].inc()
        self._note_peak()
        return src, dst

    # -- retirement / eviction --------------------------------------------

    def release(self, slot: int):
        """Retire a slot: drop its references and its unused reservation.
        Trie-registered blocks that reach refcount 0 are parked on the
        eviction LRU (future prefix hits revive them for free); anonymous
        blocks return to the free list."""
        st = self._slots.pop(slot)
        self._reserved -= st.reserved_left
        for bid in st.chain:
            self._ref[bid] -= 1
            if self._ref[bid] == 0:
                if bid in self._block_key:
                    # LRU-parked: the content (and its dtype) persists
                    # for future prefix hits
                    self._lru[bid] = None
                    self._lru.move_to_end(bid)
                else:
                    self._free.append(bid)
                    self._dtype[bid] = self._default_dtype
        self._refresh_gauges()

    # -- preemption / host tier --------------------------------------------

    @property
    def host_tier(self) -> Optional[HostTier]:
        """The host-RAM tier (None when ``host_blocks == 0``) — the
        engine reads/writes payloads through it from the swap hooks."""
        return self._host

    def host_blocks_used(self) -> int:
        return self._host.used if self._host is not None else 0

    def host_trie_blocks(self) -> int:
        """Host-tier blocks holding demoted (promotable) trie content;
        the rest of ``host_blocks_used`` is pinned swap records."""
        return len(self._host_trie)

    def host_cache_bytes(self) -> int:
        """Host-RAM entitlement of the tier: capacity x full-precision
        block bytes (payloads are per-entry dtype, so this is the
        worst case).  Deliberately NOT part of ``cache_hbm_bytes`` or
        the mesh pre-flight HBM-liveness cross-check — the tier lives
        in pinned host memory, never on device."""
        if self._host is None or not self._block_nbytes:
            return 0
        return self._host.capacity * self._block_nbytes.get("bf16", 0)

    def private_swap_blocks(self, slot: int) -> int:
        """How many of ``slot``'s blocks a swap-out would have to move
        to the host tier (refcount-1 blocks; shared blocks stay put)."""
        st = self._slots[slot]
        return sum(1 for bid in st.chain if self._ref[bid] == 1)

    def host_can_accept(self, n: int) -> bool:
        """Could the host tier take ``n`` more pinned blocks right now,
        evicting demoted trie entries if it must?  (Pinned swap records
        are never evicted for other swap records.)"""
        if self._host is None:
            return False
        return self._host.free_slots() + len(self._host_trie) >= n

    def _host_make_room(self, n: int) -> bool:
        """Ensure ``n`` free host slots by evicting the oldest demoted
        trie entries (never pinned swap records).  False when the tier
        cannot cover ``n`` — nothing is evicted needlessly first."""
        if self._host is None:
            return False
        if self._host.free_slots() + len(self._host_trie) < n:
            return False
        while self._host.free_slots() < n:
            p, (hid, _) = self._host_trie.popitem(last=False)
            self._host.free(hid)
            self._host_drop_cascade(p)
        return True

    def _host_drop_cascade(self, path: _Path):
        """Free host-trie entries STRICTLY below ``path`` — with their
        ancestor gone from both tiers the admission walk can never
        reach them, and unreachable entries would leak host capacity."""
        if self._host is None or not self._host_trie:
            return
        k = len(path)
        for p in [p for p in self._host_trie
                  if len(p) > k and p[:k] == path]:
            hid, _ = self._host_trie.pop(p)
            self._host.free(hid)

    def swap_out(self, slot: int) -> Optional[Dict[str, object]]:
        """Preempt ``slot``: tear down its allocation, moving every
        PRIVATE block (refcount 1) to a pinned host buffer and keeping
        this slot's reference on every SHARED block so the chain
        survives other owners' releases.  Returns the resume record for
        :meth:`resume_swapped` — ``entries`` is the chain in order, each
        entry ``("hbm", bid)`` (reference kept) or ``("host", hid,
        dtype)`` (payload pinned on host) — or ``None`` when the host
        tier cannot take the private blocks even after evicting every
        demoted trie entry (caller falls back to recompute or skips the
        victim).  Record entries are never trie keys: a swapped chain
        cannot serve a prefix hit until it is resumed."""
        st = self._slots[slot]
        n_priv = sum(1 for bid in st.chain if self._ref[bid] == 1)
        if not self._host_make_room(n_priv):
            return None
        st = self._slots.pop(slot)
        reserved_left = st.reserved_left
        self._reserved -= reserved_left
        entries: List[Tuple] = []
        pairs: List[Tuple[int, int]] = []
        for bid in st.chain:
            if self._ref[bid] > 1:
                entries.append(("hbm", int(bid)))
                continue
            if bid in self._block_key:
                # the physical id is about to be freed — its trie entry
                # (and descendants') would dangle
                self._unregister_cascade(bid)
            hid = self._host.alloc()
            entries.append(("host", hid, self.block_dtype(bid)))
            pairs.append((int(bid), hid))
            self._ref[bid] = 0
            self._free.append(bid)
            self._dtype[bid] = self._default_dtype
        if pairs:
            if self.on_swap_out is not None:
                self.on_swap_out(list(pairs))
            self._counters["swapped_out_blocks"].inc(len(pairs))
        self._fresh.difference_update(b for b, _ in pairs)
        self._refresh_gauges()
        return {"entries": entries, "reserved_left": int(reserved_left)}

    def resume_swapped(self, slot: int, record: Dict[str, object]
                       ) -> Optional[int]:
        """Rebuild a swapped-out chain into (free) ``slot``: allocate a
        fresh device block per ``host`` entry (payload copied back via
        ``on_swap_in``, host buffer freed), re-adopt each ``hbm`` entry
        (its reference was never dropped), and re-arm the remaining
        reservation.  Returns the chain length, or ``None`` when the
        pool cannot cover the host blocks + reservation yet (caller
        keeps the record and retries later)."""
        if slot in self._slots:
            raise ValueError(f"slot {slot} already has an allocation")
        entries = record["entries"]
        reserved = int(record["reserved_left"])
        n_host = sum(1 for e in entries if e[0] == "host")
        if self._available() < n_host + reserved:
            return None
        chain: List[int] = []
        pairs: List[Tuple[int, int]] = []
        for e in entries:
            if e[0] == "hbm":
                chain.append(int(e[1]))
                continue
            _, hid, dt = e
            bid = self._pop_block()
            self._ref[bid] = 1
            self._dtype[bid] = 1 if dt == "int8" else 0
            chain.append(bid)
            pairs.append((hid, int(bid)))
        self._slots[slot] = _SlotAlloc(chain, reserved)
        self._reserved += reserved
        if pairs:
            if self.on_swap_in is not None:
                self.on_swap_in(list(pairs))
            for hid, _ in pairs:
                self._host.free(hid)
            self._counters["swapped_in_blocks"].inc(len(pairs))
        self._note_peak()
        return len(chain)

    def drop_swap_record(self, record: Dict[str, object]):
        """Cancel a swapped-out request: release the record's pinned
        host buffers and drop the references it kept on shared blocks
        (parking registered ones on the LRU exactly like a release)."""
        for e in record["entries"]:
            if e[0] == "hbm":
                bid = int(e[1])
                self._ref[bid] -= 1
                if self._ref[bid] == 0:
                    if bid in self._block_key:
                        self._lru[bid] = None
                        self._lru.move_to_end(bid)
                    else:
                        self._free.append(bid)
                        self._dtype[bid] = self._default_dtype
            else:
                self._host.free(e[1])
        self._refresh_gauges()

    # -- cross-pool migration (ISSUE 18) -----------------------------------

    def export_blocks(self, slot: int, read_payload) -> Dict[str, object]:
        """Serialize ``slot``'s chain for migration into ANOTHER pool:
        one entry per block, in chain order, carrying the block's element
        dtype tag and the payload ``read_payload(bid)`` returns (a host
        pytree — the engine reads the device block including its scale
        row, so quantized blocks survive the trip bit-for-bit).

        By-value and read-only: shared (refcount > 1) blocks are copied
        like private ones — the importing pool is a different manager,
        so exporting never touches refcounts, the trie, or the LRU here.
        The source chain stays fully live until the caller releases it.
        """
        st = self._slots[slot]
        entries: List[Dict[str, object]] = [
            {"dtype": self.block_dtype(bid), "payload": read_payload(
                int(bid))} for bid in st.chain]
        self._counters["exported_blocks"].inc(len(entries))
        return {"entries": entries,
                "reserved_left": int(st.reserved_left),
                "block_len": int(self.block_len)}

    def import_blocks(self, slot: int, record: Dict[str, object],
                      write_payload) -> Optional[int]:
        """Materialise an exported chain into (free) ``slot`` of THIS
        pool: allocate one device block per entry, restore its dtype tag,
        and hand the payload to ``write_payload(bid, payload)``; the
        remaining admission reservation is re-armed so the imported
        request can keep decoding to its original budget.  Returns the
        chain length, or ``None`` when the pool cannot cover the blocks
        plus the reservation right now (existing reservations are
        respected — migration never strands an admitted local request).
        Imported blocks are NOT marked fresh: their scale rows arrive in
        the payload and must not be zeroed before the next dispatch."""
        if slot in self._slots:
            raise ValueError(f"slot {slot} already has an allocation")
        if int(record.get("block_len", self.block_len)) != self.block_len:
            raise ValueError(
                f"block_len mismatch: record has "
                f"{record.get('block_len')}, pool has {self.block_len}")
        entries = record["entries"]
        reserved = int(record["reserved_left"])
        if self._available() < len(entries) + reserved:
            return None
        chain: List[int] = []
        for e in entries:
            bid = self._pop_block()
            self._ref[bid] = 1
            self._dtype[bid] = 1 if e["dtype"] == "int8" else 0
            write_payload(int(bid), e["payload"])
            chain.append(bid)
        self._slots[slot] = _SlotAlloc(chain, reserved)
        self._reserved += reserved
        self._counters["imported_blocks"].inc(len(chain))
        self._note_peak()
        self._refresh_gauges()
        return len(chain)

    def preempt_free(self, slot: int):
        """Recompute-mode preemption: pool mechanics identical to
        :meth:`release` — registered prompt blocks park on the LRU, so
        the victim's resume re-prefill adopts whatever survives the
        pressure through the ordinary prefix-trie path (possibly via
        the host tier if it demotes in between)."""
        self.release(slot)

    def _evict_one(self) -> int:
        """Reclaim the LRU cached block.  Unregistering cascades through
        the block's trie descendants (their chain keys dangle once the
        parent id is reused): cached descendants move to the free list,
        live ones just lose their trie entry."""
        if not self._lru:
            raise RuntimeError(
                "KV block pool exhausted: no free or evictable blocks "
                "(reservation accounting should have prevented this)")
        bid, _ = self._lru.popitem(last=False)
        self._counters["evictions"].inc()
        # tiering: instead of dropping the content, demote it HBM ->
        # host (payload copied off by the engine BEFORE the id can be
        # handed to a new owner; the full token path keys the host trie
        # so a later admission can promote it back).  Skipped when the
        # host tier is absent or full of pinned swap records.
        bpath = self._block_path.get(bid)
        if (self._host is not None and bpath is not None
                and self._host_make_room(1)):
            hid = self._host.alloc()
            if self.on_swap_out is not None:
                self.on_swap_out([(int(bid), hid)])
            self._host_trie[bpath] = (hid, self.block_dtype(bid))
            self._counters["host_demotions"].inc()
        self._unregister_cascade(bid)
        self._dtype[bid] = self._default_dtype  # new owner rewrites it
        return bid

    def _unregister_cascade(self, bid: int):
        """Drop ``bid``'s trie registration and every descendant's —
        their chain keys dangle the moment the parent link goes, so a
        partial invalidation would leave unreachable-but-stale entries.
        Cached (refcount-0, LRU-parked) descendants move to the free
        list; live ones just lose their trie entry."""
        stack = [bid]
        while stack:
            b = stack.pop()
            key = self._block_key.pop(b, None)
            if key is not None:
                self._trie.pop(key, None)
            bpath = self._block_path.pop(b, None)
            if bpath is not None and self._host is not None:
                # host entries STRICTLY below this path lose their last
                # ancestor link — the admission walk can never reach
                # them again, so they are dropped like device-trie
                # descendants (the demoted copy AT b's own path, if the
                # eviction above just created it, survives: strict
                # descendants only)
                self._host_drop_cascade(bpath)
            stack.extend(self._children.pop(b, ()))
            if b != bid and b in self._lru:
                del self._lru[b]
                self._free.append(b)
                self._dtype[b] = self._default_dtype

    def truncate_to(self, slot: int, pos: int):
        """Roll ``slot``'s chain back to cover exactly positions
        ``[0, pos)`` — the speculative-decode ROLLBACK hook: after the
        verify step rejects a draft suffix, the blocks that existed only
        to hold rejected tokens go back to the pool and the admission
        reservation is re-credited, so the slot can grow over the same
        positions again as real decoding proceeds (growth stays
        infallible).  A no-op when the chain is already within ``pos``.

        Safety invariants, in the order they matter:

          * **trie**: every registered block at chain index >=
            ``pos // block_len`` is cascade-unregistered BEFORE anything
            is freed.  The partial block at the cut stays in the chain
            but will be rewritten in place at positions >= ``pos``, and
            removed blocks return to the free list for arbitrary reuse —
            either way, a later prefix lookup must never be served by
            them (the stale-hit hazard :meth:`_evict_one` also guards).
            In the engine flow only *generated* positions are ever
            rolled back, so registered PROMPT blocks sit strictly below
            the cut and keep serving hits;
          * **refcounts / COW**: removed blocks are deref'd, not freed
            outright — a block shared with another slot's chain (COW
            sharing, adopted prefixes) survives untouched for its other
            owners and only leaves this chain's table;
          * **reservation**: each block this slot actually releases is
            re-credited to its ``reserved_left``, keeping
            ``blocks_needed``-based admission exact.
        """
        st = self._slots[slot]
        if pos < 0:
            raise ValueError(f"pos must be >= 0, got {pos}")
        keep = -(-pos // self.block_len)         # blocks covering [0, pos)
        cut = pos // self.block_len              # first rewritable block
        for bid in st.chain[cut:]:
            if bid in self._block_key:
                self._unregister_cascade(bid)
        removed = st.chain[keep:]
        if not removed:
            self._refresh_gauges()
            return
        del st.chain[keep:]
        for bid in removed:
            self._ref[bid] -= 1
            if self._ref[bid] == 0:
                # unregistered above, so never LRU-parked: straight back
                # to the free list
                self._free.append(bid)
                self._dtype[bid] = self._default_dtype
        st.reserved_left += len(removed)
        self._reserved += len(removed)
        self._refresh_gauges()

    # -- table export ------------------------------------------------------

    def table_row(self, slot: int, max_blocks: int) -> np.ndarray:
        """(max_blocks,) int32 physical ids, null-block-filled past the
        allocated chain (every entry is a valid pool index).

        Null-block aliasing rule (ISSUE 14; the kernel pre-flight's
        ClampCheck proves the other half): PAD columns past the chain
        may map to ``NULL_BLOCK`` — the decode kernel's walk stops at
        the row's last live block, so they are never dereferenced — but a LIVE chain entry
        mapping to block 0 would alias the null block's pad data into
        the row's attention window, silently corrupting the output.
        The allocator can never produce one (block 0 is excluded from
        the free list at construction), so this is asserted, not
        handled."""
        st = self._slots[slot]
        if len(st.chain) > max_blocks:
            raise ValueError(
                f"slot {slot} chain ({len(st.chain)} blocks) exceeds "
                f"max_blocks ({max_blocks})")
        assert NULL_BLOCK not in st.chain, (
            f"slot {slot} chain references the null block: live rows "
            f"must never map to block 0 (pad aliasing)")
        row = np.full((max_blocks,), NULL_BLOCK, np.int32)
        row[:len(st.chain)] = st.chain
        return row

    def chain(self, slot: int) -> List[int]:
        return list(self._slots[slot].chain)

    def _note_peak(self):
        used = self._refresh_gauges()
        if used > self._peak:
            self._peak = used
            self._g_peak.set(used)

    def _refresh_gauges(self) -> int:
        """Push the pool-occupancy gauges; returns blocks_in_use."""
        used = self.blocks_in_use()
        self._g_in_use.set(used)
        self._g_occ.set(used / self.usable_blocks)
        self._g_free.set(len(self._free))
        self._g_cached.set(len(self._lru))
        live = self._live_mask()
        n_int8 = int((live & (self._dtype == 1)).sum())
        self._g_quant.set(n_int8)
        if self._host is not None:
            self._g_host_used.set(self._host.used)
            self._g_host_trie.set(len(self._host_trie))
        if self._block_nbytes:
            self._g_bytes["int8"].set(
                n_int8 * self._block_nbytes.get("int8", 0))
            self._g_bytes["bf16"].set(
                (int(live.sum()) - n_int8)
                * self._block_nbytes.get("bf16", 0))
        return used
