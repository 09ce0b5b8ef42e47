"""Slot-based continuous-batching engine over the stacked KV cache.

Design (Orca-style iteration-level scheduling, expressed TPU-first):

  * the KV cache is ONE stacked array ``(L, 2, num_slots, max_length,
    Hkv, D)`` — the ``generate()`` cache with the batch axis reinterpreted
    as *slots*.  A slot is a lease on one cache row; requests come and go,
    the array never changes shape, so nothing ever recompiles;
  * the **step function** ``(params, cache, tokens, positions, slot_mask,
    sampling vectors, rng) -> (next_tokens, cache)`` is jitted ONCE for
    the slot count and reused for the engine's lifetime.  Per-slot
    position vectors (ops/attention.py cache masking, llama.py scatter
    writes) are what let one program serve rows at different depths, and
    per-slot sampling vectors (generation.py ``sample_tokens``, traced
    form) let greedy and sampled requests share a batch; that epilogue
    does only what the tick's vectors ask for, by conditionals inside the
    one program — an all-greedy tick runs the argmax alone, the
    full-vocabulary sort runs only when a sampling row truncates, and a
    row's token never depends on its neighbours.  The host holds the
    same vectors, so each tick's span (``sample_path=``) and the
    ``serving.sample_path`` counter name the way it went without a
    readback.  The same
    position vector doubles as the flash-decode kernel's live-prefix
    hint: at max_length >= FLAGS_decode_attention_min_len the attention
    dispatcher hands it to ops/pallas/decode_attention.py as a
    scalar-prefetch operand that sizes each row's block walk, so each step
    streams only each slot's live cache prefix — slots at shallow,
    heterogeneous depths under a worst-case-sized max_length stop paying
    for the dead tail, with no retrace;
  * **prefill** reuses the existing static-``pos=0`` path — the one that
    routes through the Pallas flash kernel on TPU: admitted prompts are
    right-padded to a power-of-two bucket, run through ``decode_step`` on
    a fresh cache of the wave's rows, and the finished rows are
    scattered into their slots.  Padding is sound because attention is
    causal (pad queries influence nobody) and the cache mask never reads
    past the row's position, while decode overwrites each pad slot with
    fresh K/V before the mask can reach it.  The program's row count
    follows what a row costs (PR 41): below ``_ROW_FILLS_CHIP`` positions a
    row's products are bound by the weights' stream, rows beside it ride
    for nothing, and a wave is padded to ``prefill_batch`` rows — its dummy
    rows riding along via out-of-bounds slot ids, which the scatter drops;
    from that bucket on a row is bound by compute, a dummy row costs what a
    real one does, and each request is prefilled ALONE, in a one-row
    program at its own bucket.  One compiled prefill program per bucket
    length either way, so a warm-up of one prompt a bucket reaches every
    program;
  * the **host scheduler** owns admission and retirement: a FIFO queue,
    waves of batched prefill into free slots, EOS/max-token retirement,
    and per-request outputs returned in arrival order.  Device work per
    tick is one step-function call; the only host sync is fetching the
    (num_slots,) token vector the scheduler must branch on.

Relation to ``generate()``: same model code path (``decode_step``), same
sampling implementation, same cache layout — greedy engine outputs are
token-identical to ``greedy_generate`` (tests/test_serving.py asserts
this across admission orders).  ``generate()`` remains the right tool for
offline parity/eval batches; the engine is the right tool for traffic.

**Paged mode** (``paged=True``): the per-slot cache rows are replaced by
the kv_cache.py block pool — one
``(L, 2, num_blocks, block_len, Hkv·D)`` array plus a host-side
:class:`~paddle_tpu.serving.kv_cache.BlockManager`.  What changes and
what doesn't:

  * the step function signature gains one tiny traced input, the
    ``(num_slots, max_blocks)`` block table; it is still jitted ONCE —
    allocation churn moves data through that input, never a retrace;
  * HBM cost becomes live tokens + shared prefixes instead of
    ``num_slots × max_length``: blocks are allocated lazily as slots
    deepen (admission reserves the worst case so mid-flight allocation
    can't fail), retired prompt blocks stay cached for prefix hits until
    pool pressure evicts them LRU-first;
  * admission consults the prefix trie: a request whose prompt opens with
    already-cached full blocks adopts them (refcount, zero recompute) and
    prefill runs ONLY the suffix — a shared system prompt is computed and
    stored once, which the manager's hit counters prove;
  * prefill therefore runs as decode-at-depth on the pool itself (per-row
    ``pos`` = adopted prefix length) rather than on a fresh pos=0
    sub-cache — it takes the cached-attention path, not the flash-prefill
    kernel; the trade is recompute avoided vs kernel choice, and it wins
    whenever prefixes actually repeat.  Greedy outputs stay
    token-identical to the contiguous engine (tests/test_serving_paged.py).

**Chunked prefill** (``chunked=True`` / FLAGS_serving_chunked_prefill):
wave admission stalls every in-flight decode for a whole prompt's prefill
latency (~90 ms at b=8, prompt 1024 per BENCH_DECODE.json) — the classic
TPOT-spike / head-of-line-blocking failure Sarathi-Serve's chunked
prefill and Orca's iteration-level scheduling target.  Chunked mode
replaces the wave with a **token-budget scheduler**:

  * each admitted prompt becomes a cursor (:class:`_Prefill`), not a
    prefill dispatch; every tick runs ONE **mixed step** — all decode
    rows advance one token AND at most one ``prefill_chunk``-token slice
    of the prompt streams into its slot's cache (as decode-at-depth:
    per-row positions, the flash-decode kernel's chunked q mode at long
    caches).  The per-tick token budget is ``num_slots + prefill_chunk``,
    so TPOT degrades by a bounded, chunk-sized amount instead of a
    whole-prompt stall, and TTFT pipelines across ticks;
  * the mixed step is jitted ONCE (chunk size static, budget-1
    ``track_retraces`` site ``serving.step``); a chunk-free tick runs a
    second program, the same pass over the decode rows and a stub of 8 of
    the chunk's ``prefill_chunk`` positions, jitted once too (site
    ``serving.step_rows``) and chosen on the host by what the tick holds;
  * ``chunk_policy`` trades the two SLOs: ``"prefill"`` (default) runs a
    pending chunk every tick, ``"decode"`` interleaves chunks with
    chunk-free ticks while decodes are active;
  * paged composition: admission adopts cached prefix blocks (the cursor
    starts past them), chains grow per chunk, and full prompt blocks are
    trie-registered only AFTER the chunk writing them is dispatched —
    an unwritten block can never satisfy a prefix lookup.

Greedy outputs remain token-identical to the wave engine (and therefore
to ``greedy_generate``) — tests/test_serving.py staggered traces with a
long prompt arriving mid-decode assert it for both cache layouts.

**Speculative decoding** (``spec_decode=True``):
at b=1 the decode step already sits AT the bf16 weight-stream floor
(BENCH_DECODE.json, 1.0–1.07x of bound), so no kernel tuning helps — the
only lever left is amortising each pass of the weights over MORE than one
token.  Spec mode does that without a second model:

  * a host-side **self-drafter** (drafter.py: prompt-lookup / n-gram
    match over each slot's prompt+generated history, the vLLM ``ngram``
    speculator scheme) proposes up to ``spec_k`` tokens per greedy slot
    per tick;
  * ONE once-jitted **verify step** feeds every row its (k+1)-token
    window ``[current, d_1..d_k]`` at its own depth — exactly the
    q-tiled mode the flash-decode kernel grew for chunked prefill, with
    per-row positions riding scalar-prefetch as always — so all drafts
    of all slots are scored in a single pass of the weights
    (``ops.kernel_path{op="spec_verify"}`` counts the routing);
  * ``accept_draft_tokens`` (models/generation.py) keeps each row's
    longest verified prefix plus the bonus token — 1..k+1 tokens
    committed per step, token-identical to plain greedy decode; sampled
    rows accept one token (exact distribution, no approximation);
  * **rollback** of a rejected suffix is bookkeeping, not device work:
    contiguous rows simply don't advance past the accept point (stale
    K/V above it is overwritten before any mask can read it), paged rows
    additionally return draft-only blocks to the pool via
    ``BlockManager.truncate_to`` (refcount/COW-safe, reservation
    re-credited, trie invalidated past the cut);
  * rows with no draft hit ride the SAME program as depth-1 decode (k is
    static; absent drafts are pad columns masked out of acceptance, with
    their junk writes steered exactly like idle rows'), so the retrace
    budget stays 1 and the graph lint stays green in every layout.
    Chunked prefill composes: the mixed step's decode half becomes the
    verify window while a prefilling slot — inactive by construction —
    drafts nothing until its cursor completes.

**Block diffusion** (a model that declares ``block_diffusion``: its block
length ``B`` and mask token).  Everything above says a row advances one
token a tick and a prompt's last chunk samples the request's first token;
this is the one exception, and it is no switch of the constructor's: the
rows part becomes a **block rows part** — ``tokens`` is the ``(num_slots,
B)`` matrix of each row's current block (the mask token where a position is
still masked), at the block's first position, the verify window's shape —
whose epilogue is the unmasking rule (``models.generation.unmask_block``)
in place of one sampled token.  A row's tick is a **denoising forward**
(it unmasks one or more positions by confidence) or, when its block went in
mask-free, the **commit forward** (it unmasks nothing; the K/V it writes
are what later blocks read) after which the row advances by ``B`` and opens
its next block.  A block that comes back mask-free is DELIVERED at once:
``result(rid)`` grows a block at a time, cut at ``max_new_tokens``, and the
first delivery is the request's first token; ``unmask_steps(rid)`` says at
which forward-in-block each token was unmasked.  A prompt's whole blocks
stream in through the chunk part under the same block-causal mask (the
model's attention states the mask; the chunk's sampled token is unused) and
the ``P mod B`` tokens left over open the first block already unmasked.
Growth and the admission reservation go a block of positions at a time.
Strategy and threshold are per request (``SamplingParams``).

**One path** (PR 29).  The three switches select parts of one composed
path, not copies of it: ONE step program (``_step_program``: a rows part —
plain, verify or a block-diffusion model's block rows — and a chunk part
when chunked, over block tables or
slot rows, in ONE pass of the model's weights (``decode_parts``, since PR
34: every token-wise operation once over both parts' tokens, attention and
per-slot state a part at a time); where each kind of idle write lands is
argued there, once) and
one prefill program for the wave engines, each with its signature written
down once as an operand table (``_operand_tables``) from which the layout of
the ONE buffer its operands cross in is derived (PR 38: ``_lay_out``, filled
by ``_upload``, taken apart by the program's first lines, ``_unpack``), and
which the lint's arguments and the mesh shardings read too; ONE tick
(``_step_inner``); and one device seam each for the tick and the wave
(``_device_step``, ``_device_prefill``), which is all the fleet simulator
replaces.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import itertools
import json
import math
import time
from collections import deque
from typing import (Deque, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags as _flags
from .. import observability as _obs
from ..distributed import moe as _moe
from ..models.generation import (SAMPLE_PATHS, _place_on_mesh,
                                 accept_draft_tokens, decode_mesh_specs,
                                 init_kv_cache, sample_path, sample_tokens,
                                 unmask_block)
from ..models.parts import DecodePart, ServingTraits
from ..nn.layer import bind_params
from ..ops import _dispatch as _disp
from .drafter import DraftModelDrafter, NgramDrafter
from .kv_cache import BlockManager, init_paged_kv_cache

__all__ = ["ServingEngine", "SamplingParams", "Request", "TICK_PHASES",
           "TICK_COSTS"]

#: The phases of one scheduler tick: the names of the non-overlapping
#: child spans that tile ``serving.step`` in the tick and in the prefill
#: wave (a wave's phases nest inside ``serving.admit`` >
#: ``serving.prefill``).  Whoever attributes host time or a device idle
#: gap to the tick reads these names (benchmark/harness/engine_spans.py):
#:
#: - ``serving.admit``: queue scan, pool reservation, swap resumes, wave
#:   building (``_admit*``);
#: - ``serving.grow``: block-table growth and COW over the slots,
#:   ``ensure_capacity``, ``_flush_fresh_scales`` (paged only);
#: - ``serving.build_inputs``: the chunk operand's assembly and the
#:   host->device uploads (spec mode: also the draft, which builds the
#:   verify window, in a span of its own before ``serving.grow``);
#: - ``serving.dispatch`` (``leaves=``: the leaves of ``params`` and
#:   ``cache`` the call flattens): the call of the step / prefill program
#:   and nothing else, which returns when the program is enqueued;
#: - ``serving.readback``: the token fetch, the tick's one sync;
#: - ``serving.advance``: per-slot advance, chunk accounting, retirement,
#:   queued demotions.
TICK_PHASES = ("serving.admit", "serving.grow", "serving.build_inputs",
               "serving.dispatch", "serving.readback", "serving.advance")
_ADMIT, _GROW, _BUILD, _DISPATCH, _READBACK, _ADVANCE = TICK_PHASES
#: Two costs inside those phases, each a span of its own so that host
#: time can be put to it (benchmark/harness/tick_host.py reads the self
#: time of every name of both tuples); a reader that knows the six
#: phases alone sees through them:
#:
#: - ``serving.upload`` (``operands=``, ``bytes=``, ``transfers=``): a
#:   program's operands packed into one host buffer by its table's layout
#:   and sent in ONE host->device transfer (``_upload``; one more for an
#:   operand that is not small), inside ``serving.build_inputs`` of a tick
#:   and of a wave alike; what is left of ``serving.build_inputs`` around
#:   it is assembly on the host;
#: - ``serving.account``: host work that exists only to feed a span
#:   argument, a counter, a gauge, a histogram or the cost model — what
#:   the program's own measurement costs with the profiler off.  At most
#:   twice a tick: before the device seam, inside ``serving.build_inputs``
#:   (``_kv_walk``, ``_note_sample_path``, the chunk-queue depth, the
#:   state gauges, a block step's counts: the arguments of
#:   ``serving.decode``), and after it, inside ``serving.advance`` (the
#:   step-latency histogram, ``_perf_tick``, ``_note_model_counters``,
#:   the ``serving.diffusion.*`` counters); once a wave, before
#:   ``serving.prefill`` opens (its ``sample_path``, ``kv_blocks``,
#:   ``kv_walk``).
TICK_COSTS = ("serving.upload", "serving.account")
_UPLOAD, _ACCOUNT = TICK_COSTS

# The two families of device program, as ``ops._dispatch.program_part``
# leads the names of the Pallas kernels built inside their parts
# (``_step_impl_decode_rows_flash_decode``, ...).  The spelling is the
# one the device trace had before kernels carried a name, when XLA named
# a kernel after the jitted ``_*step_impl*`` / ``_prefill_impl*``
# function it sat in: the benchmark's accepted readers find the step
# programs' kernels by ``_\w*step_impl\w*`` on that name.
_STEP, _PREFILL = "_step_impl", "_prefill_impl"
# what a cursor engine's rows-alone step program keeps of the chunk part
_STUB_CHUNK = 8

# engine instances share the default registry; the ``engine`` label keeps
# their series (and retrace budgets) independent
_ENGINE_IDS = itertools.count()


# From this bucket on a prompt is prefilled alone, one row a program call:
# the tokens at which a bf16 matrix product stops being bound by its
# weights' stream and starts being bound by compute (peak FLOP/s over twice
# the bytes/s: 240 on a TPU v5e, 229 on a v4, 166 on a v5p), as a bucket.
# Below it the rows of a wave share one stream of the weights, so padding a
# wave to ``prefill_batch`` rows costs little and batching several short
# prompts is a gain; from it on a call's time goes by its positions (PERF.md
# section 5, PR 41: one row of 256 takes what four of 64 do, one of 512 what
# four of 128 do), so a dummy row costs what a real one does — four fifths
# of what the waves of ``mistral-7b.decode-saturated`` computed — and
# batching real ones buys nothing.  A constant of the hardware family, not
# a knob.
_ROW_FILLS_CHIP = 256


# The rows from which a run of leading table columns is walked ONCE for the
# rows that hold it (``ServingEngine._regroup``): a q tile of stacked rows
# costs the MXU a full tile whatever its fill — about what this many rows'
# own walks cost at one row's heads a tile (PERF.md section 6, PR 43: the
# chip's reading) — so fewer rows on a prefix walk it alone, as before.
_SHARE_FROM = 3


# Admission must have blocked this many consecutive ticks before a waiter
# may preempt a SAME-priority victim (a strictly lower-priority one goes at
# once): guards against churn under transient pressure.
_PREEMPT_AFTER = 2


class _Operand(NamedTuple):
    """One operand of a device program after ``(params, cache)``: a row of
    the engine's operand tables (``ServingEngine._operand_tables``), as
    the program's body sees it."""

    name: str                  # what the program's body calls it
    # a ``None`` is the prefill wave's bucket; a wave's operands lead with
    # the rows its table is for
    shape: Tuple
    dtype: object              # int32, float32, bool, or the typed key's
    # where the host takes the value from: a mirror array of the engine,
    # read as it stands, or the name under which the tick (the wave)
    # hands over a value of its own
    src: object
    fill: int = 0              # the abstract trace's value (``_lint_args``)


# How a table's operands cross to the device (``ServingEngine._upload``):
# as ONE buffer of 32-bit words that the program takes apart in its first
# lines (``_unpack``).  int32 rides as it is, float32 as its bits, a bool
# as a word compared with 0, and the key as the base key's words and the
# tick's number, which the program folds together itself (the base key is
# data and no constant of the program: a constant would put the engine's
# seed into the program's text, and with it into its place in the compile
# cache).  Each operand starts on a lane tile, so the program's slices are
# aligned views.  An operand that is not small (the spec engine's
# ``draft_probs``, megabytes at a real vocabulary) would cost in the
# staging copy what its transfer costs, so one over ``_OWN_TRANSFER_BYTES``
# at the wave's largest bucket keeps a transfer and a place in the
# signature of its own, after the buffer.
_ALIGN = 128
_OWN_TRANSFER_BYTES = 1 << 20
_put = jax.device_put          # the one host->device call of the engine


class _Layout(NamedTuple):
    """Where each operand of a table lies in the packed buffer
    (``_lay_out``)."""

    packed: Tuple              # (operand, offset in words)
    own: Tuple                 # the operands with a transfer of their own
    words: int                 # the buffer's length at bucket 0 ...
    a_token: int               # ... and what a token of the bucket adds

    def nbytes(self, bucket: int = 0) -> int:
        """What one upload moves, the buffer's padding included."""
        return 4 * (self.words + self.a_token * bucket) + sum(
            np.dtype(o.dtype).itemsize
            * math.prod(bucket if d is None else d for d in o.shape)
            for o in self.own)


def _is_key(dtype) -> bool:
    return jnp.issubdtype(dtype, jax.dtypes.prng_key)


def _lay_out(table, largest: int) -> _Layout:
    """The layout of ``table``: its small operands of fixed shape in the
    table's order, each from a multiple of ``_ALIGN`` words on, then the
    one whose shape holds the wave's bucket (``ids``), which takes what is
    left of the buffer — so the offsets are the same at every bucket, and
    the buffer's length states the bucket to the program compiled for it.
    ``largest`` is the bucket that decides which operands are not small: a
    program has one signature whatever the bucket."""
    def words(o, bucket):
        if _is_key(o.dtype):        # the base key's words, then the tick
            return o.dtype.itemsize // 4 + 1
        return math.prod(bucket if d is None else d for d in o.shape)

    def small(o):
        return (_is_key(o.dtype)
                or 4 * words(o, largest) <= _OWN_TRANSFER_BYTES)

    def bucketed(o):
        return None in o.shape

    at, packed = 0, []
    for o in sorted(filter(small, table), key=bucketed):        # stable
        packed.append((o, at))
        at += -(-words(o, 0) // _ALIGN) * _ALIGN
    rest = [words(o, 1) for o, _ in packed if bucketed(o)]
    assert len(rest) <= 1, packed         # one may take "what is left"
    return _Layout(tuple(packed),
                   tuple(o for o in table if not small(o)), at, sum(rest))


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs.  These become traced (num_slots,)
    vectors inside the step function, so any mixture across the batch
    reuses the one compiled program.  Conventions: ``temperature <= 0``
    ⇒ greedy; ``top_k == 0`` ⇒ no top-k; ``top_p == 1.0`` ⇒ no top-p.
    ``unmask_strategy`` / ``unmask_threshold`` are read by a
    block-diffusion model's engine alone (``models.generation
    .unmask_block``: ``"low_confidence_dynamic"`` |
    ``"low_confidence_static"``, and the dynamic rule's confidence
    threshold); None ⇒ the model's own default."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    unmask_strategy: Optional[str] = None
    unmask_threshold: Optional[float] = None


class _Rejected(Exception):
    """Internal admission rejection: pairs the user-facing ValueError
    message with a stable machine-readable reason for the lifecycle
    log (``rejected`` event / ``slo_violations{kind="rejected"}``)."""

    def __init__(self, reason: str, msg: str):
        super().__init__(msg)
        self.reason = reason


@dataclasses.dataclass(eq=False)
class Request:
    """A queued generation request (created by ``submit``).  Identity
    equality (``eq=False``): scheduler queues remove entries by object
    identity, and the numpy ``prompt`` field has no scalar ``==``."""

    request_id: int
    prompt: np.ndarray                 # (plen,) int32
    max_new_tokens: int
    sampling: SamplingParams
    t_submit: float = 0.0              # perf_counter at submit (SLO clock)
    uid: int = -1                      # RequestLog correlation uid
    t_admit: float = 0.0               # perf_counter at admission
    ttft_slo_ms: float = 0.0           # deadlines recorded at submit;
    tpot_slo_ms: float = 0.0           # 0 = that deadline disabled
    blocked_ticks: int = 0             # pool-full admission deferrals
    defer_ticks: int = 0               # predictive-admission deferrals
    priority: int = 0                  # preemption class (higher wins)
    preempt_count: int = 0             # times this request was preempted
    # per-request drafter override (spec mode): 'ngram' | 'model' | a
    # Drafter instance | None = the engine default
    drafter: Optional[object] = None
    # recompute-resume marker: set ONLY on the synthetic re-prefill
    # request a recompute preemption enqueues (see _do_preempt)
    resume: Optional["_ResumeInfo"] = None


@dataclasses.dataclass
class _Slot:
    rid: int
    remaining: int                     # new tokens still allowed
    t_first: float = 0.0               # perf_counter at first token (TPOT)
    # the request's prompt — the self-drafter's lookup corpus (spec mode)
    prompt: Optional[np.ndarray] = None
    # the originating request — retirement reads its uid + SLO deadlines
    req: Optional[Request] = None
    # block diffusion (``ServingEngine``, "Block diffusion"): the forwards
    # the slot's current block has had, per position the forward at which
    # it was unmasked (0: given by the prompt), and how many of the block's
    # leading positions the prompt gave
    forwards: int = 0
    unmasked_at: Optional[np.ndarray] = None
    given: int = 0


@dataclasses.dataclass
class _Prefill:
    """A partially-prefilled request (chunked mode): admitted to a slot,
    its prompt streaming into the cache one chunk per mixed step."""

    req: Request
    slot: int
    cursor: int                        # prompt tokens already in the cache
    end: int = 0                       # prompt tokens the cursor ingests


@dataclasses.dataclass
class _ResumeInfo:
    """Recompute-resume bookkeeping, attached to the synthetic request a
    recompute preemption enqueues: the re-prefill covers the original
    prompt plus every committed token but the last; at slot re-creation
    the re-sampled token is DISCARDED and ``last_token`` forced back, so
    the resumed decode continues exactly where the victim stopped."""

    orig: Request                      # the preempted request
    last_token: int                    # last committed token (forced back)
    remaining: int                     # decode budget left at preemption
    t_first: float                     # original TTFT clock (preserved)


@dataclasses.dataclass
class _SwapResume:
    """A swapped-out (preempted) request parked on the host tier: the
    BlockManager swap record plus the exact host-mirror state needed to
    restore the slot bit-for-bit once pool space frees up."""

    req: Request
    record: Dict[str, object]          # BlockManager.swap_out record
    last_token: int
    position: int
    remaining: int
    t_first: float
    blocked_ticks: int = 0             # failed resume attempts


class ServingEngine:
    """Continuous-batching serving over a causal LM with the stacked KV
    cache (``decode_parts`` + ``init_kv_cache`` layout; plain or
    ``quantize_for_decode``-wrapped models both work).

    ``submit()`` enqueues, ``step()`` runs one scheduler tick (admit →
    one jitted decode step → retire), ``drain()`` runs ticks until every
    request is finished and returns outputs in arrival order.
    """

    # the bucket from which a prompt is prefilled alone (``_wave_rows``)
    _lone_from = _ROW_FILLS_CHIP

    def __init__(self, model, num_slots: int = 8, max_length: int = 1024,
                 eos_token_id: Optional[int] = None, pad_token_id: int = 0,
                 prefill_batch: int = 4, seed: int = 0,
                 paged: bool = False,
                 block_len: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 chunked: Optional[bool] = None,
                 prefill_chunk: int = 256,
                 chunk_policy: str = "prefill",
                 spec_decode: bool = False,
                 spec_k: int = 4,
                 kv_cache_dtype: Optional[str] = None,
                 int8_weights: Optional[bool] = None,
                 mesh=None,
                 preempt: str = "off",
                 host_blocks: int = 0,
                 drafter="ngram",
                 draft_model=None):
        """``prefill_batch``: the most requests a prefill wave admits, and
        the rows a wave of short prompts is padded to; a prompt whose bucket
        is ``_ROW_FILLS_CHIP`` positions or more is prefilled alone, in a
        one-row program, whatever this says.

        ``paged`` selects the paged block-pool cache; ``block_len``
        (FLAGS_kv_cache_block_len) and ``num_blocks``
        (FLAGS_kv_cache_num_blocks; 0 derives the contiguous cache's
        footprint, num_slots·max_length/block_len, plus the null block)
        size it; ``prefix_cache`` toggles prompt-prefix sharing.

        ``chunked`` (default FLAGS_serving_chunked_prefill) selects
        chunked-prefill admission: prompts are split into
        ``prefill_chunk``-token chunks folded into the ONE mixed decode
        step, so a long prompt never stalls in-flight decodes for a
        whole-prompt prefill; ``chunk_policy``: 'prefill' runs a
        pending chunk every tick, 'decode' interleaves chunks with
        chunk-free ticks while decodes are active (TPOT protection at
        half the prompt-ingest rate).

        ``spec_decode`` selects speculative decoding: a drafter proposes
        up to ``spec_k`` tokens per slot per tick and one verify
        step commits the longest accepted prefix — greedy outputs
        token-identical to plain decode, sampled rows exact under
        rejection sampling, 1..k+1 tokens per step.  Composes with
        every cache layout and with chunked prefill (the verify window
        replaces the mixed step's decode half).

        ``drafter`` picks the proposer: ``'ngram'`` (host-side prompt
        lookup), ``'model'`` (a draft model sharing the engine — see
        ``draft_model``), or a
        :class:`~paddle_tpu.serving.drafter.Drafter` instance.
        ``draft_model``: the draft model for kind ``'model'`` — a
        ``(model, params)`` pair, a bare model (its own state_dict is
        taken), or ``None`` for self-drafting with the TARGET model
        (zero extra weights; the acceptance-rate ceiling).
        ``submit(drafter=...)`` overrides per request, so one engine
        can mix drafter kinds across its slot batch.

        ``mesh`` (default FLAGS_serving_mesh) makes the engine
        MESH-NATIVE — the tensor-parallel execution path of ROADMAP
        item 1: a jax ``Mesh``, a ``HybridCommunicateGroup``, or a
        compact axis string like ``"mp2dp2"`` (resolved over the first
        matching prefix of ``jax.devices()``).  Params and the KV cache
        are placed per :func:`decode_mesh_specs` at construction
        (vocab-parallel lm_head on ``mp``, cache kv-heads mp-sharded —
        the paged block pool shards ONLY the head dim, so block tables
        stay per-replica logical and the BlockManager is untouched),
        and every step/prefill program is jitted ONCE with DECLARED
        ``in_shardings``/``out_shardings`` and the cache still donated.
        The Pallas decode kernel is gated off under a mesh (the XLA
        gather path partitions under GSPMD; see
        ``ops.attention._mesh_sharded_trace``); greedy outputs stay
        token-identical to the single-chip engine in every layout.

        ``kv_cache_dtype`` (default FLAGS_serving_kv_cache_dtype):
        ``'bf16'`` keeps the model-dtype cache; ``'int8'`` stores K/V as
        int8 with per-block(-granule)-per-kv-head symmetric scales —
        quantized at scatter time inside the step, dequantized inside
        the flash-decode chunk loop — halving the cache footprint and
        the per-step streamed cache bytes; ``'mixed'`` (paged only)
        writes blocks bf16 and demotes them to simulated int8 (an
        in-place quantize→dequantize device rewrite) when they register
        as cold full prefix blocks.  ``int8_weights`` (default
        FLAGS_serving_int8_weights) wraps the model with
        ``quantize_for_decode`` so the engine's linear layers run the
        weight-only int8 path.  Both compose with every layout above;
        every program stays jitted once."""
        limit = getattr(model.config, "max_position_embeddings", None)
        if limit is not None and max_length > limit:
            raise ValueError(
                f"max_length {max_length} exceeds the model's "
                f"max_position_embeddings ({limit})")
        self._int8_weights = bool(
            _flags.flag("serving_int8_weights")
            if int8_weights is None else int8_weights)
        if self._int8_weights and not hasattr(model, "unwrapped"):
            from ..models.quantized import quantize_for_decode
            model = quantize_for_decode(model)
        self.model = model
        self.config = model.config
        self.num_slots = int(num_slots)
        self.max_length = int(max_length)
        self.eos_token_id = eos_token_id
        self.pad_token_id = int(pad_token_id)
        self.prefill_batch = int(prefill_batch)
        self.paged = bool(paged)
        self.kv_dtype = str(kv_cache_dtype
                            or _flags.flag("serving_kv_cache_dtype"))
        if self.kv_dtype not in ("bf16", "int8", "mixed"):
            raise ValueError(
                f"kv_cache_dtype must be bf16|int8|mixed, got "
                f"{self.kv_dtype!r}")
        if self.kv_dtype == "mixed" and not self.paged:
            raise ValueError(
                "kv_cache_dtype='mixed' requires the paged cache: "
                "demotion is per-block, and contiguous rows have no "
                "block registration point")
        # 'int8' quantizes the DEVICE pool (dict cache, scales as step
        # operands); 'mixed' keeps the device pool bf16 and simulates
        # int8 per demoted block, so only 'int8' changes program shapes
        self.quantized = self.kv_dtype == "int8"
        self.chunked = bool(_flags.flag("serving_chunked_prefill")
                            if chunked is None else chunked)
        self.prefill_chunk = int(prefill_chunk)
        self._chunk_policy = str(chunk_policy)
        if self._chunk_policy not in ("prefill", "decode"):
            raise ValueError(
                f"chunk_policy must be 'prefill' or 'decode', got "
                f"{self._chunk_policy!r}")
        if self.chunked and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        self.spec = bool(spec_decode)
        self.spec_k = int(spec_k)
        if self.spec and self.spec_k < 1:
            raise ValueError(
                f"spec_k must be >= 1, got {self.spec_k}")
        # drafter construction is deferred past param placement (the
        # draft-model drafter aliases the PLACED params for self-draft)
        self._drafter_arg = drafter
        self._draft_model_arg = draft_model
        self._drafters: Dict[str, object] = {}
        self._drafter = None
        # preemptive scheduling + host KV tier (ISSUE 16).  'swap'
        # parks a victim's private blocks on the pinned host pool and
        # restores them verbatim; 'recompute' frees the chain and
        # re-prefills prompt+committed tokens through the prefix trie.
        # Both are host-side pool surgery + block-table updates — the
        # once-jitted step never sees a new trace.
        self._init_preempt(preempt, host_blocks)
        self.mesh = self._resolve_mesh(mesh)
        # quantized-decode hooks, exactly as models/generation.py binds
        self._bind = getattr(model, "unwrapped", model)
        # the contract: ``models.parts.ServingTraits``
        self._bind_traits(getattr(self._bind, "serving_traits",
                                  ServingTraits()))
        if (hasattr(self._bind, "init_decode_state")
                and not self._slot_leaves):
            raise NotImplementedError(
                f"{type(self._bind).__name__} cannot be served: it keeps a "
                f"decode state of its own (init_decode_state) and does not "
                f"declare it as serving state — slot_state (the leaves that "
                f"are fixed-size per slot) and init_serving_cache (the "
                f"paged pool and those leaves for N slots)")
        self._refuse_unsupported(self.paged and bool(prefix_cache))
        if self._block and (self.prefill_chunk % self._block
                            or self.max_length % self._block):
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} and max_length "
                f"{self.max_length} must be multiples of the model's block "
                f"of {self._block}: a chunk and a cache hold whole blocks")
        self._init_metrics()
        self._init_scheduler_state()

        self._prepare = getattr(model, "_prepare_params", lambda p: p)
        params = model.state_dict(include_buffers=True)
        # the layers that hold K/V: all of them, or the paged leaf's own
        # count where the model makes its serving cache itself
        self._kv_layers = int(model.config.num_hidden_layers)
        if self.paged:
            nb, bl = self._init_pool(block_len, num_blocks, prefix_cache)
            if self._block and bl % self._block:
                raise ValueError(
                    f"block_len {bl} is no multiple of the model's block "
                    f"of {self._block}")
            if self._slot_leaves:
                # one row a slot and a null row, which a chunk-free tick's
                # stub of a chunk part addresses
                cache = self._traits.init_serving_cache(self.num_slots + 1,
                                                        nb, bl)
                (pool,) = (v for k, v in cache.items()
                           if k not in self._slot_leaves)
                self._kv_layers = int(pool.shape[0])
            else:
                cache = init_paged_kv_cache(model.config, nb, bl,
                                            quantized=self.quantized,
                                            entry=self._pool_entry)
            # arm the pool's bytes_by_dtype gauges with this model's
            # per-block costs (payload + the int8 block's scale row)
            c = model.config
            native = jnp.zeros((), c.dtype).dtype.itemsize
            if self._pool_entry is not None:
                # a declared entry: its arrays and stored width a position
                self._position_bytes = (
                    self._kv_layers * self._pool_entry.arrays
                    * self._pool_entry.width * native)
                self.kv.set_block_nbytes(
                    {"bf16": self._position_bytes * bl})
                self._m_pool_position_bytes.set(float(self._position_bytes))
            else:
                tok = (self._kv_layers * 2 * c.num_key_value_heads
                       * c.head_dim)
                self.kv.set_block_nbytes({
                    "bf16": tok * bl * native,
                    "int8": tok * bl
                    + self._kv_layers * 2 * c.num_key_value_heads * 4})
        else:
            cache = init_kv_cache(model.config, self.num_slots,
                                  self.max_length,
                                  quantized=self.quantized)
        self._init_kv_walk()
        params, cache, _ = _place_on_mesh(
            self._bind, params, cache,
            jnp.zeros((self.num_slots, 1), jnp.int32),
            paged_cache=self.paged, mesh=self.mesh)
        self._params, self._cache = params, cache
        if self._slot_leaves:
            self._m_state_rows.set(float(self.num_slots + 1))
            self._m_state_bytes.set(float(sum(
                cache[k].nbytes for k in self._slot_leaves)))
        if self.spec:
            self._drafter = self._make_drafter(self._drafter_arg)
            self._drafters[getattr(self._drafter, "kind", "custom")] = \
                self._drafter
        def pool_program(impl, site, n_args):
            # one of the cache's own small programs: the cache first and
            # donated, keeping its declared sharding under a mesh
            return _obs.track_retraces(
                impl, site, labels={"engine": self._eid},
                donate_argnums=(0,),
                **(self._mesh_jit_shardings(n_args, 1, cache_argnum=0,
                                            with_params=False)
                   if self.mesh is not None else {}))
        if self.paged:
            # COW device copy (compiled once; only dispatched when a
            # shared block is about to be written — see kv_cache.py).
            # The pool is donated: the copy aliases it in place.  Under
            # a mesh the pool keeps its declared sharding through the
            # copy (the block axis is unsharded, so a block copy never
            # crosses devices).  The int8 pool copies the block's scale
            # row along with its payload — COW destinations inherit the
            # source's live quantization scale.
            if self.quantized:
                def _cow_impl(c, src, dst):
                    return {
                        "kv": c["kv"].at[:, :, dst].set(c["kv"][:, :, src]),
                        "scale": c["scale"].at[:, :, dst].set(
                            c["scale"][:, :, src])}
            else:
                def _cow_impl(c, src, dst):
                    return c.at[:, :, dst].set(c[:, :, src])
            self._cow_fn = pool_program(_cow_impl, "serving.cow", 3)
        if self.paged and self.quantized:
            # a reused block carries its previous tenant's scale row; the
            # running-max write path would inherit it and quantize the
            # new tenant too coarsely, so every block newly appended to a
            # chain (BlockManager.drain_fresh) gets its scale zeroed
            # before the next dispatch.  Mask form: one static shape, one
            # compile, and the scale tensor is tiny.
            def _reset_impl(c, mask):
                return {"kv": c["kv"],
                        "scale": jnp.where(mask[None, None, :, None],
                                           jnp.float32(0), c["scale"])}
            self._scale_reset_fn = pool_program(
                _reset_impl, "serving.scale_reset", 2)
        if not self.paged and self.quantized:
            # contiguous slot reuse (chunked admission writes into a row
            # a retired request used): zero the row's granule scales
            def _row_reset_impl(c, slot):
                return {"kv": c["kv"],
                        "scale": c["scale"].at[:, :, slot].set(0.0)}
            self._row_reset_fn = pool_program(
                _row_reset_impl, "serving.scale_reset", 2)
        if self.paged and self.kv_dtype == "mixed":
            # mixed mode: the pool stays bf16 (plain array, plain step
            # programs) and a block demoted by the BlockManager — cold
            # full prefix block at trie registration — is rewritten
            # in place through a quantize→dequantize round trip
            # (simulated int8: the precision of the quantized store, the
            # layout of the hot path).  Applied AFTER the dispatch that
            # writes the block's contents (registration precedes the
            # wave-prefill dispatch), via the _pending_demote queue.
            hkv = int(model.config.num_key_value_heads)

            def _demote_impl(c, bid):
                flat = c[:, :, bid]                     # (L,2,bl,Hkv·D)
                # per-kv-head absmax: heads apart on this one block
                blk = flat.astype(jnp.float32).reshape(
                    flat.shape[:3] + (hkv, -1))
                sc = jnp.max(jnp.abs(blk), axis=(2, 4),
                             keepdims=True) / 127.0
                safe = jnp.where(sc > 0, sc, 1.0)
                q = jnp.clip(jnp.round(blk / safe), -127, 127)
                return c.at[:, :, bid].set(
                    (q * safe).astype(c.dtype).reshape(flat.shape))
            self._demote_fn = pool_program(
                _demote_impl, "serving.demote", 2)
            self.kv.on_demote = self._pending_demote.extend
        if self.paged:
            # block movers are built on first use (_block_movers): the
            # host tier's swap hooks AND the ISSUE-18 export/import
            # migration path share them, but an engine that never swaps
            # or migrates must not spend two jit.traces counter children
            # on them (per-engine label cardinality is capped)
            self._read_block_fn = None
            self._write_block_fn = None
            if self._host_blocks > 0:
                self.kv.on_swap_out = self._host_swap_out
                self.kv.on_swap_in = self._host_swap_in

        self._base_key = jax.random.key(seed)
        # its words as the packed buffer carries them (``_pack``)
        self._key_bits = np.asarray(
            jax.random.key_data(self._base_key)).view(np.int32).reshape(-1)
        # the scheduler's time source: every SLO stamp (t_submit,
        # queue-wait, TTFT, TPOT) reads through this indirection, so the
        # fleet simulator (serving/fleet_sim.py) can drive the SAME
        # scheduler with a cost-model clock instead of the wall
        self._clock = time.perf_counter
        # trace accounting rides the retrace watchdog
        # (observability/watchdog.py): the wrapper counts compilations —
        # python side effects fire at TRACE time only — into the shared
        # registry and BUDGETS them; the step function's budget of 1 is
        # the continuous-batching contract itself, enforced at the
        # moment a retrace happens instead of asserted after the fact.
        # ``step_traces``/``prefill_traces`` read through to the counters.
        lbl = {"engine": self._eid}
        # every step/prefill program takes the FULL cache as operand 1
        # and returns it: donating that operand lets XLA alias the
        # buffers in place, so a tick keeps ONE cache resident instead
        # of double-buffering the dominant HBM consumer (the engine
        # rebinds self._cache from the output immediately, so the
        # donated input is never read again).  The graph-lint donation
        # rule (paddle_tpu/static_analysis) verifies this stays true.
        donate = {"donate_argnums": (1,)}
        # mesh mode: the SAME once-jitted programs, now with DECLARED
        # shardings — params/cache per decode_mesh_specs, the packed
        # buffer of operands (token/position/mask vectors, block tables,
        # the tick's number) replicated, tokens replicated on the way out
        # and the cache keeping its spec.  Declaring both sides keeps the
        # donated cache aliasable in place (in/out layouts provably
        # match) and makes the step's sharding contract the same one
        # mesh_preflight lints abstractly.
        self._step_table, self._wave_tables = self._operand_tables()
        # the wave padded to ``prefill_batch`` rows: the table every wave
        # had before PR 41, and the one whose buffer crosses flat
        self._prefill_table = self._wave_tables.get(self.prefill_batch)
        # each table's layout in the packed buffer, computed once
        self._step_layout = _lay_out(self._step_table, self.max_length)
        # (the two tables are ONE program's signature, which a mesh engine
        # declares its shardings by: what is small is decided for both as
        # for ``prefill_batch`` rows)
        self._wave_layouts = {
            rows: _lay_out(table,
                           self.max_length * self.prefill_batch // rows)
            for rows, table in self._wave_tables.items()}
        # what ``serving.dispatch`` states: the leaves a call flattens
        self._program_leaves = len(jax.tree_util.tree_leaves(
            (self._params, self._cache)))
        self._step_outputs = (
            ("tokens",) + ("n_acc",) * self.spec
            + ("n_unmasked",) * bool(self._block)
            + ("chunk_token",) * self.chunked
            + ("expert_load",) * bool(self._expert_layers) + ("cache",))

        def program(body, table, outputs, site, budget):
            kwargs = dict(donate)
            if self.mesh is not None:
                kwargs.update(self._mesh_jit_shardings(
                    self._program_arity(table), len(outputs)))
            return _obs.track_retraces(self._under_mesh(body), site,
                                       budget=budget, labels=lbl, **kwargs)
        # ONE step program serves every tick with the same parts.  The
        # budget of 1 IS the scheduler's contract: admission, chunk
        # progress, drafts and retirement all move through traced inputs.
        # A cursor engine has one more for the ticks with no chunk, the
        # same body with the chunk part cut to a stub, under the same
        # contract; a wave engine has its prefill program, compiled once
        # per bucket.
        self._step_fn = program(self._step_program(), self._step_table,
                                self._step_outputs, "serving.step", 1)
        self._rows_fn = program(
            self._step_program(rows_alone=True), self._step_table,
            self._step_outputs, "serving.step_rows", 1) \
            if self.chunked else None
        # Its budget is no knob: one program a bucket ``max_length`` allows.
        self._prefill_fn = None if self.chunked else program(
            self._prefill_program(), self._prefill_table,
            ("tokens", "cache"), "serving.prefill",
            len(self._wave_buckets()))
        self._linted = False           # first-tick self-lint (graph_lint)
        # per-tick roofline cost model (ISSUE 15): predictions are
        # memoized host math, so the steady-state tick pays a dict
        # lookup; FLAGS_perf_model 'off' skips the layer entirely
        self._perf = (self._build_perf_model()
                      if _flags.flag("perf_model") == "on" else None)

    def _bind_traits(self, traits: ServingTraits):
        """What the model declares (the simulator, which has none: the
        defaults), under the names the scheduler reads it by."""
        self._traits = traits
        self._pool_entry = traits.pool_entry
        self._diffusion = traits.block_diffusion
        # the block length (0: every row's tick is one token)
        self._block = int(self._diffusion.length) if self._diffusion else 0
        self._slot_leaves = tuple(traits.slot_state)
        self._expert_layers = int(traits.expert_layers)
        self._windows = tuple(int(w) for w in traits.attention_windows
                              if w is not None)

    def _refuse_unsupported(self, prefix_cache: bool):
        """Refuse, by name, the layout this engine was asked for where the
        model lists it as ``unsupported``: the first in the model's order."""
        asked = {
            "contiguous_cache": (not self.paged,
                                 "the contiguous cache (paged=False)"),
            "wave_prefill": (not self.chunked,
                             "wave prefill (chunked=False)"),
            "prefix_cache": (prefix_cache,
                             "a prefix cache (prefix_cache=True)"),
            "preemption": (self.preempt != "off" or self._host_blocks,
                           f"preempt={self.preempt!r} / "
                           f"host_blocks={self._host_blocks}"),
            "kv_cache_dtype": (self.kv_dtype != "bf16",
                               f"kv_cache_dtype={self.kv_dtype!r}"),
            "mesh": (self.mesh is not None, "a mesh"),
            "spec_decode": (self.spec, "speculative decoding"),
            "int8_weights": (self._int8_weights, "int8_weights"),
        }
        who = type(self._bind).__name__
        unknown = set(self._traits.unsupported) - set(asked)
        if unknown:
            raise ValueError(
                f"{who}.serving_traits.unsupported names no layout of the "
                f"engine's: {sorted(unknown)} (known: {sorted(asked)})")
        for layout, why in self._traits.unsupported.items():
            refused, what = asked[layout]
            if refused:
                raise NotImplementedError(
                    f"{who} cannot be served with {what}: {why}")

    def _init_preempt(self, preempt: str, host_blocks: int):
        self.preempt = str(preempt)
        if self.preempt not in ("off", "swap", "recompute"):
            raise ValueError(
                f"preempt must be off|swap|recompute, got "
                f"{self.preempt!r}")
        if self.preempt != "off" and not self.paged:
            raise ValueError(
                "preemption requires the paged cache: victim block free "
                "and swap/recompute resume are BlockManager operations")
        hb = int(host_blocks)
        if self.preempt == "swap" and hb < 1:
            raise ValueError(
                "preempt='swap' needs a host tier: pass host_blocks >= 1")
        self._host_blocks = hb if self.paged else 0

    def _init_pool(self, block_len, num_blocks, prefix_cache):
        """The paged cache's host side: the BlockManager and the slots'
        block tables.  Returns (blocks in the pool, block length)."""
        bl = int(block_len or _flags.flag("kv_cache_block_len"))
        if self.max_length % bl:
            raise ValueError(
                f"max_length {self.max_length} is not a multiple of "
                f"block_len {bl}")
        self.block_len = bl
        self.max_blocks = self.max_length // bl
        nb = int(num_blocks or _flags.flag("kv_cache_num_blocks")
                 or self.num_slots * self.max_blocks + 1)
        self.kv = BlockManager(
            nb, bl,
            prefix_cache=bool(prefix_cache),
            kv_dtype=self.kv_dtype,
            host_blocks=self._host_blocks)
        self._tables = np.zeros((self.num_slots, self.max_blocks), np.int32)
        self._init_shared_walk()
        return nb, bl

    def _init_shared_walk(self):
        """The mirrors of a two-part walk (``ops.pallas.decode_attention
        .SharedWalk``: four more rows of the step program's operand table),
        for a pool whose declared layout has one, read through a prefix
        trie: without the trie no two rows hold one block.  As many tiles
        as the slots can ever fill from ``_SHARE_FROM`` rows on."""
        from ..ops.pallas.decode_attention import (LatentLayout, SharedWalk,
                                                   tile_members)
        layout = getattr(self._pool_entry, "layout", None)
        if not (self.kv.prefix_cache and isinstance(layout, LatentLayout)):
            return
        tiles = max(1, self.num_slots // _SHARE_FROM)
        members = tile_members(layout, self._pool_entry.group)
        self._share = SharedWalk(*(
            np.zeros(shape, np.int32) for shape in (
                (self.num_slots,), (self.num_slots,), (tiles, members),
                (tiles,))))
        self._regroup()

    def _init_scheduler_state(self):
        """The scheduler's host state, empty (the simulator's too)."""
        # host-side mirrors of the step inputs (tiny; re-uploaded per tick)
        s = self.num_slots
        self._tokens = np.zeros((s,), np.int32)
        self._positions = np.zeros((s,), np.int32)
        self._active = np.zeros((s,), bool)
        self._temps = np.zeros((s,), np.float32)
        self._topk = np.zeros((s,), np.int32)
        self._topp = np.ones((s,), np.float32)
        if self._block:
            # block diffusion: each row's current block (mask id where
            # masked) in place of its one token, and its unmasking rule
            self._blocks = np.full((s, self._block),
                                   self._diffusion.mask_token_id, np.int32)
            self._unmask_n = np.zeros((s,), np.int32)
            self._unmask_thr = np.ones((s,), np.float32)
            # per delivered token, the forward-in-block that unmasked it
            self._unmasked_at: Dict[int, List[int]] = {}

        # which decoding rows walk which leading columns together, where
        # the pool's layout has such a walk (``_init_shared_walk``), and
        # whether the rows changed since it was worked out (``_regroup``)
        self._share = None
        self._share_stale = False
        self._slots: List[Optional[_Slot]] = [None] * s
        self._prefill: Optional[_Prefill] = None   # chunked-mode cursor
        self._queue: Deque[Request] = deque()
        # the chunks the two queues' prompts will take under the cursor,
        # kept where a request enters or leaves one (``_pending_chunks``)
        self._queued_chunks = 0
        # preempted work awaiting resume, each kept sorted by
        # (-priority, request id) so resume order is deterministic
        self._swap_resume: List[_SwapResume] = []
        self._resume_q: Deque[Request] = deque()
        # every preemption decision, in order — preempt_signature()
        # hashes this list, the loadgen saturated gate replays it
        self._preempt_log: List[Dict[str, object]] = []
        self._results: Dict[int, List[int]] = {}
        self._next_rid = 0
        self._ticks = 0
        self._tick_swap_bytes = 0      # host<->HBM bytes moved this tick
        self._pending_demote: List[int] = []
        self._kernel_preflight_cache = None  # memoized kernel_preflight()

    # -- cost model / perf attribution (ISSUE 15) --------------------------

    def _build_perf_model(self):
        """Compose the existing static models into the tick roofline:
        the params tree's actual bytes (int8 weights shrink the weight-
        stream term), the pool's dtype-aware per-token KV cost (the
        committed 0.254x int8 streamed-bytes ratio), and — under a mesh
        — comm_report's per-step collective bytes, evaluated lazily
        (one abstract trace) on the first prediction."""
        from ..observability import costmodel as _cm
        leaves = jax.tree_util.tree_leaves(self._params)
        weight_bytes = int(sum(leaf.nbytes for leaf in leaves))
        n_params = int(sum(leaf.size for leaf in leaves))
        # int8 scale amortization granule: the paged pool keeps one
        # scale row per block, the contiguous pool one per 128-token
        # granule (models/generation.init_kv_cache)
        if self._pool_entry is not None:
            # what the walk streams a live position: the entry as stored
            kv_tok = float(self._position_bytes)
        else:
            kv_tok = _cm.kv_bytes_per_token(
                self.config, self.kv_dtype,
                block_len=self.block_len if self.paged else 128,
                num_layers=self._kv_layers)
        comm_fn = None
        if self.mesh is not None:
            def comm_fn():
                comm = self.mesh_preflight()["comm"]
                return int(comm.get("total_bytes_per_step", 0))
        # a decoding row reads and writes its row of every slot leaf
        state_row = 2.0 * sum(
            self._cache[k].nbytes for k in self._slot_leaves) / (
                self.num_slots + 1)
        model = _cm.CostModel(
            _cm.resolve_profile(), weight_bytes=weight_bytes,
            n_params=n_params, kv_token_bytes=kv_tok,
            num_slots=self.num_slots, comm_bytes_fn=comm_fn,
            state_row_bytes=state_row)
        return _cm.TickAttribution(model, engine_id=self._eid)

    def _perf_tick(self, measured_ms: float, occ: int,
                   chunk_tokens: int = 0, compiled: bool = False) -> None:
        """Stamp one measured tick with the model's prediction at the
        tick's ACTUAL occupancy / live depths / chunk state (positions
        are still pre-advance here — the depths the step just read).
        A tick that ``compiled`` the rows-alone program is left out: it
        comes after the detectors' skip of an engine's first ticks, and
        its seconds of compiling would calibrate their band.
        Host↔HBM bytes any swap/demotion moved since the last dispatch
        ride along — the roofline's swap term (costmodel.py) bounds the
        tick by host-link bandwidth when they dominate."""
        swap_bytes, self._tick_swap_bytes = self._tick_swap_bytes, 0
        if self._perf is None or compiled:
            return
        live = int(self._positions[self._active].sum()) if occ else 0
        self._perf.on_tick(
            measured_ms, occ=occ, live_tokens=live,
            chunk_tokens=chunk_tokens,
            window=self._row_tokens, swap_bytes=swap_bytes)

    def perf_report(self) -> Dict[str, object]:
        """Predicted-vs-measured attribution for this engine: per-bound
        tick shares, per-term predicted totals, measured/predicted
        ratio percentiles, drift findings (static_analysis Finding
        shape) and anomaly counts.  The predicted side is a pure
        function of the deterministic schedule — loadgen's smoke gate
        checks it byte-stable across replays via
        observability.perf_signature."""
        if self._perf is None:
            return {"enabled": False}
        return dict(self._perf.report(), enabled=True)

    # -- predictive SLO admission (control plane) --------------------------

    def admission_armed(self) -> bool:
        """True when the predictive gate actively prices admissions on
        this engine: FLAGS_serving_admission is 'predictive', the cost
        model is built (FLAGS_perf_model on), and the model carries no
        drift finding — a model that has left its calibrated band must
        not gate admission (ISSUE 17: fall back conservative)."""
        return (self._perf is not None
                and str(_flags.flag("serving_admission")) == "predictive"
                and not self._perf.has_drift())

    def admission_probe(self, prompt_len: int) -> Optional[Dict[str, float]]:
        """Price admitting ONE more request at this engine's current
        (occupancy, queue depth, chunk backlog) — the control-plane
        placement question the router asks before placing.  Returns the
        predicted post-admission tick time (which is the per-slot TPOT:
        decode emits one token per tick) and a coarse TTFT estimate
        (ticks to drain the backlog ahead, one admission wave per tick,
        times the predicted tick), or None when FLAGS_perf_model is off.
        Predictions are in the cost model's domain — compare against
        wall deadlines through FLAGS_serving_admission_calib."""
        if self._perf is None:
            return None
        occ_now = self.num_active
        backlog = self.queue_depth + self.num_pending + self.num_preempted
        occ_after = min(self.num_slots, occ_now + backlog + 1)
        live = int(self._positions[self._active].sum()) if occ_now else 0
        chunk = (getattr(self, "prefill_chunk", 0)
                 if self.chunked and (backlog or self._prefill is not None)
                 else 0)
        pred = self._perf.model.predicted_tick_ms(
            occ_after, live + int(prompt_len), chunk_tokens=chunk,
            window=self._row_tokens)
        waves = 1 + backlog // max(1, self.prefill_batch)
        return {"predicted_tick_ms": pred,
                "predicted_tpot_ms": pred,
                "predicted_ttft_ms": pred * waves,
                "occupancy_after": float(occ_after),
                "backlog": float(backlog)}

    def _admission_defer(self, req: Request, occ_after: int,
                         live_after: int, chunk_tokens: int = 0) -> bool:
        """The gate itself: True holds ``req`` in the submit queue this
        tick.  Pure function of scheduler state (occupancy, live depth,
        SLO fields, defer age) — NO wall-clock input, so twin replays of
        one trace make byte-identical decisions.  Never defers into an
        empty engine (progress guarantee), never defers a recompute
        resume (its admission was already paid before preemption), and
        ages out after FLAGS_serving_admission_max_defer_ticks."""
        if req.resume is not None or not self.admission_armed():
            return False
        if occ_after <= 1:
            return False
        maxd = int(_flags.flag("serving_admission_max_defer_ticks"))
        if maxd > 0 and req.defer_ticks >= maxd:
            return False
        # the pooled guard: the tightest TPOT deadline among running
        # slots and the candidate itself — admitting a deadline-free
        # batch request must not blow a resident interactive SLO
        guards = [s.req.tpot_slo_ms for s in self._slots
                  if s is not None and s.req is not None
                  and s.req.tpot_slo_ms > 0]
        if req.tpot_slo_ms > 0:
            guards.append(req.tpot_slo_ms)
        if not guards:
            return False
        pred = self._perf.model.predicted_tick_ms(
            occ_after, live_after, chunk_tokens=chunk_tokens,
            window=self._row_tokens)
        calib = float(_flags.flag("serving_admission_calib"))
        slack = float(_flags.flag("serving_admission_slack"))
        return pred * calib > min(guards) * slack

    def _defer(self, req: Request) -> None:
        """Account one predictive deferral: the submit queue IS the
        engine-level hold queue (head-of-line order preserved), the
        request just does not enter a slot this tick."""
        req.defer_ticks += 1
        self._m_deferred.inc()
        self._tracer.instant("serving.admission_deferred",
                             rid=req.request_id)
        if req.defer_ticks == 1:
            self._rlog.event(req.uid, "admission_deferred",
                             engine=self._eid, reason="predicted_slo")

    # -- mesh execution (ISSUE 9) ------------------------------------------

    @staticmethod
    def _resolve_mesh(mesh):
        """Normalise the ``mesh`` constructor argument to a concrete jax
        ``Mesh`` or ``None`` (single-chip): ``None`` consults
        FLAGS_serving_mesh; a ``HybridCommunicateGroup`` contributes its
        mesh; a compact axis string like ``"mp2dp2"`` is laid over the
        first matching prefix of ``jax.devices()``.  An all-ones mesh
        collapses to ``None`` — placement would be a no-op."""
        if mesh is None:
            mesh = str(_flags.flag("serving_mesh"))
        if mesh is None or mesh == "":
            return None
        m = getattr(mesh, "mesh", mesh)        # HybridCommunicateGroup
        if isinstance(m, str):
            from jax.sharding import Mesh

            from ..static_analysis import MeshInfo
            minfo = MeshInfo.of(m)
            shape = tuple(n for _, n in minfo.axes)
            need = int(np.prod(shape))
            devs = jax.devices()
            if need > len(devs):
                raise ValueError(
                    f"mesh {m!r} needs {need} devices; only "
                    f"{len(devs)} available on this host")
            m = Mesh(np.asarray(devs[:need]).reshape(shape), minfo.names)
        if all(m.shape[a] == 1 for a in m.axis_names):
            return None
        return m

    def _make_drafter(self, sel):
        """Build a drafter from a selector: a Drafter instance passes
        through; ``'ngram'``/``'model'`` build the corresponding
        proposer (the model drafter aliases the engine's placed params
        when no ``draft_model`` was given — self-drafting)."""
        if not isinstance(sel, str):
            return sel
        if sel == "ngram":
            return NgramDrafter(self.spec_k)
        if sel == "model":
            src = self._draft_model_arg
            if src is None:
                dm, dp = self.model, self._params
            elif isinstance(src, (tuple, list)):
                dm, dp = src
            else:
                dm, dp = src, src.state_dict(include_buffers=True)
            return DraftModelDrafter(
                self.spec_k, dm, dp, self.num_slots, self.max_length,
                pad_token_id=self.pad_token_id, mesh=self.mesh,
                engine_id=self._eid)
        raise ValueError(
            f"drafter must be 'ngram', 'model' or a Drafter instance, "
            f"got {sel!r}")

    def _drafter_for(self, sel):
        """Resolve a request's drafter override (``None`` = the engine
        default); string kinds are built once and shared."""
        if sel is None:
            return self._drafter
        if isinstance(sel, str):
            d = self._drafters.get(sel)
            if d is None:
                d = self._drafters[sel] = self._make_drafter(sel)
            return d
        return sel

    def _drafter_reset(self, i: int):
        """Slot (re)assignment/teardown: clear per-slot drafter state
        (the draft model's consumed-history counter)."""
        if not self.spec:
            return
        seen = []
        for d in [self._drafter] + list(self._drafters.values()):
            if d is not None and d not in seen:
                seen.append(d)
                rs = getattr(d, "reset_slot", None)
                if rs is not None:
                    rs(i)

    def _under_mesh(self, impl):
        """Trace-time mesh scope for a step/prefill body: the model's
        internal sharding constraints (``mp_layers.constrain``) and the
        shard_map vocab lookup resolve against ``env.active_mesh()``, so
        a mesh given only to THIS engine must be installed around the
        trace — python bodies run at trace time only, so this costs
        nothing per call.  Single-chip engines pass through untouched."""
        if self.mesh is None:
            return impl
        from ..distributed import env as _denv

        @functools.wraps(impl)
        def traced_under_mesh(*args):
            with _denv.use_mesh(self.mesh):
                return impl(*args)
        return traced_under_mesh

    def _mesh_jit_shardings(self, n_args, n_out, cache_argnum=1,
                            with_params=True):
        """The DECLARED jit shardings of a mesh engine's program: params
        and cache per :func:`decode_mesh_specs`, every other operand
        replicated (token/position/mask vectors, block tables and chunk
        scalars are tiny and every device needs them whole), sampled
        tokens replicated on the way out with the cache keeping its
        spec (the trailing output by convention; ``n_out == 1`` is the
        cache-only COW copy)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        param_specs, cache_spec, _ = decode_mesh_specs(
            self._bind, self._params, self.mesh.axis_names,
            paged_cache=self.paged, quantized_cache=self.quantized)

        def ns(spec):
            return NamedSharding(self.mesh, spec)

        def ns_cache(spec):
            # int8 cache spec is a {kv, scale} pytree of PartitionSpecs
            # (tuple subclasses — tree_map must not descend into them)
            return jax.tree_util.tree_map(
                ns, spec, is_leaf=lambda x: isinstance(x, P))

        repl = ns(P())
        in_sh = [repl] * n_args
        in_sh[cache_argnum] = ns_cache(cache_spec)
        if with_params:
            in_sh[0] = jax.tree_util.tree_map(ns, param_specs)
        if n_out == 1:
            out_sh = ns_cache(cache_spec)
        else:
            out_sh = tuple([repl] * (n_out - 1) + [ns_cache(cache_spec)])
        return {"in_shardings": tuple(in_sh), "out_shardings": out_sh}

    def _init_metrics(self):
        """Declare this engine's series in the shared registry (metric
        name conventions: README "Observability").  One ``engine=<id>``
        label keeps concurrent engines' series and retrace budgets
        independent; every hot-path update below is O(1) host work."""
        reg = _obs.default_registry()
        self._eid = str(next(_ENGINE_IDS))
        self._tracer = _obs.get_tracer()
        self._rlog = _obs.get_request_log()
        self._uids: Dict[int, int] = {}    # engine rid -> lifecycle uid
        lbl = {"engine": self._eid}
        hist, ctr, gauge = reg.histogram, reg.counter, reg.gauge
        self._m_queue_wait = hist(
            "serving.queue_wait_ms",
            "submit → admission wait per request").labels(**lbl)
        self._m_ttft = hist(
            "serving.ttft_ms",
            "time to first token: submit → first sampled token "
            "fetched").labels(**lbl)
        self._m_tpot = hist(
            "serving.tpot_ms",
            "per-token decode latency per finished request: "
            "(t_last - t_first) / (tokens - 1)").labels(**lbl)
        self._m_step_ms = hist(
            "serving.decode_step_ms",
            "wall time of one jitted decode step incl. the (num_slots,) "
            "token fetch").labels(**lbl)
        self._m_active = gauge(
            "serving.active_slots",
            "busy slots at the last scheduler tick").labels(**lbl)
        self._m_occ = gauge(
            "serving.slot_occupancy",
            "active_slots / num_slots at the last tick").labels(**lbl)
        self._m_submitted = ctr(
            "serving.requests_submitted", "submit() calls").labels(**lbl)
        self._m_finished = ctr(
            "serving.requests_finished",
            "requests retired (all reasons)").labels(**lbl)
        self._f_retired = ctr(
            "serving.retired",
            "retirements by reason: eos | max_new_tokens | max_length")
        self._f_slo_viol = ctr(
            "serving.slo_violations",
            "requests that missed their recorded TTFT/TPOT deadline, by "
            "attributed cause: rejected (admission refused) | queue_wait "
            "| prefill (missed TTFT, split by larger segment) | decode "
            "(missed TPOT); BASELINE.md 'SLO accounting conventions'")
        self._m_tokens = ctr(
            "serving.tokens_generated",
            "sampled tokens returned to requests (prefill first tokens "
            "included)").labels(**lbl)
        self._f_bucket = ctr(
            "serving.prefill_bucket",
            "admission waves per padded prefill bucket length (paged: "
            "suffix bucket)")
        f_path = ctr(
            "serving.sample_path",
            "step and prefill programs run, by the way their sampling "
            "epilogue went (the heavier of a mixed step's two): greedy "
            "(argmax alone) | categorical (a row samples, none "
            "truncates) | truncated (a sampling row set top_k or top_p: "
            "the full-vocabulary sort)")
        self._m_sample_path = tuple(
            f_path.labels(path=p, **lbl) for p in SAMPLE_PATHS)
        self._m_waves = ctr(
            "serving.prefill_waves", "batched prefill waves").labels(**lbl)
        self._m_blocked = ctr(
            "serving.admission_blocked",
            "admission attempts deferred because the paged pool could "
            "not cover the request yet").labels(**lbl)
        self._m_deferred = ctr(
            "serving.admission_deferred",
            "admission attempts held back by the predictive SLO gate "
            "(serving_admission='predictive'): the cost model priced "
            "the post-admission tick over the pooled TPOT deadline")\
            .labels(**lbl)
        self._m_prefill_computed = ctr(
            "serving.prefill_tokens_computed",
            "prompt tokens actually prefilled (pads excluded; prefix "
            "hits skip these)").labels(**lbl)
        self._m_prefill_total = ctr(
            "serving.prefill_tokens_total",
            "prompt tokens submitted across admitted requests").labels(
                **lbl)
        self._m_chunks = ctr(
            "serving.prefill_chunks",
            "prompt chunks folded into mixed steps (chunked "
            "admission)").labels(**lbl)
        self._m_chunk_tokens = ctr(
            "serving.prefill_chunk_tokens",
            "real prompt tokens carried by mixed-step chunks (chunk "
            "padding excluded)").labels(**lbl)
        self._m_chunk_queue = hist(
            "serving.chunk_queue_depth",
            "pending prefill chunks at each scheduler tick: the active "
            "prompt's remaining chunks plus every queued prompt's",
            buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512)).labels(
                **lbl)
        # speculative decoding (serving.spec* conventions: BASELINE.md) —
        # accounting is in COMMITTED tokens; drafted/rejected tokens
        # never reach serving.tokens_generated or any tok/s number.
        # Every spec series carries a ``drafter=`` label (kind of the
        # proposer that drafted the row — per-request overrides can mix
        # kinds in one engine); labeled children are built lazily per
        # kind via _spec_m.
        self._f_drafted = ctr(
            "serving.spec_drafted_tokens",
            "draft tokens the drafter proposed (sent to verification)")
        self._f_draft_hits = ctr(
            "serving.spec_draft_hit_tokens",
            "proposed draft tokens verified AND committed")
        self._f_draft_miss = ctr(
            "serving.spec_draft_miss_tokens",
            "proposed draft tokens rejected by verification (rolled "
            "back)")
        self._f_rollbacks = ctr(
            "serving.spec_rollbacks",
            "row-steps whose rejected draft suffix was rolled back "
            "(position pinned at the accept point; paged: draft-only "
            "blocks returned via truncate_to)")
        self._f_spec_accept = hist(
            "serving.spec_accepted_per_step",
            "tokens committed per active slot per verify step (1 = no "
            "speculative win that step; k+1 = whole window accepted)",
            buckets=(1, 2, 3, 4, 5, 6, 7, 8, 16))
        # engine-total children (the pre-drafter-label series, kept for
        # dashboards and the metrics() rollup) + lazily-built per-kind
        # children carrying the drafter= label
        self._m_drafted = self._f_drafted.labels(**lbl)
        self._m_draft_hits = self._f_draft_hits.labels(**lbl)
        self._m_draft_miss = self._f_draft_miss.labels(**lbl)
        self._m_rollbacks = self._f_rollbacks.labels(**lbl)
        self._m_spec_accept = self._f_spec_accept.labels(**lbl)
        self._spec_children: Dict[str, tuple] = {}
        # int8 KV cache (quantization accounting conventions: BASELINE.md)
        self._m_demoted = ctr(
            "serving.kv_demoted_blocks",
            "mixed-mode blocks rewritten to simulated int8 at trie "
            "registration").labels(**lbl)
        self._m_dequant_err = hist(
            "serving.kv_dequant_error",
            "max |logit(bf16) - logit(int8-KV)| observed by a parity "
            "oracle (tests / bench feed this; the engine never computes "
            "it on the hot path)",
            buckets=(1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1,
                     1.0)).labels(**lbl)
        self._m_step_traces = ctr(
            "jit.traces", "").labels(site="serving.step", **lbl)
        self._m_prefill_traces = ctr(
            "jit.traces", "").labels(site="serving.prefill", **lbl)
        self._m_rows_traces = ctr(
            "jit.traces", "").labels(site="serving.step_rows", **lbl)
        self._m_rows_only = ctr(
            "serving.rows_only_ticks",
            "ticks of a cursor engine with no prompt chunk: the rows-alone "
            "step program ran, a pass over the rows part and a stub of the "
            "chunk part").labels(**lbl)
        # preemptive scheduling + host KV tier (ISSUE 16; BASELINE.md
        # "Preemption accounting conventions": swap bytes are pool
        # traffic, NEVER streamed-KV bytes)
        self._f_preempt = ctr(
            "serving.preemptions",
            "running slots evicted at blocked admission, by resume "
            "mode: swap (chain parked on the host tier) | recompute "
            "(chain freed, re-prefilled through the prefix trie)")
        self._f_resumed = ctr(
            "serving.resumes",
            "preempted requests restored to a slot, by mode")
        # what only some models feed (``_note_model_counters``), and only
        # their engines register: routed experts' load (the per-expert
        # children of ``moe.expert_load`` are made at the first load
        # vector) and window layers' dead positions
        self._expert_pairs: Optional[np.ndarray] = None
        self._expert_totals = np.zeros(3, np.int64)
        self._window_dead = 0
        self._state_live = 0
        self._model_counters = bool(self._expert_layers or self._windows)
        if self._expert_layers:
            self._m_pairs_elsewhere = ctr(
                "moe.pairs_elsewhere",
                "(token, expert) pairs routed to experts another expert-"
                "parallel rank holds: what the exchange would carry"
                ).labels(**lbl)
            self._m_experts_touched = hist(
                "moe.experts_touched",
                "held experts with at least one routed pair, per expert-"
                "layer call: the expert weights the grouped product had "
                "to read").labels(**lbl)
        if self._block:
            self._m_diffusion = tuple(ctr(
                "serving.diffusion." + name, text).labels(**lbl)
                for name, text in (
                    ("forwards", "rows' forwards over a block: every live "
                     "row of every block step, denoising or commit"),
                    ("unmasked", "positions unmasked by a denoising "
                     "forward"),
                    ("commits", "rows' forwards that found their block "
                     "mask-free: they unmask nothing and write the K/V "
                     "later blocks read"),
                    ("delivered", "tokens delivered to requests, a block "
                     "at a time, cut at max_new_tokens")))
        if self._slot_leaves:
            self._m_state_live = gauge(
                "kv_cache.state_rows_live",
                "rows of the fixed-size per-slot state held by a resident "
                "request (decoding, or mid-prompt under the cursor) at the "
                "last tick").labels(**lbl)
            self._m_state_rows = gauge(
                "kv_cache.state_rows",
                "rows of the fixed-size per-slot state allocated: one a "
                "slot and the null row").labels(**lbl)
            self._m_state_bytes = gauge(
                "kv_cache.state_bytes",
                "bytes of the fixed-size per-slot state leaves").labels(
                    **lbl)
        if self._pool_entry is not None:
            self._m_pool_position_bytes = gauge(
                "kv_cache.position_bytes",
                "bytes one position holds in the paged pool over every "
                "layer, as stored (a declared pool entry's arrays and "
                "padded width)").labels(**lbl)
        if self._windows:
            self._m_window_dead = gauge(
                "kv_cache.window_dead_positions",
                "live (position, window layer) pairs behind their layer's "
                "sliding window at the last tick: what a window-aware "
                "allocator would free").labels(**lbl)
        self._m_swap_out_bytes = ctr(
            "serving.swap_out_bytes",
            "HBM→host bytes moved by swap-outs and trie demotions "
            "(pool traffic, not streamed KV bytes)").labels(**lbl)
        self._m_swap_in_bytes = ctr(
            "serving.swap_in_bytes",
            "host→HBM bytes moved by swap-ins and trie "
            "promotions").labels(**lbl)
        self._m_cancelled = ctr(
            "serving.cancelled",
            "cancel() calls that found and tore down a live "
            "request").labels(**lbl)
        # cross-worker KV migration (ISSUE 18; BASELINE.md "Multi-host
        # accounting conventions": migration bytes are pool traffic over
        # the transport, NEVER streamed-KV bytes and NEVER swap bytes)
        self._m_mig_out = ctr(
            "migration.requests_out",
            "requests exported for cross-worker migration").labels(**lbl)
        self._m_mig_in = ctr(
            "migration.requests_in",
            "migration records imported into this engine").labels(**lbl)
        self._m_mig_bytes_out = ctr(
            "migration.bytes_out",
            "KV payload bytes serialized out by export_request "
            "(block payloads + scale rows)").labels(**lbl)
        self._m_mig_bytes_in = ctr(
            "migration.bytes_in",
            "KV payload bytes written into the pool by "
            "import_request").labels(**lbl)

    def _spec_m(self, kind: str):
        """The drafter-labeled spec-series children for one drafter
        kind: (drafted, hits, miss, rollbacks, accept_hist).  Built
        lazily — kinds are a tiny closed set (ngram/model/custom), so
        cardinality stays bounded."""
        m = self._spec_children.get(kind)
        if m is None:
            lbl = {"engine": self._eid, "drafter": kind}
            m = self._spec_children[kind] = (
                self._f_drafted.labels(**lbl),
                self._f_draft_hits.labels(**lbl),
                self._f_draft_miss.labels(**lbl),
                self._f_rollbacks.labels(**lbl),
                self._f_spec_accept.labels(**lbl))
        return m

    # -- the device programs: one operand table, one builder each ---------

    def _operand_tables(self):
        """The signatures of the step program and of the prefill wave's
        — one by the rows its program runs, 1 and ``prefill_batch``
        (``_wave_rows``); none for the cursor engine, which has no wave —,
        after ``(params,
        cache)``: each a list of :class:`_Operand` in the order the
        program's body names them.  Built once; everything that states a
        signature reads it — the packed buffer's layout (``_layout``: what
        a tick and a wave upload, ``_upload``, and what the program bodies
        take apart, ``_unpack``), ``_lint_args`` and the length of a mesh
        engine's declared shardings (``_program_arity``).  The key's row
        says what the body sees; what crosses for it is the base key's
        words and the tick's number."""
        s, k = self.num_slots, self.spec_k
        mb = self.max_blocks if self.paged else 0
        i32, f32, op = np.int32, np.float32, _Operand
        key = op("key", (), self._base_key.dtype, "key")

        def knobs(n, c, temps, topk, topp):
            # the three per-row vectors of one sample_tokens call
            return [op(c + "temps", (n,), f32, temps),
                    op(c + "topk", (n,), i32, topk),
                    op(c + "topp", (n,), f32, topp, fill=1)]
        step = [op("tokens", (s, k + 1), i32, "tokens") if self.spec
                else op("tokens", (s, self._block), i32, self._blocks)
                if self._block
                else op("tokens", (s,), i32, self._tokens),
                # the cursor engine on the contiguous cache steers its idle
                # rows' positions each tick (``_step_inner``)
                op("positions", (s,), i32,
                   "positions" if self.chunked and not self.paged
                   else self._positions)]
        if self.paged:
            step.append(op("tables", (s, mb), i32, self._tables))
        if self._share is not None:
            # which rows walk which leading columns together (``_regroup``)
            step += [op("share_" + f, x.shape, i32, x)
                     for f, x in zip(self._share._fields, self._share)]
        step.append(op("slot_mask", (s,), bool, self._active))
        if self.spec:
            step += [op("draft_ok", (s, k), bool, "draft_ok"),
                     op("draft_probs", (s, k, self.config.vocab_size), f32,
                        "draft_probs")]
        step += knobs(s, "", self._temps, self._topk, self._topp)
        if self._block:          # each row's unmasking rule (unmask_block)
            step += [op("unmask_n", (s,), i32, self._unmask_n),
                     op("unmask_thr", (s,), f32, self._unmask_thr, fill=1)]
        if self.chunked:
            step += [op("cids", (1, self.prefill_chunk), i32, "cids"),
                     op("cpos", (), i32, "cpos"),
                     op("clen", (), i32, "clen", fill=1),
                     # where the chunk is written: its slot's row of the
                     # block table, or its slot
                     op("cdst", (1, mb) if self.paged else (), i32, "cdst")]
            if self._slot_leaves:
                # the row of the per-slot state the chunk part addresses:
                # the cursor's slot (a table row names blocks, not a slot);
                # the null row on a chunk-free tick
                step.append(op("cslot", (), i32, "cslot"))
            step += knobs(1, "c", "ctemps", "ctopk", "ctopp")
            return step + [key], {}

        def wave(nb):
            rows = [op("ids", (nb, None), i32, "ids")]
            if self.paged:
                rows += [op("prefix_lens", (nb,), i32, "prefix_lens"),
                         op("lens", (nb,), i32, "lens", fill=1),
                         op("tables", (nb, mb), i32, "tables")]
            else:
                rows += [op("lens", (nb,), i32, "lens", fill=1),
                         op("slot_ids", (nb,), i32, "slot_ids")]
            return rows + knobs(nb, "", "temps", "topk", "topp") + [key]
        return step + [key], {nb: wave(nb)
                              for nb in (1, self.prefill_batch)}

    def _layout(self, table) -> _Layout:
        """``table``'s layout in the packed buffer (a wave's table is
        known by its rows: ``ids`` leads it)."""
        return (self._step_layout if table is self._step_table
                else self._wave_layouts[table[0].shape[0]])

    def _buffer_shape(self, table, bucket: int = 0) -> Tuple:
        """The shape ``table``'s packed buffer crosses in.  Flat, its
        length stating the wave's bucket — but a length cannot also state
        the rows (one row of ``4b`` tokens and four of ``b`` are one
        length, and would be one program to ``jit``), so the one-row
        wave's buffer says its rows by a leading axis of its own."""
        lay = self._layout(table)
        n = lay.words + lay.a_token * bucket
        flat = table is self._step_table or table is self._prefill_table
        return (n,) if flat else (1, n)             # the one-row wave's

    def _program_arity(self, table) -> int:
        """The arguments of ``table``'s program: params, cache, the packed
        buffer, and the operands that are not small."""
        return 3 + len(self._layout(table).own)

    def _unpack(self, table, packed, own) -> Dict[str, jax.Array]:
        """The first lines of a device program: its operands by name, cut
        out of the ``packed`` buffer by the layout ``_upload`` filled it
        by — static slices from lane-tile offsets, a float32's bits read
        back as float32, a mask's words compared with 0, the key folded
        from the base key's words and the tick's number (the fold the host
        made eagerly a tick before PR 38: the same bits) — with the
        operands that crossed on their ``own`` beside them."""
        lay = self._layout(table)
        if packed.ndim == 2:          # a one-row wave's (``_buffer_shape``)
            packed = packed[0]
        a = {o.name: x for o, x in zip(lay.own, own)}
        for o, at in lay.packed:
            if _is_key(o.dtype):
                n = self._key_bits.size
                base = jax.random.wrap_key_data(
                    jax.lax.bitcast_convert_type(packed[at:at + n],
                                                 np.uint32),
                    impl=jax.random.key_impl(self._base_key))
                a[o.name] = jax.random.fold_in(base, packed[at + n])
                continue
            if None in o.shape:     # the wave's ids: the rest of the buffer
                x = packed[at:].reshape(
                    [-1 if d is None else d for d in o.shape])
            else:
                x = packed[at:at + math.prod(o.shape)].reshape(o.shape)
            a[o.name] = (
                x != 0 if o.dtype is bool
                else jax.lax.bitcast_convert_type(x, np.float32)
                if o.dtype is np.float32 else x)
        return a

    def _step_program(self, rows_alone=False):
        """The Python body of THE step program, composed for this engine's
        layout and compiled exactly once: ONE pass of the model's weights
        (``decode_parts``) over a rows part and, when ``chunked``, a chunk
        part, over the cache addressed through block tables when ``paged``
        and by slot row when not.  Named by its layout,
        ``_[spec_][mixed_]step_impl[_paged]``: the device trace's module
        line and the benchmark's readers go by that name.  ``rows_alone``
        gives a cursor engine's second program, for its chunk-free ticks
        (``_[spec_]rows_step_impl[_paged]``, below).

        A part (``models.parts.DecodePart``) is a run of tokens with its
        own way into the per-request state: ids, positions, its block
        table (paged) or its slot rows of the cache (contiguous), the mask
        of its real tokens for a model that routes experts or keeps
        per-slot state, its rows of that state, the position its logits
        are wanted at, and the ``program_part`` its kernels are named by.
        The model runs everything token-wise — norms, projections, FFNs,
        routed experts, the head — once over both parts' tokens laid end to
        end (``num_slots·(k+1) + prefill_chunk`` of them, named
        ``token_pass``), so a tick streams every weight once; RoPE, the K/V
        write, the cached-attention read and a per-slot state's update run
        a part at a time, the rows' before the chunk's on the one cache.
        A program of one part is that part's ``decode_step``.

        Rows part.  Row i holds request state at ``positions[i]``.  Plain:
        every row advances one token.  ``spec``: ``tokens`` is the
        (num_slots, k+1) window matrix ``[current, d_1..d_k]`` (pad columns
        where the drafter had nothing), ``draft_ok`` the real-proposal mask,
        and ONE forward scores every row's window at its own depth — q-depth
        k+1 rides the q-tiled flash-decode path (``kernel_path_hint``
        relabels the trace's dispatch counts ``op="spec_verify"``), so all
        drafts of all slots cost a single pass of the weights — then
        ``accept_draft_tokens`` keeps each row's longest verified prefix
        plus the bonus token: greedy rows by the exact prefix-match rule,
        sampled rows by rejection sampling against ``draft_probs`` (the
        (s, k, vocab) proposal distributions: one-hot for deterministic
        proposers, the draft model's softmax otherwise), so every committed
        token is distributed exactly as plain sampling.  Row i writes K/V at
        ``positions[i]..positions[i]+k``; the host commits only the accepted
        prefix and never advances past it, so writes past an accept point
        are dead cells the next steps overwrite before any mask can read
        them (the stale-tail argument plain decode already relies on).  A
        draft-free tick is the same program with all-pad windows.
        Block rows (a model that declares ``block_diffusion``; the module
        docstring's "Block diffusion"): ``tokens`` is the (num_slots, B)
        matrix of each row's current block at ``positions[i]``, a multiple
        of B; ONE forward runs every row's block under the model's
        block-causal mask and ``unmask_block`` returns each row's new block
        and how many positions it unmasked.  The window's two arguments
        carry over: the block's K/V at ``positions[i]..positions[i]+B-1``
        are written before they are read, and a denoising forward's K/V are
        overwritten by the next forward of the same block, the commit
        forward's being final (no mask reads past a block's end).

        Where a row that is not decoding writes.  Paged: its table row is
        all null, so the write lands in the null block (kv_cache.py's
        convention; a spec row's pad columns past its chain land there too,
        so a row near its reservation ceiling never allocates for drafts it
        did not propose).  Contiguous, wave engine: junk at position 0 of an
        idle row, which the next wave prefill rebuilds whole.  Contiguous,
        cursor engine: the host steers the position to ``max_length`` and
        the scatter drops out of bounds, because chunked prefill builds a
        row incrementally and an idle write must be dropped, not absorbed.

        Chunk part: decode-at-depth of ``cids`` (one (1, chunk) row, chunk
        size static) at positions ``cpos..cpos+chunk-1``: paged, through the
        slot's own (1, max_blocks) table row ``cdst`` straight into its
        blocks (the rows part saw that slot as an all-null row); contiguous,
        over the ``cdst`` cache row, each layer's cut out with a dynamic
        slice and put back.  Pad-tail writes past the prompt land where
        decode overwrites them before the mask can read them (the
        wave-prefill padding argument).  Its logits are
        taken at ``clen - 1`` alone: the sampled chunk token is the
        request's FIRST token when this chunk completes the prompt; the
        host discards it otherwise (always, for a block-diffusion model:
        its cursor stops at the prompt's last whole block and its first
        token comes with the first block's delivery).  A prefilling slot is
        inactive until its cursor completes, so the two parts never touch
        the same row.

        A chunk-free tick runs the ``rows_alone`` program instead, which
        the host chooses by what the tick holds (``_device_step``): the
        same body with the chunk part cut to a stub of its first
        ``_stub_chunk`` (8) positions — ``num_slots·(k+1) + 8`` token rows
        in the pass where the mixed program has ``prefill_chunk`` more —
        the same operand table and packed layout, the same outputs (the
        chunk's token is junk, which the host does not read).  On such a
        tick ``clen`` is 0, so the stub holds no real token: its table is
        all null, or ``cpos = max_length`` (every write drops, the row
        round-trips bit-identical), its state row the null row.  It is
        compiled once, at the engine's first chunk-free tick, under a
        budget of 1 of its own (site ``serving.step_rows``).

        Why a stub and not the rows part alone: what XLA:TPU rounds to
        bfloat16 follows from what it fuses (``xla_allow_excess_precision``),
        and what it fuses from the parts' cuts.  With a chunk part beside
        the rows part a projection's result is materialised in bfloat16
        where the parts take their cuts of it; with the rows part alone it
        stays float32 through the q/k norm and RoPE (SDAR, PR 44: the K the
        rows part wrote differed from the mixed program's in a fifth of its
        elements in the first layer, and the served tokens lay twice as far
        from the reference's as the parent's did on the same requests).  A
        stub keeps the cuts and with them the mixed program's rounding: a
        live row computes what it computes in the mixed program.

        Per-slot state (a model's ``slot_state`` leaves): the rows part
        addresses the slots' rows (the null row stays out), the chunk part
        the row ``cslot``: the cursor's slot (a table row names blocks, not
        a slot), the null row on a chunk-free tick.

        Returns ``_step_outputs``: block rows add ``n_unmasked`` after the
        blocks, a model with expert layers adds their load, (1, expert
        layers, held + 1), before the cache."""
        chunked, spec = self.chunked, self.spec

        def step(params, cache, packed, *own):
            a = self._unpack(self._step_table, packed, own)
            prep = self._prepare(params)
            mask, key = a["slot_mask"], a["key"]
            parts = self._step_parts(a, stub=rows_alone)
            tokens = parts[0].input_ids
            if chunked:
                whole = _disp.program_part(_STEP, "token_pass")
            else:
                # one part: the pass IS the part, named as it ever was
                whole = parts[0].scope()
                parts = [parts[0]._replace(scope=contextlib.nullcontext)]
            with whole, bind_params(self._bind, prep), \
                    (_moe.expert_load() if self._expert_layers
                     else contextlib.nullcontext(())) as load:
                (logits, *clogits), cache = self.model.decode_parts(parts,
                                                                    cache)
            if self._block:
                # the block rows' epilogue: the unmasking rule in place of
                # one sampled token a row (``unmask_block``)
                with jax.named_scope("unmask"):
                    new, n_un = unmask_block(
                        logits, tokens, self._diffusion.mask_token_id, key,
                        a["temps"], a["topk"], a["topp"], a["unmask_n"],
                        a["unmask_thr"])
                outs = [jnp.where(mask[:, None], new, tokens),
                        jnp.where(mask, n_un, 0)]
            elif spec:
                with jax.named_scope("accept"):
                    out, n_acc = accept_draft_tokens(
                        logits, tokens[:, 1:], a["draft_ok"], key,
                        a["temps"], a["topk"], a["topp"],
                        pad_token_id=self.pad_token_id,
                        draft_probs=a["draft_probs"])
                outs = [jnp.where(mask[:, None], out,
                                  jnp.int32(self.pad_token_id)), n_acc]
            else:
                with jax.named_scope("sample"):
                    nxt = sample_tokens(logits[:, -1], key, a["temps"],
                                        a["topk"], a["topp"])
                    outs = [jnp.where(mask, nxt,
                                      jnp.int32(self.pad_token_id))]
            if chunked:
                with jax.named_scope("sample_chunk"):
                    outs.append(sample_tokens(
                        clogits[0][:, 0], jax.random.fold_in(key, 1),
                        a["ctemps"], a["ctopk"], a["ctopp"])[0])
            # a model with routed experts: their load rides out
            return (*outs, *([jnp.stack(load)[None]] if load else ()), cache)

        step.__name__ = step.__qualname__ = (
            "_" + "spec_" * spec
            + ("rows_" if rows_alone else "mixed_" * chunked) + "step_impl"
            + "_paged" * self.paged)
        return step

    @property
    def _pass_rows(self) -> int:
        """The token rows of the step program's one pass of the weights:
        its parts' rows x positions, padding and all."""
        return (self.num_slots * self._row_tokens
                + self.prefill_chunk * self.chunked)

    @property
    def _stub_chunk(self) -> int:
        """The positions the rows-alone program keeps of the chunk part:
        one sublane tile of them (``_step_program``)."""
        return min(_STUB_CHUNK, self.prefill_chunk)

    @property
    def _row_tokens(self) -> int:
        """The positions one row of the rows part holds: the verify window,
        a block-diffusion model's block, or one token."""
        return self.spec_k + 1 if self.spec else self._block or 1

    def _step_parts(self, a, stub=False):
        """The step program's parts (``_step_program``), from its operands
        by name: the rows part, then the chunk part when ``chunked`` —
        ``stub``: cut to the first ``_stub_chunk`` of its positions, the
        rows-alone program's."""
        spec, paged = self.spec, self.paged
        part = functools.partial(_disp.program_part, _STEP)

        @contextlib.contextmanager
        def verify_rows():
            with part("verify_rows"), _disp.kernel_path_hint("spec_verify"):
                yield
        # a model with expert layers or per-slot state is told the real
        # tokens: padding is routed to no expert and advances no state
        masked = bool(self._expert_layers or self._slot_leaves)
        mask = a["slot_mask"]
        real = None
        if masked:
            real = mask[:, None] & jnp.concatenate(
                [jnp.ones_like(mask)[:, None], a["draft_ok"]],
                1) if spec else jnp.broadcast_to(
                    mask[:, None], (self.num_slots, self._block)
                ) if self._block else mask[:, None]
        parts = [DecodePart(
            a["tokens"] if spec or self._block else a["tokens"][:, None],
            a["positions"], a.get("tables"), valid=real,
            slots=(0, self.num_slots) if self._slot_leaves else None,
            scope=(verify_rows if spec else functools.partial(
                part, "block_rows" if self._block else "decode_rows")),
            shared=self._share and type(self._share)(
                *(a["share_" + f] for f in self._share._fields)))]
        if self.chunked:
            cids, clen, cdst = a["cids"], a["clen"], a["cdst"]
            if stub:
                cids = cids[:, :self._stub_chunk]
            parts.append(DecodePart(
                cids, a["cpos"][None], cdst if paged else None,
                valid=((jnp.arange(cids.shape[1]) < clen)[None]
                       if masked else None),
                slots=((a["cslot"], 1) if self._slot_leaves
                       else None if paged else (cdst, 1)),
                last=clen - 1,
                scope=functools.partial(part, "prompt_chunk")))
        return parts

    def _prefill_program(self):
        """The Python body of the wave engine's prefill program
        (``_prefill_impl[_paged]``), one compilation per padded bucket
        length; the body is one for both row counts (``_wave_rows``:
        ``prefill_batch`` rows below ``_ROW_FILLS_CHIP`` positions, one row
        from there on) and reads the rows off its operands.  Each row's
        first token samples from the logits at its last REAL position
        (``lens``).

        Contiguous: the prompts run through the static-``pos=0`` path
        (flash-eligible) on a fresh cache of the wave's rows, and the
        finished rows are scattered into their slots.  Dummy rows carry
        ``slot_id == num_slots``; the ``mode="drop"`` scatter discards them.

        Paged: each row computes ONLY its prompt suffix — the tokens its
        prefix-cache match did not cover — as a decode-at-depth over the
        pool (per-row ``pos`` = adopted prefix length; the adopted blocks
        are read, not recomputed).  Writes scatter straight into the rows'
        own blocks (the null block absorbs bucket padding, and rows admitted
        in the same wave see each other's writes because every layer's
        scatter precedes its attention read)."""
        paged = self.paged

        def prefill(params, cache, packed, *own):
            # the wave's rows, as its buffer's shape says them
            rows = self.prefill_batch if packed.ndim == 1 else packed.shape[0]
            a = self._unpack(self._wave_tables[rows], packed, own)
            ids = a["ids"]
            nb = ids.shape[0]
            if paged:
                rows, pos, at = cache, a["prefix_lens"], {
                    "block_tables": a["tables"]}
            else:
                rows, pos, at = init_kv_cache(
                    self.config, nb, self.max_length,
                    quantized=self.quantized), 0, {}
            with _disp.program_part(_PREFILL, "wave_rows"), \
                    bind_params(self._bind, self._prepare(params)):
                logits, rows = self.model.decode_step(ids, rows, pos, **at)
            with jax.named_scope("sample"):
                last = logits[jnp.arange(nb), a["lens"] - 1]  # (nb, vocab)
                tok = sample_tokens(last, a["key"], a["temps"], a["topk"],
                                    a["topp"])
            if paged:
                return tok, rows
            # leaf-wise slot scatter (the int8 cache is a {kv, scale} pytree
            # with batch at axis 2 in both leaves; the fresh sub-cache's zero
            # scales reset the reused rows' quantization state for free)
            return tok, jax.tree_util.tree_map(
                lambda c, s: c.at[:, :, a["slot_ids"]].set(s, mode="drop"),
                cache, rows)

        prefill.__name__ = prefill.__qualname__ = (
            "_prefill_impl" + "_paged" * paged)
        return prefill

    # -- public API --------------------------------------------------------

    def submit(self, prompt: Sequence[int],
               max_new_tokens: int = 32,
               sampling: Optional[SamplingParams] = None,
               request_uid: Optional[int] = None,
               priority: int = 0,
               ttft_slo_ms: Optional[float] = None,
               tpot_slo_ms: Optional[float] = None,
               drafter=None) -> int:
        """Enqueue a request; returns its id.  Admission happens inside
        ``step()`` as slots free up (FIFO).

        ``request_uid`` threads an existing lifecycle uid through (a
        router minted it and already logged ``submitted``); direct
        callers leave it None and the engine mints one — either way the
        uid correlates every later lifecycle event, across replicas on
        failover included.

        ``ttft_slo_ms`` / ``tpot_slo_ms`` override the ambient SLO
        flags — the router captures a request's deadlines once at
        ROUTER submit time and threads them through here, so a request
        placed ticks later from the predictive hold queue still carries
        the class deadlines it arrived with (not whatever the flags say
        at placement time).  None reads the flags (direct callers).

        ``priority`` is the preemption class (higher wins; default 0).
        With ``preempt`` armed, the queue admits by priority class
        (stable FIFO within a class) and a blocked admission may evict
        a running lower-priority request — see ``_try_preempt`` for
        the victim selection contract.

        ``drafter`` overrides the engine's default drafter for THIS
        request (spec mode): ``"ngram"``, ``"model"``, or a Drafter
        instance — a router can mix n-gram and draft-model requests in
        one engine; string kinds are built lazily and memoized, and
        lifecycle ``spec_accept`` events record ``drafter_kind``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if ttft_slo_ms is None:
            ttft_slo_ms = float(_flags.flag("serving_slo_ttft_ms"))
        if tpot_slo_ms is None:
            tpot_slo_ms = float(_flags.flag("serving_slo_tpot_ms"))
        if request_uid is None:
            uid = self._rlog.new_uid()
            self._rlog.event(
                uid, "submitted", engine=self._eid,
                prompt_len=int(prompt.size),
                max_new_tokens=int(max_new_tokens),
                ttft_slo_ms=float(ttft_slo_ms),
                tpot_slo_ms=float(tpot_slo_ms))
        else:
            uid = int(request_uid)
        try:
            if prompt.size < 1:
                raise _Rejected("bad_prompt",
                                "prompt must contain at least one token")
            if max_new_tokens < 1:
                raise _Rejected(
                    "bad_max_new_tokens",
                    f"max_new_tokens must be >= 1, got {max_new_tokens}")
            if prompt.size + max_new_tokens > self.max_length:
                raise _Rejected(
                    "too_long",
                    f"prompt ({prompt.size}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds the engine's max_length "
                    f"({self.max_length})")
            if self.paged:
                need = self.kv.blocks_needed(
                    prompt.size,
                    self._reserved_new(prompt.size, max_new_tokens))
                if need > self.kv.usable_blocks:
                    raise _Rejected(
                        "pool_too_small",
                        f"request needs {need} KV blocks but the pool "
                        f"only has {self.kv.usable_blocks} usable blocks")
        except _Rejected as e:
            self._rlog.event(uid, "rejected", engine=self._eid,
                             reason=e.reason)
            self._f_slo_viol.labels(engine=self._eid,
                                    kind="rejected").inc()
            raise ValueError(str(e)) from None
        rid = self._next_rid
        self._next_rid += 1
        self._results[rid] = []
        self._uids[rid] = uid
        self._enqueue(self._queue, Request(
            rid, prompt, int(max_new_tokens),
            sampling or SamplingParams(),
            t_submit=self._clock(), uid=uid,
            ttft_slo_ms=float(ttft_slo_ms),
            tpot_slo_ms=float(tpot_slo_ms),
            priority=int(priority), drafter=drafter))
        self._m_submitted.inc()
        return rid

    def request_uid(self, rid: int) -> int:
        """The lifecycle uid behind engine request ``rid`` — the key
        into :func:`paddle_tpu.observability.get_request_log`."""
        return self._uids[rid]

    def step(self) -> List[int]:
        """One scheduler tick: admit queued requests into free slots
        (batched prefill waves), then run ONE jitted decode step over the
        slot batch.  Returns the request ids finished this tick.

        Idle ticks (no queued work, no active slots — the poll loop of a
        server waiting for traffic) return immediately: no admission
        scan, no device dispatch of a fully-masked decode step."""
        if (not self._queue and not self._resume_q
                and not self._swap_resume and not self._active.any()
                and self._prefill is None):
            self._set_occupancy(0)
            return []
        if not self._linted:
            # first real tick: self-lint the once-jitted step under
            # FLAGS_graph_lint (one abstract trace, no compile) — the
            # donation/dtype/const/host-sync/retrace rules fail loudly
            # here, BEFORE the first device dispatch, when armed
            self._linted = True
            if _flags.flag("graph_lint") != "off":
                from .. import static_analysis as _sa
                _sa.enforce(self.lint_step(),
                            context=f"serving.step engine={self._eid}")
        with self._tracer.span("serving.step", tick=self._ticks):
            return self._step_inner()

    def _grow_row_for_writes(self, i: int, last_pos: int):
        """Paged pre-dispatch bookkeeping for one slot about to write K/V
        at ``positions[i]..last_pos``: grow the chain over every block
        boundary in the span and COW-privatise each block in it (no-ops
        unless a forking feature shared them), refreshing the uploaded
        table row when anything changed.  Plain decode spans one
        position; a spec verify step spans the row's real draft window."""
        pos = int(self._positions[i])
        changed = self.kv.ensure_capacity(i, last_pos)
        for lb in range(pos // self.block_len,
                        last_pos // self.block_len + 1):
            cow = self.kv.ensure_writable(i, lb)
            if cow is not None:
                self._cache = self._cow_fn(self._cache, jnp.int32(cow[0]),
                                           jnp.int32(cow[1]))
                changed = True
        if changed:
            self._tables[i] = self.kv.table_row(i, self.max_blocks)

    def _flush_fresh_scales(self):
        """int8 pool pre-dispatch hygiene: zero the device scale rows of
        every block newly appended to a chain since the last dispatch
        (see BlockManager.drain_fresh) so a reused block's stale scale
        never inflates its new tenant's quantization."""
        if not (self.paged and self.quantized):
            return
        fresh = self.kv.drain_fresh()
        if not fresh:
            return
        mask = np.zeros((self.kv.num_blocks,), bool)
        mask[fresh] = True
        self._cache = self._scale_reset_fn(self._cache, jnp.asarray(mask))

    def _apply_demotions(self):
        """Mixed-mode post-dispatch hygiene: run the queued simulated-
        int8 block rewrites.  Queued at trie registration, applied only
        after the dispatch that wrote the blocks' contents (wave
        registration precedes its prefill; chunked registration follows
        its chunk) — a demotion must never be overwritten by the prefill
        it raced."""
        if not self._pending_demote:
            return
        # drain in place: kv.on_demote holds a bound ``extend`` of THIS
        # list, so rebinding the attribute would orphan the hook
        pending = list(self._pending_demote)
        self._pending_demote.clear()
        for bid in pending:
            self._cache = self._demote_fn(self._cache, jnp.int32(bid))
        self._m_demoted.inc(len(pending))

    # -- host tier plumbing (swap hooks) -----------------------------------

    def _block_movers(self):
        """Build (once, lazily) the jitted one-block movers the swap
        hooks and the export/import migration path share.  Each is
        jitted ONCE with a traced block id — a different block is
        different DATA, not a different trace, so the retrace budget of
        1 holds for every swap/migration volume.  The read fn does NOT
        donate (the pool is read again); the write fn donates the pool
        and the engine rebinds it, the step's aliasing contract.  Both
        map over the cache pytree, so the int8 {kv, scale} pool moves a
        block's scale row together with its payload — a round trip
        restores quantized blocks bit-for-bit."""
        if self._read_block_fn is not None:
            return
        def _read_block_impl(c, bid):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    a, bid, 1, axis=2), c)

        def _write_block_impl(c, payload, bid):
            return jax.tree_util.tree_map(
                lambda a, p: jax.lax.dynamic_update_slice_in_dim(
                    a, p, bid, axis=2), c, payload)
        read_kwargs, write_kwargs = {}, {}
        if self.mesh is not None:
            # the one-block payload keeps the pool's per-leaf specs
            # (only the head dim is sharded; the block axis never is,
            # so a single-block slice stays on-device-local)
            sh = self._mesh_jit_shardings(2, 1, cache_argnum=0,
                                          with_params=False)
            read_kwargs = dict(in_shardings=sh["in_shardings"],
                               out_shardings=sh["out_shardings"])
            write_kwargs = dict(
                in_shardings=(sh["in_shardings"][0],
                              sh["out_shardings"],
                              sh["in_shardings"][1]),
                out_shardings=sh["out_shardings"])
        self._read_block_fn = _obs.track_retraces(
            _read_block_impl, "serving.swap_read", budget=1,
            labels={"engine": self._eid}, **read_kwargs)
        self._write_block_fn = _obs.track_retraces(
            _write_block_impl, "serving.swap_write", budget=1,
            labels={"engine": self._eid}, donate_argnums=(0,),
            **write_kwargs)

    def _host_swap_out(self, pairs):
        """BlockManager ``on_swap_out`` hook: copy each ``(bid, hid)``
        pair's device block into its host buffer.  The ``device_get``
        is the synchronization point — the payload lands on the host
        BEFORE ``swap_out``/``_evict_one`` returns the physical block to
        the free list, so a re-allocation can never race the copy."""
        self._block_movers()
        tier = self.kv.host_tier
        for bid, hid in pairs:
            payload = jax.device_get(
                self._read_block_fn(self._cache, jnp.int32(bid)))
            tier.put(hid, payload)
            nbytes = sum(int(a.nbytes) for a in
                         jax.tree_util.tree_leaves(payload))
            self._tick_swap_bytes += nbytes
            self._m_swap_out_bytes.inc(nbytes)

    def _host_swap_in(self, pairs):
        """BlockManager ``on_swap_in`` hook: write each ``(hid, bid)``
        pair's host payload back into its (re)allocated device block.
        The write fn donates the pool — same in-place aliasing contract
        as the step — and runs strictly between dispatches, so the
        once-jitted step never observes a swap as a new trace."""
        self._block_movers()
        tier = self.kv.host_tier
        for hid, bid in pairs:
            payload = jax.tree_util.tree_map(jnp.asarray, tier.get(hid))
            self._cache = self._write_block_fn(self._cache, payload,
                                               jnp.int32(bid))
            nbytes = sum(int(a.nbytes) for a in
                         jax.tree_util.tree_leaves(payload))
            self._tick_swap_bytes += nbytes
            self._m_swap_in_bytes.inc(nbytes)

    # -- preemptive scheduling (ISSUE 16) ----------------------------------

    def _try_preempt(self, *, priority: int, rid: int,
                     blocked_ticks: int) -> bool:
        """Pick and preempt ONE victim so the blocked waiter's admission
        can retry.  Victim selection is the BASELINE.md determinism
        contract — a pure function of schedule state, ranked by
        (priority ASC, loosest TTFT SLO first, shortest progress,
        youngest request, slot index).  The SLO key is the RELATIVE
        budget, deliberately not a submit-anchored absolute deadline:
        t_submit is wall clock, and ranking on it would make victim
        selection timing-dependent, breaking the byte-stable replay
        signature (no-SLO victims rank as infinitely loose, i.e. first):

          * a strictly-lower-priority victim is preempted immediately;
          * a same-priority victim only after the waiter has been
            blocked ``_PREEMPT_AFTER`` consecutive ticks,
            and never one that was itself already preempted once —
            together these stop two equal-priority requests from
            swapping each other forever.

        Returns True if a victim was preempted (the caller retries
        admission), False if nobody is eligible."""
        if self.preempt == "off":
            return False
        cands = []
        for i, slot in enumerate(self._slots):
            if slot is None or slot.req is None:
                continue
            vr = slot.req
            if vr.priority < priority:
                pass                       # strictly lower: immediate
            elif (vr.priority == priority
                  and blocked_ticks >= _PREEMPT_AFTER
                  and vr.preempt_count == 0):
                pass                       # FIFO fairness gate passed
            else:
                continue
            dl = vr.ttft_slo_ms if vr.ttft_slo_ms > 0 else float("inf")
            prog = len(self._results[vr.request_id])
            cands.append(((vr.priority, -dl, prog, -vr.request_id, i), i))
        if not cands:
            return False
        _, victim = min(cands)
        self._do_preempt(victim, waiter_rid=rid)
        return True

    def _do_preempt(self, i: int, waiter_rid: int):
        """Evict slot ``i``'s request: swap its private blocks to the
        host tier (falling back to recompute if the tier can't take
        them) or free the chain for recompute-from-prefix, then park the
        request on the matching resume queue."""
        slot = self._slots[i]
        req = slot.req
        mode = self.preempt
        record = None
        if mode == "swap":
            record = self.kv.swap_out(i)
            if record is None:
                mode = "recompute"  # host tier full: degrade gracefully
        if mode == "recompute":
            self.kv.preempt_free(i)
        req.preempt_count += 1
        gen = self._results[req.request_id]
        self._preempt_log.append({
            "tick": self._ticks, "victim_rid": req.request_id,
            "waiter_rid": waiter_rid, "mode": mode, "slot": i,
            "progress": len(gen)})
        self._f_preempt.labels(engine=self._eid, mode=mode).inc()
        self._tracer.instant("serving.preempted", rid=req.request_id,
                             mode=mode, slot=i)
        self._rlog.event(req.uid, "preempted", engine=self._eid,
                         mode=mode, slot=int(i), tokens=len(gen),
                         waiter=int(waiter_rid))
        if mode == "swap":
            n_host = sum(1 for e in record["entries"] if e[0] == "host")
            self._rlog.event(req.uid, "swapped_out", engine=self._eid,
                             blocks=len(record["entries"]),
                             host_blocks=int(n_host))
            self._push_swap_resume(_SwapResume(
                req=req, record=record,
                last_token=int(self._tokens[i]),
                position=int(self._positions[i]),
                remaining=slot.remaining, t_first=slot.t_first))
        else:
            # recompute: the synthetic resume request re-prefills the
            # prompt plus every committed token but the last through the
            # prefix trie.  The cache covered positions
            # [0, plen + n_gen - 1) at preemption, which is EXACTLY
            # len(prompt ++ gen[:-1]) — and blocks_needed(plen2, rem+1)
            # equals the original reservation, so resume admission can
            # never demand more blocks than first admission did.
            prompt2 = (np.concatenate(
                [req.prompt, np.asarray(gen[:-1], np.int32)])
                if len(gen) > 1 else req.prompt)
            self._push_resume_q(dataclasses.replace(
                req, prompt=prompt2, max_new_tokens=slot.remaining + 1,
                blocked_ticks=0,
                resume=_ResumeInfo(orig=req, last_token=int(gen[-1]),
                                   remaining=slot.remaining,
                                   t_first=slot.t_first)))
        self._clear_slot(i)

    def _push_swap_resume(self, entry: _SwapResume):
        self._swap_resume.append(entry)
        self._swap_resume.sort(
            key=lambda e: (-e.req.priority, e.req.request_id))

    def _push_resume_q(self, req: Request):
        # re-order IN PLACE: admission may hold a reference to this
        # deque across a preemption that pushes here (the retry loop)
        self._enqueue(self._resume_q, req)
        if len(self._resume_q) > 1:
            items = sorted(self._resume_q,
                           key=lambda r: (-r.priority, r.request_id))
            self._resume_q.clear()
            self._resume_q.extend(items)

    def _next_admit(self) -> Tuple[Deque, Request]:
        """Pick the next request to admit and the queue it lives in.

        With preemption off and the predictive gate disarmed: resume
        entries (there are none unless preemption ran) then strict
        submit FIFO.  With preemption armed — or the predictive
        admission gate armed — the choice spans BOTH queues by
        ``(-priority, request_id)``: a priority submit is a scheduling
        request; parking it behind a blocked lower-priority
        recompute-resume head would undo the victim selector's work one
        queue position earlier (and vice versa, a resume entry never
        jumps a higher-priority submit).  The predictive control plane
        needs the same order for a different reason: its gate DEFERS
        over-SLO batch work at the queue head, and strict FIFO would
        let that deferred head keep head-of-line-blocking the
        interactive class whose deadline the deferral protects.
        Scanning the resume queue first makes resume entries win exact
        ties, though ids are unique so ties cannot actually occur."""
        if self.preempt == "off" and not self.admission_armed():
            src = self._resume_q if self._resume_q else self._queue
            return src, src[0]
        best: Optional[Tuple[Tuple[int, int], Deque, Request]] = None
        for q in (self._resume_q, self._queue):
            for r in q:
                key = (-r.priority, r.request_id)
                if best is None or key < best[0]:
                    best = (key, q, r)
        assert best is not None
        return best[1], best[2]

    def _free_slots(self) -> List[int]:
        """The slots a request can enter.  The cursor engine's mid-prefill
        slot owns a kv chain but no ``_Slot`` yet — it is NOT free."""
        busy = -1 if self._prefill is None else self._prefill.slot
        return [i for i, s in enumerate(self._slots)
                if s is None and i != busy]

    def _service_swap_resumes(self):
        """Admission preamble: restore swapped-out requests (highest
        priority, then oldest, first) into free slots whenever the pool
        can hold their chain again.  A blocked high-priority resume may
        itself preempt a running lower-priority slot — swap-out and
        swap-in compose without ever touching the step program."""
        while self._swap_resume:
            entry = self._swap_resume[0]
            free = self._free_slots()
            if not free:
                return
            si = free[0]
            got = self.kv.resume_swapped(si, entry.record)
            if got is None:
                entry.blocked_ticks += 1
                if not self._try_preempt(priority=entry.req.priority,
                                         rid=entry.req.request_id,
                                         blocked_ticks=entry.blocked_ticks):
                    return
                continue                   # a victim freed room: retry
            self._swap_resume.pop(0)
            req = entry.req
            # restore the EXACT pre-preemption slot state: mirrors,
            # table row, decode budget, original TTFT clock
            self._seat(si, _Slot(req.request_id, entry.remaining,
                                 t_first=entry.t_first, prompt=req.prompt,
                                 req=req),
                       entry.last_token, entry.position, req.sampling)
            # re-register the prompt so prefix sharing resumes (the
            # round trip preserved per-block dtype tags, so mixed-mode
            # re-registration never re-demotes an int8 block)
            self.kv.register_prompt_upto(si, req.prompt,
                                         int(req.prompt.size))
            self._rlog.event(req.uid, "swapped_in", engine=self._eid,
                             slot=int(si), blocks=int(got))
            self._note_resumed(req, "swap", si)

    def preempt_signature(self) -> str:
        """SHA-256 over the ordered preemption-decision log (victim,
        waiter, tick, mode, progress per decision) — the byte-stability
        gate loadgen's saturated smoke replays: identical traffic must
        reproduce identical victim selection."""
        blob = json.dumps(self._preempt_log, sort_keys=True,
                          separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()

    @property
    def preempt_decisions(self) -> List[Dict[str, object]]:
        return list(self._preempt_log)

    # -- cross-worker migration (ISSUE 18) ---------------------------------

    def _refuse_state_migration(self, what: str):
        if self._block:
            raise NotImplementedError(
                f"{type(self._bind).__name__} cannot be served with "
                f"{what}: a request's record carries one last token, not a "
                f"block under denoising and the forwards it has had")
        if self._slot_leaves:
            raise NotImplementedError(
                f"{type(self._bind).__name__} cannot be served with "
                f"{what}: a request's record carries its KV blocks and not "
                f"its fixed-size per-slot state {self._slot_leaves}")
        if self._pool_entry is not None:
            raise NotImplementedError(
                f"{type(self._bind).__name__} cannot be served with "
                f"{what}: no test shows a record's blocks exact on a pool "
                f"of a declared entry ({self._pool_entry.arrays} array(s) "
                f"of {self._pool_entry.width} lanes a position)")

    def export_request(self, rid: int,
                       release: bool = True) -> Optional[Dict[str, object]]:
        """Serialize an ACTIVELY DECODING request for migration to
        another engine: the exact slot state a swap-resume would restore
        (position, last token, decode budget, sampling knobs, SLO
        deadlines, lifecycle uid) plus the request's KV chain by value
        (``BlockManager.export_blocks`` with the jitted one-block reader
        — scale rows travel with their payloads, so quantized blocks
        migrate bit-for-bit).  Returns ``None`` when ``rid`` is not in a
        decode slot (queued / mid-prefill / preempted requests are not
        exportable — migrate them by resubmission instead).

        ``release=True`` (the default) frees the slot and its blocks
        after the copy — the request now lives wherever the record is
        imported; partial output stays readable via ``result()``.  The
        disaggregation flow is: prefill worker decodes the FIRST token,
        exports, decode worker imports and finishes the request."""
        if not self.paged:
            raise RuntimeError(
                "export_request requires the paged cache "
                "(ServingEngine(..., paged=True))")
        self._refuse_state_migration("export_request")
        self._block_movers()
        for i, slot in enumerate(self._slots):
            if slot is None or slot.rid != rid:
                continue
            req = slot.req

            def _read(bid: int):
                return jax.device_get(
                    self._read_block_fn(self._cache, jnp.int32(bid)))

            blocks = self.kv.export_blocks(i, _read)
            nbytes = sum(
                int(a.nbytes) for e in blocks["entries"]
                for a in jax.tree_util.tree_leaves(e["payload"]))
            record = {
                "uid": int(req.uid),
                "prompt": [int(t) for t in req.prompt],
                "generated": list(self._results.get(rid, [])),
                "max_new_tokens": int(req.max_new_tokens),
                "remaining": int(slot.remaining),
                "position": int(self._positions[i]),
                "last_token": int(self._tokens[i]),
                "had_first": bool(slot.t_first > 0.0),
                "sampling": {"temperature": float(req.sampling.temperature),
                             "top_k": int(req.sampling.top_k),
                             "top_p": float(req.sampling.top_p)},
                "priority": int(req.priority),
                "ttft_slo_ms": float(req.ttft_slo_ms),
                "tpot_slo_ms": float(req.tpot_slo_ms),
                "blocks": blocks,
                "payload_bytes": int(nbytes),
            }
            self._m_mig_out.inc()
            self._m_mig_bytes_out.inc(nbytes)
            self._rlog.event(req.uid, "exported", engine=self._eid,
                             slot=int(i),
                             blocks=len(blocks["entries"]),
                             bytes=int(nbytes))
            self._tracer.instant("migration.export", rid=rid,
                                 blocks=len(blocks["entries"]),
                                 bytes=int(nbytes))
            if release:
                self._release(i)
            return record
        return None

    def import_request(self, record: Dict[str, object]) -> Optional[int]:
        """Materialise an exported request into a free slot of THIS
        engine and continue its decode exactly where the exporter
        stopped: blocks land via ``BlockManager.import_blocks`` + the
        jitted one-block writer, host mirrors restore the swap-resume
        way, and the prompt re-registers in the local prefix trie (dtype
        tags preserved — mixed mode never re-demotes).  Returns the
        LOCAL rid (the lifecycle uid in the record is adopted, so the
        request keeps ONE timeline across workers), or ``None`` when no
        free slot or pool room is available right now — the caller keeps
        the record and retries, nothing is consumed."""
        if not self.paged:
            raise RuntimeError(
                "import_request requires the paged cache "
                "(ServingEngine(..., paged=True))")
        self._refuse_state_migration("import_request")
        self._block_movers()
        free = self._free_slots()
        if not free:
            return None
        si = free[0]

        def _write(bid: int, payload):
            self._cache = self._write_block_fn(
                self._cache,
                jax.tree_util.tree_map(jnp.asarray, payload),
                jnp.int32(bid))

        got = self.kv.import_blocks(si, record["blocks"], _write)
        if got is None:
            return None
        uid = int(record["uid"])
        prompt = np.asarray(record["prompt"], np.int32)
        sp = record["sampling"]
        req = Request(
            self._next_rid, prompt, int(record["max_new_tokens"]),
            SamplingParams(temperature=float(sp["temperature"]),
                           top_k=int(sp["top_k"]),
                           top_p=float(sp["top_p"])),
            t_submit=self._clock(), uid=uid,
            ttft_slo_ms=float(record["ttft_slo_ms"]),
            tpot_slo_ms=float(record["tpot_slo_ms"]),
            priority=int(record["priority"]))
        rid = self._next_rid
        self._next_rid += 1
        self._results[rid] = list(record["generated"])
        self._uids[rid] = uid
        # restore the slot the swap-resume way: mirrors, table row,
        # decode budget; the TPOT clock restarts on this engine's clock
        # (cross-process wall clocks don't compare — BASELINE.md
        # "Multi-host accounting conventions")
        self._seat(si, _Slot(rid, int(record["remaining"]),
                             t_first=(self._clock()
                                      if record["had_first"] else 0.0),
                             prompt=prompt, req=req),
                   int(record["last_token"]), int(record["position"]),
                   req.sampling)
        self.kv.register_prompt_upto(si, prompt, int(prompt.size))
        nbytes = int(record.get("payload_bytes", 0))
        self._m_mig_in.inc()
        self._m_mig_bytes_in.inc(nbytes)
        self._rlog.event(uid, "imported", engine=self._eid,
                         slot=int(si), blocks=int(got),
                         bytes=nbytes)
        self._tracer.instant("migration.import", rid=rid,
                             blocks=int(got), bytes=nbytes)
        return rid

    # -- cancellation (ISSUE 16 satellite) ---------------------------------

    def cancel(self, rid: int) -> bool:
        """Tear down request ``rid`` wherever it currently lives —
        queued, awaiting recompute-resume, swapped out on the host tier,
        mid-chunked-prefill, or actively decoding — with refcount-safe
        block free, a ``retired(reason="cancelled")`` lifecycle event
        and rejected-style SLO accounting.  Returns True if the request
        was found and torn down, False if unknown or already finished.
        Partial output (if any) stays readable via ``result()``."""
        for q in (self._queue, self._resume_q):
            for req in q:
                if req.request_id == rid:
                    self._dequeue(q, req)
                    self._finish_cancel(
                        req if req.resume is None else req.resume.orig)
                    return True
        for k, entry in enumerate(self._swap_resume):
            if entry.req.request_id == rid:
                self._swap_resume.pop(k)
                self.kv.drop_swap_record(entry.record)
                self._finish_cancel(entry.req)
                return True
        pf = self._prefill
        if pf is not None and pf.req.request_id == rid:
            # mid-chunked-prefill: the slot owns a kv chain (admission
            # reserved it) but no _Slot/mirror state yet
            self._prefill = None
            if self.paged:
                self.kv.release(pf.slot)
                self._tables[pf.slot] = 0
            self._finish_cancel(
                pf.req if pf.req.resume is None else pf.req.resume.orig)
            return True
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.rid == rid:
                req = slot.req
                self._release(i)
                self._finish_cancel(req)
                return True
        return False

    def _finish_cancel(self, req: Request):
        tokens = len(self._results.get(req.request_id, []))
        self._m_finished.inc()
        self._m_cancelled.inc()
        self._f_retired.labels(engine=self._eid, reason="cancelled").inc()
        self._f_slo_viol.labels(engine=self._eid, kind="cancelled").inc()
        self._rlog.event(req.uid, "retired", engine=self._eid,
                         reason="cancelled", tokens=int(tokens),
                         violation="cancelled")
        self._tracer.instant("serving.cancelled", rid=req.request_id)

    def _init_kv_walk(self):
        """What a span needs to count the flash-decode kernel's block walk
        (:meth:`_kv_walk`): the GQA group, the block length and table
        width the kernel is handed for this cache layout, and how many
        layers read each sliding window (None: the whole prefix)."""
        from ..ops.pallas.decode_attention import contiguous_block_kv
        c = self.config
        if self.paged:
            bk, cols = self.block_len, self.max_blocks
        else:
            try:        # init_kv_cache's granules, where the cache is int8
                bk = contiguous_block_kv(
                    self.max_length,
                    max(1, self.max_length // 128) if self.quantized
                    else None)
            except NotImplementedError:     # no kernel at this length
                bk = self.max_length
            cols = self.max_length // bk
        windows = (self._traits.attention_windows
                   or (None,) * self._kv_layers)
        if self._pool_entry is not None:
            # the declared entry's walk: every head one query group over
            # the one stored entry, tiles and copy groups the layout's; its
            # spans also say how many of the blocks the rows walk are
            # distinct (rows of one shared prefix walk the same blocks).
            # Resolved here, once: the tick calls ``self._kv_walk``
            self._kv_walk_geom = (
                int(self._pool_entry.group), int(bk), int(cols),
                ((None, self._kv_layers),))
            self._kv_walk = self._kv_walk_shared
            return
        self._kv_walk_geom = (
            int(c.num_attention_heads) // int(c.num_key_value_heads),
            int(bk), int(cols),
            tuple((w, windows.count(w)) for w in set(windows)))

    @functools.cached_property
    def _kv_key_bytes(self) -> int:
        """What a key takes over the pool's arrays, as stored: a wide
        pool's copy groups are smaller
        (``ops.pallas.decode_attention.group_blocks``)."""
        from ..ops.pallas.decode_attention import stored_key_bytes
        c, entry = self.config, self._pool_entry
        width, arrays = (
            (entry.width, entry.arrays) if entry is not None
            else (int(c.num_key_value_heads) * int(c.head_dim), 2))
        return stored_key_bytes(
            width, arrays, "int8" if self.quantized else c.dtype)

    def _kv_walk(self, *calls) -> Dict[str, int]:
        """``kv_blocks=`` and ``kv_walk=`` of a tick's or a wave's span:
        over the program's flash-decode calls (``calls``: the positions
        vector and q length of each, as uploaded) and the model's layers,
        the KV blocks the rows' q tiles need and the block slots the
        kernel walks for them (whole copy groups), by the kernel's own
        bounds (``ops.pallas.decode_attention.walk_counts``).  Nothing
        where there is no kernel (the simulator)."""
        if self._kv_walk_geom is None:
            return {}
        from ..ops.pallas.decode_attention import walk_counts
        g, bk, cols, layers = self._kv_walk_geom
        blocks = walk = 0
        for pos, s in calls:
            for window, n in layers:
                kb, kw = walk_counts(pos, s, g, bk=bk, n_cols=cols,
                                     window=window,
                                     key_bytes=self._kv_key_bytes)
                blocks += n * kb
                walk += n * kw
        return {"kv_blocks": blocks, "kv_walk": walk}

    def _regroup(self):
        """Work out which decoding rows walk which leading columns
        together (``self._share``, uploaded as it stands): rows whose
        tables hold the same block ids in the same leading columns — a
        prefix adopted through the trie, and the row that wrote it — are
        cut into tiles of the walk's member rows, each tile read through
        its first row's table.  Only whole blocks BEHIND a row's current
        one count, so what a row shares is fixed from its first decode
        tick to its retirement, and this runs when the set of decoding
        rows changed (``_seat``, ``_clear_slot``), not every tick.  From
        the deepest row down: the rows that share at least half its
        columns with it form its group, walked together over the columns
        they ALL share; fewer than ``_SHARE_FROM`` rows (a group, or its
        last tile) walk alone."""
        from ..ops.pallas.decode_attention import group_blocks
        self._share_stale = False
        share = self._share
        for mirror in share:
            mirror[...] = 0
        members = share.tile_rows.shape[1]
        depth = self._positions // self.block_len * self._active
        left = np.flatnonzero(depth)
        left = left[np.argsort(-depth[left], kind="stable")]
        t = 0
        while left.size >= _SHARE_FROM:
            d = depth[left[0]]
            same = self._tables[left, :d] == self._tables[left[0], :d]
            run = np.minimum(same.cumprod(axis=1).sum(axis=1), depth[left])
            pick = run >= (d + 1) // 2
            rows, n = left[pick], run[pick].min()
            left = left[~pick]
            for i in range(0, rows.size - _SHARE_FROM + 1, members):
                cut = rows[i:i + members]
                share.tile_rows[t] = cut[0]
                share.tile_rows[t, :cut.size] = cut
                share.tile_n[t] = share.n[cut] = n
                share.at[cut] = t * members + np.arange(cut.size)
                t += 1
        # a row outside every tile resumes from nothing: it stays on the
        # blocks the row before it fetched (``_flash_call``'s index map)
        grouped = share.n > 0
        share.at[...] = share.at[np.maximum.accumulate(
            np.where(grouped, np.arange(grouped.size), 0))]
        gb = group_blocks(self.block_len, self._pool_entry.layout.group_keys,
                          self._kv_key_bytes)
        self._share_counts = (
            int(share.tile_n.sum()), int((-(-share.tile_n // gb) * gb).sum()),
            {"rows_grouped": int(grouped.sum()), "shared_tiles": t})

    def _kv_walk_shared(self, *calls) -> Dict[str, int]:
        """:meth:`_kv_walk` for a model that declares its pool's entry
        (``pool_entry``): the same two counts by the layout's own tiles
        and groups, and of the ROWS part alone (the first call), a layer:
        ``rows_depth`` the live rows' summed depths (the positions each
        row's query sees), ``rows_blocks`` the blocks their walks read,
        ``rows_distinct`` how many different physical blocks those are and
        ``rows_positions`` the different positions they hold — rows that
        adopted one prefix hold the same blocks.  Under a two-part walk
        (``_regroup``) the counts are of what it reads: a tile's shared
        columns once, then each row's own from its first column on; and
        ``rows_grouped`` the live rows that sit in a tile, ``shared_tiles``
        the tiles."""
        from ..ops.pallas.decode_attention import walk_counts
        g, bk, cols, ((_, layers),) = self._kv_walk_geom
        layout = self._pool_entry.layout
        share = self._share
        tile_blocks, tile_walk, grouping = (
            self._share_counts if share else (0, 0, {}))
        blocks, walk = layers * tile_blocks, layers * tile_walk
        for i, (pos, s) in enumerate(calls):
            kb, kw = walk_counts(
                pos, s, g, bk=bk, n_cols=cols, latent=layout,
                first=share.n if share and not i else None,
                key_bytes=self._kv_key_bytes)
            blocks += layers * kb
            walk += layers * kw
        live = np.flatnonzero(self._active)
        depth = self._positions[live].astype(np.int64) + 1
        last = (depth - 1) // bk            # a row's last block is its own
        full = [self._tables[i, :n] for i, n in zip(live, last)]
        distinct = len(np.unique(np.concatenate(full))) if full else 0
        own = last + 1 - (share.n[live] if share else 0)
        return {"kv_blocks": blocks, "kv_walk": walk,
                "rows_depth": int(depth.sum()),
                "rows_blocks": tile_blocks + int(own.sum()),
                "rows_distinct": distinct + len(live),
                "rows_positions": distinct * bk
                + int((depth - last * bk).sum()), **grouping}

    def _note_sample_path(self, *knobs) -> str:
        """Name and count the way this tick's sampling epilogue goes:
        ``knobs`` are the (temperature, top_k, top_p) vectors of each
        ``sample_tokens`` call the program makes (a mixed step: the
        rows', then the chunk's), read by the device's own predicates
        (``generation.sample_path``); the tick's name is the heaviest."""
        i = max(sample_path(*k) for k in knobs)
        self._m_sample_path[i].inc()
        return SAMPLE_PATHS[i]

    def _step_inner(self) -> List[int]:
        """THE tick, for every layout: admit (waves into free slots, or
        the cursor engine's one prompt cursor) → draft (spec) → grow
        (paged) → build inputs → dispatch → readback → advance the rows →
        advance the chunk (if one ran) → queued demotions.  Device work is
        ONE step-program call, so a long prompt under the cursor engine
        costs every in-flight decode a bounded, chunk-sized bump per tick
        instead of a whole-prompt stall; a verify step commits 1..k+1
        tokens a row for one pass of the weights, a block step delivers 0
        or a block's tokens a row."""
        span = self._tracer.span
        paged, chunked, spec = self.paged, self.chunked, self.spec
        with span(_ADMIT):
            finished = self._admit_chunked() if chunked else self._admit()
            occ = int(self._active.sum())
            self._set_occupancy(occ)
            pf = self._prefill
            # decode-priority policy: while decodes are active, pending
            # chunks run on alternate ticks only (odd _ticks), halving the
            # prompt-ingest rate to shave the mixed-step TPOT bump
            do_chunk = pf is not None and (
                self._chunk_policy == "prefill" or occ == 0
                or self._ticks % 2 == 1)
        if not occ and not do_chunk:
            return finished
        self._ticks += 1
        own, chunk, clen, draft_ok = {"key": self._ticks}, None, 0, None
        if do_chunk:
            clen = min(self.prefill_chunk, pf.end - pf.cursor)
            cpos, cslot = pf.cursor, pf.slot
        elif chunked:
            # chunk-free tick: the rows-alone program runs, whose chunk
            # part is a stub of ``_stub_chunk`` null positions: contiguous
            # writes drop past max_length, paged writes land in the null
            # block, and no position is a real token (``clen`` 0: none
            # reaches an expert or advances a state)
            cslot, cpos = 0, 0 if paged else self.max_length
            self._m_rows_only.inc()
        if spec:
            # the draft builds the verify window, and growth below needs its
            # real span: an input-building phase of its own, before the grow.
            # A prefilling slot is inactive until its cursor completes, so
            # its window is suspended by construction.  The draft model's
            # seed is this tick's number on the cursor engine and one less
            # on a wave engine: replays of either are byte-stable on it.
            with span(_BUILD), span("serving.draft"):
                drafts, draft_ok, own["draft_probs"] = self._propose_drafts(
                    seed=self._ticks - (not chunked))
                own["tokens"] = np.concatenate(
                    [self._tokens[:, None], drafts], axis=1)
                own["draft_ok"] = draft_ok
        t0 = self._clock()
        with span(_BUILD):
            if self._share and self._share_stale:
                self._regroup()
            rows_pos = self._positions
            if chunked and not paged:
                # non-decoding rows (idle or mid-prefill) write at
                # max_length so the scatter drops them — chunked
                # prefill owns those rows' contents now
                rows_pos = own["positions"] = np.where(
                    self._active, self._positions,
                    self.max_length).astype(np.int32)
            state = {}
            if self._slot_leaves:
                # the state row the chunk part addresses: the cursor's
                # slot, or the null row (a chunk-free tick's stub lands
                # there); the rows part advances the decoding rows alone
                own["cslot"] = cslot if do_chunk else self.num_slots
                state = {"state": "carried" if cpos else "fresh"}
            with span(_ACCOUNT):
                # the rows span's arguments, the gauges and histograms of
                # what goes in: nothing here is read by the device program
                if chunked:
                    self._m_chunk_queue.observe(self._pending_chunks())
                knobs = [(self._temps, self._topk, self._topp)]
                if do_chunk:  # the chunk's one row, as _device_step fills it
                    sp = pf.req.sampling
                    knobs.append((np.float32(sp.temperature),
                                  np.int32(sp.top_k), np.float32(sp.top_p)))
                walks = [(rows_pos, self._row_tokens)]
                if chunked:     # the chunk part, or the stub it is cut to
                    walks.append(([cpos], self.prefill_chunk if do_chunk
                                  else self._stub_chunk))
                facts = dict(sample_path=self._note_sample_path(*knobs),
                             **self._kv_walk(*walks))
                if self._slot_leaves:
                    facts["state_rows"] = occ
                    self._state_live = occ + (pf is not None)
                    self._m_state_live.set(float(self._state_live))
                drafted = int(draft_ok[self._active].sum()) if spec else 0
                diffusion = {}
                if self._block:
                    # what goes in: the live rows' masked positions, and
                    # the rows whose block is mask-free (their forward is
                    # the commit); ``unmasked`` and ``delivered`` follow
                    # the readback
                    live_masked = (self._blocks[self._active]
                                   == self._diffusion.mask_token_id).sum(-1)
                    diffusion = {"block": self._block,
                                 "masked_in": int(live_masked.sum()),
                                 "commits": int((live_masked == 0).sum())}
        rows_span = span(
            "serving.verify" if spec else "serving.decode", slots=occ,
            **facts,
            # the ``decode_parts`` call of the program this tick runs: how
            # often it streams the token-wise weights, over how many parts
            # that hold tokens and how many padded token rows (a chunk-free
            # tick's stub of a chunk part among them), how many of them real
            # (live rows' tokens + the chunk's)
            weight_passes=1, parts=1 + do_chunk,
            pass_rows=(self.num_slots * self._row_tokens + (
                self.prefill_chunk if do_chunk
                else self._stub_chunk * chunked)),
            pass_tokens=occ * (self._block or 1) + drafted + clen,
            **({"drafted": int(draft_ok.sum())} if spec else {}),
            **diffusion)
        chunk_span = (span("serving.chunk", slot=cslot, start=cpos,
                           tokens=clen, **state)
                      if do_chunk else contextlib.nullcontext())
        with rows_span as rows_open, chunk_span:
            if paged:
                with span(_GROW):
                    beyond = self._row_tokens - 1
                    for i, slot in enumerate(self._slots):
                        if slot is None:
                            continue
                        # this tick writes K/V at positions[i] and, spec,
                        # over the row's REAL draft span only: pad-column
                        # writes past the chain steer to the null block, so
                        # no block is ever allocated for a draft that was
                        # never proposed
                        self._grow_row_for_writes(
                            i, int(self._positions[i])
                            + (int(draft_ok[i].sum()) if spec else beyond))
                    if do_chunk:
                        # grow the chain to cover this chunk's real
                        # tokens; pad-tail positions fall past the chain
                        # and steer to the null block (the admission
                        # reservation makes the growth infallible)
                        self.kv.ensure_capacity(cslot, cpos + clen - 1)
                        cdst = self.kv.table_row(cslot,
                                                 self.max_blocks)[None]
                    elif chunked:
                        cdst = np.zeros((1, self.max_blocks), np.int32)
                    self._flush_fresh_scales()
            if chunked:
                chunk = (pf if do_chunk else None, cpos, clen,
                         cdst if paged else cslot)
            traced = self.rows_step_traces
            out = iter(self._device_step(own, chunk))
            # a cursor engine's first chunk-free tick compiles its program
            compiled = self.rows_step_traces > traced
            toks = next(out)
            if self._block:
                # what the forward did, on the tick's span: positions
                # unmasked, and the tokens of the blocks that came back
                # mask-free (delivered now, ``_advance_block``)
                n_unmasked = next(out)
                deliver = self._block_deliveries(toks)
                diffusion.update(
                    unmasked=int(n_unmasked.sum()),
                    delivered=sum(len(d) for d in deliver.values()))
                if rows_open is not None:
                    rows_open.args.update(diffusion)
        now = self._clock()
        with span(_ADVANCE):
            n_acc = next(out) if spec else None
            ctok = next(out) if chunked else None
            with span(_ACCOUNT):
                # what the tick did, for the registry and the cost model
                # (positions are still pre-advance: the depths it read)
                self._m_step_ms.observe((now - t0) * 1e3)
                self._perf_tick((now - t0) * 1e3, occ,
                                chunk_tokens=clen if do_chunk else 0,
                                compiled=compiled)
                if self._model_counters:
                    self._note_model_counters(list(out))
                if self._block:
                    for m, n in zip(self._m_diffusion, (
                            occ, *(diffusion[k] for k in
                                   ("unmasked", "commits", "delivered")))):
                        m.inc(n)
            finished.extend(
                self._advance_block(toks, deliver, now)
                if self._block
                else self._advance_decode_spec(toks, n_acc, draft_ok, now)
                if spec else self._advance_decode(toks, now))
            if do_chunk:
                finished.extend(
                    self._advance_chunk(pf, clen, int(ctok), now))
            self._apply_demotions()
        return finished

    def _pack(self, table, value, bucket: int = 0) -> List:
        """A program's arguments after ``(params, cache)`` from
        ``value(operand)``, the host's value of each row of ``table``: ONE
        buffer that holds every small operand at its place in the layout
        (``_lay_out``; ``_unpack`` is its inverse on the device), then the
        operands that keep a transfer of their own.  One ``_put`` each.

        The buffer is a tick's own, allocated fresh: nothing writes it
        after the ``_put``, so an upload that aliases host memory (the CPU
        backend's zero-copy ``device_put``) or a loop that builds tick
        n + 1 before tick n's program has read its operands (a
        dispatch-ahead loop) still hands each program its own tick's
        values.  The mirrors it copies FROM are mutated in place once the
        tick's tokens are read back; they never cross themselves."""
        lay = self._layout(table)
        shape = self._buffer_shape(table, bucket)
        buf = np.zeros(shape[-1], np.int32)
        bits = buf.view(np.float32)
        for o, at in lay.packed:
            flat = (np.append(self._key_bits, np.int32(value(o)))
                    if _is_key(o.dtype) else np.asarray(value(o)).reshape(-1))
            (bits if o.dtype is np.float32 else buf)[
                at:at + flat.size] = flat
        return [_put(buf.reshape(shape))] + [
            _put(np.asarray(value(o), o.dtype)) for o in lay.own]

    def _upload(self, table, own, bucket: int = 0) -> List:
        """The upload of ``serving.build_inputs``, for either program
        (``serving.upload``): the mirrors and the tick's (the wave's)
        ``own`` values packed by the table's layout (``_pack``) and sent
        as one buffer — ``transfers`` says how many host->device calls
        that took (1, or 2 for an engine whose table holds an operand
        that is not small), ``bytes`` what crossed, the layout's padding
        included, ``operands`` the table's rows."""
        lay = self._layout(table)
        with self._tracer.span(_UPLOAD, operands=len(table),
                               bytes=lay.nbytes(bucket),
                               transfers=1 + len(lay.own)):
            return self._pack(
                table, lambda o: (o.src if o.src.__class__ is np.ndarray
                                  else own[o.src]), bucket)

    def _device_step(self, own, chunk) -> List[np.ndarray]:
        """The tick's device seam: build and upload the step program's
        operands, call it, fetch what it returned but the cache (the tick's
        ONE host sync), as host arrays in ``_step_outputs`` order.  ``own``
        holds the tick's own operand values by name (the rest are mirrors);
        ``chunk`` the cursor engine's ``(cursor or None, start, tokens,
        destination)``.  ``fleet_sim.SimEngine`` overrides this and nothing
        else of the tick."""
        span = self._tracer.span
        with span(_BUILD):
            if chunk is not None:
                pf, own["cpos"], clen, own["cdst"] = chunk
                cids = np.full((1, self.prefill_chunk), self.pad_token_id,
                               np.int32)
                sp = SamplingParams() if pf is None else pf.req.sampling
                if pf is not None:
                    cids[0, :clen] = pf.req.prompt[pf.cursor:pf.cursor + clen]
                own.update(
                    cids=cids, clen=clen,
                    ctemps=np.full((1,), sp.temperature, np.float32),
                    ctopk=np.full((1,), sp.top_k, np.int32),
                    ctopp=np.full((1,), sp.top_p, np.float32))
            args = self._upload(self._step_table, own)
        # a cursor engine's tick with no chunk runs the rows-alone program
        fn = self._step_fn if own.get("clen", 1) else self._rows_fn
        with span(_DISPATCH, leaves=self._program_leaves):
            *out, self._cache = fn(self._params, self._cache, *args)
        with span(_READBACK):
            return jax.device_get(out)

    def _advance_decode(self, nxt: np.ndarray, now: float) -> List[int]:
        """Per-slot bookkeeping after a decode/mixed step's token fetch."""
        finished: List[int] = []
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            tok = int(nxt[i])
            self._positions[i] += 1
            self._tokens[i] = tok
            self._results[slot.rid].append(tok)
            slot.remaining -= 1
            self._m_tokens.inc()
            reason = self._finish_reason(tok, slot, i)
            if reason is not None:
                finished.append(slot.rid)
                self._retire(slot, i, reason, now)
        return finished

    # -- block-diffusion scheduler (block rows) -----------------------------

    def _prompt_commit(self, req: Request) -> int:
        """The prompt tokens the cursor ingests: all of them, or for a
        block-diffusion model the prompt's whole blocks (the rest open the
        first block, already unmasked)."""
        n = int(req.prompt.size)
        return n - n % self._block if self._block else n

    def _reserved_new(self, prompt_len: int, max_new_tokens: int) -> int:
        """The new positions a request reserves: ``max_new_tokens``, or for
        a block-diffusion model up to the end of the block the last token
        falls in (a forward writes its block whole)."""
        return int(max_new_tokens) + -(
            int(prompt_len) + int(max_new_tokens)) % (self._block or 1)

    def _open_block(self, i: int, given=()):
        """Open slot ``i``'s next block at its position: ``given`` (the
        prompt's tokens past its last whole block) and then mask tokens."""
        slot = self._slots[i]
        self._blocks[i] = self._diffusion.mask_token_id
        self._blocks[i, :len(given)] = given
        slot.forwards, slot.given = 0, len(given)
        slot.unmasked_at = np.zeros((self._block,), np.int32)

    def _block_deliveries(self, new: np.ndarray) -> Dict[int, List[int]]:
        """Per slot whose block went in with a mask and came back
        mask-free, the tokens it delivers now: the block's generated
        positions, cut at the request's budget and after an EOS."""
        mask_id = self._diffusion.mask_token_id
        out = {}
        for i, slot in enumerate(self._slots):
            if (slot is None or (new[i] == mask_id).any()
                    or not (self._blocks[i] == mask_id).any()):
                continue
            toks = [int(t) for t in
                    new[i, slot.given:slot.given + slot.remaining]]
            if self.eos_token_id in toks:
                toks = toks[:toks.index(self.eos_token_id) + 1]
            out[i] = toks
        return out

    def _advance_block(self, new: np.ndarray, deliver: Dict[int, List[int]],
                       now: float) -> List[int]:
        """Per-slot bookkeeping after a block step.  A row whose block went
        in mask-free has COMMITTED it (that forward's K/V are final):
        advance by a block, open the next.  Any other row had a denoising
        forward: note which positions it unmasked and at which
        forward-in-block; if the block came back mask-free its tokens are
        delivered now (``deliver``) — the request's first delivery is its
        first token — and the request retires here when they end it, its
        commit forward being the next tick otherwise."""
        mask_id = self._diffusion.mask_token_id
        finished: List[int] = []
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            was = self._blocks[i] == mask_id
            if not was.any():
                self._positions[i] += self._block
                self._open_block(i)
                continue
            slot.forwards += 1
            slot.unmasked_at[was & (new[i] != mask_id)] = slot.forwards
            self._blocks[i] = new[i]
            toks = deliver.get(i)
            if toks is None:
                continue
            if slot.t_first == 0.0:
                slot.t_first = now
                self._note_first_token(slot.req, now)
            self._results[slot.rid].extend(toks)
            self._unmasked_at[slot.rid].extend(
                int(f) for f in
                slot.unmasked_at[slot.given:slot.given + len(toks)])
            slot.remaining -= len(toks)
            self._m_tokens.inc(len(toks))
            reason = ("eos" if toks[-1] == self.eos_token_id
                      and self.eos_token_id is not None
                      else "max_new_tokens" if slot.remaining <= 0
                      else "max_length" if (int(self._positions[i])
                                            + 2 * self._block
                                            > self.max_length) else None)
            if reason is not None:
                finished.append(slot.rid)
                self._retire(slot, i, reason, now)
        return finished

    def unmask_steps(self, rid: int) -> List[int]:
        """Beside :meth:`result`, for a block-diffusion model: per token
        delivered so far, the forward-in-block (1 = the block's first
        denoising forward) at which it was unmasked.  With the tokens it
        says what each of a block's forwards was fed — at forward f the
        positions unmasked at a forward before f, the rest masked — which is
        what a reference needs to recompute the logits behind a token."""
        if not self._block:
            raise RuntimeError(
                f"{type(self._bind).__name__} does not generate by "
                f"diffusion over blocks: its tokens have no unmask order")
        return list(self._unmasked_at[rid])

    # -- speculative-decode scheduler (verify steps) -----------------------

    def _propose_drafts(self, seed: int) -> Tuple[np.ndarray, np.ndarray,
                                                  np.ndarray]:
        """The draft phase: ask each slot's drafter (engine default or
        the request's ``submit(drafter=...)`` override) for up to
        ``spec_k`` tokens, capped so an accepted window can never
        overrun the row's token budget (``remaining - 1`` drafts ⇒ at
        most ``remaining`` commits) or ``max_length - 1`` (every window
        write stays in bounds).

        Host proposers (n-gram and injected scripted drafters) run per
        slot and carry ONE-HOT proposal distributions — deterministic
        q, so sampled rows accept draft d w.p. p_target(d) and greedy
        rows keep the exact prefix-match rule.  Device proposers (the
        draft model) run ONE batched draft step per tick across all
        their slots and return the true proposal softmax q.  Returns
        the (num_slots, k) draft matrix (pad-filled), the bool
        real-proposal mask, and the (num_slots, k, vocab) f32 q stack
        (all-zero rows at non-proposed columns — the acceptance treats
        those residuals as the plain target distribution)."""
        s, k = self.num_slots, self.spec_k
        vocab = self.config.vocab_size
        drafts = np.full((s, k), self.pad_token_id, np.int32)
        ok = np.zeros((s, k), bool)
        probs = np.zeros((s, k, vocab), np.float32)
        kinds: List[Optional[str]] = [None] * s
        caps = np.zeros((s,), np.int32)
        device_jobs: Dict[int, Dict[int, np.ndarray]] = {}
        device_objs: Dict[int, object] = {}
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            d = self._drafter_for(slot.req.drafter
                                  if slot.req is not None else None)
            if d is None:
                continue
            cap = min(k, slot.remaining - 1,
                      self.max_length - 1 - int(self._positions[i]))
            if cap < 1:
                continue
            caps[i] = cap
            kinds[i] = str(getattr(d, "kind", "custom"))
            hist = np.concatenate(
                [slot.prompt,
                 np.asarray(self._results[slot.rid], np.int32)])
            if getattr(d, "uses_device", False):
                # batch every draft-model row into one device step
                device_objs.setdefault(id(d), d)
                device_jobs.setdefault(id(d), {})[i] = hist
                continue
            prop = np.asarray(d.propose(hist), np.int32)[:cap]
            if prop.size:
                m = int(prop.size)
                drafts[i, :m] = prop
                ok[i, :m] = True
                probs[i, np.arange(m), prop] = 1.0
                self._m_drafted.inc(m)
                self._spec_m(kinds[i])[0].inc(m)
        for did, rows in device_jobs.items():
            dd, dp = device_objs[did].propose_batch(
                rows, self._temps, seed=seed)
            for i in rows:
                m = int(caps[i])
                drafts[i, :m] = dd[i, :m]
                ok[i, :m] = True
                probs[i, :m] = dp[i, :m]
                self._m_drafted.inc(m)
                self._spec_m(kinds[i])[0].inc(m)
        self._tick_drafter_kind = kinds
        return drafts, ok, probs

    def _advance_decode_spec(self, out: np.ndarray, n_acc: np.ndarray,
                             draft_ok: np.ndarray, now: float
                             ) -> List[int]:
        """Per-slot bookkeeping after a verify step: commit each row's
        accepted prefix — stopping AT an EOS inside the window — and
        roll the rejected suffix back.  A multi-token accept is N tokens
        in ONE step everywhere: ``tokens_generated`` += N, ONE
        accepted-per-step observation, ONE retirement, and TPOT stays a
        per-request retirement-time readout (never per-token)."""
        finished: List[int] = []
        kinds = getattr(self, "_tick_drafter_kind", [None] * len(out))
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            n = int(n_acc[i])
            drafted = int(draft_ok[i].sum())
            kind = kinds[i] if i < len(kinds) else None
            km = self._spec_m(kind) if (drafted and kind) else None
            take, reason = n, None
            if self.eos_token_id is not None:
                hits = np.where(out[i, :n] == self.eos_token_id)[0]
                if hits.size:
                    take, reason = int(hits[0]) + 1, "eos"
            toks = [int(t) for t in out[i, :take]]
            self._results[slot.rid].extend(toks)
            self._positions[i] += take
            self._tokens[i] = toks[-1]
            slot.remaining -= take
            self._m_tokens.inc(take)
            self._m_spec_accept.observe(take)
            if km is not None:
                km[4].observe(take)
            if drafted and slot.req is not None:
                self._rlog.event(slot.req.uid, "spec_accept",
                                 engine=self._eid, tokens=int(take),
                                 drafted=int(drafted),
                                 drafter_kind=kind or "custom")
            if drafted:
                # hits = committed draft tokens (the bonus token is free
                # either way); misses = drafts verification rejected —
                # an EOS cut discards verified drafts without counting
                # them on either side
                self._m_draft_hits.inc(take - 1)
                self._m_draft_miss.inc(drafted - (n - 1))
                if km is not None:
                    km[1].inc(take - 1)
                    km[2].inc(drafted - (n - 1))
            if take <= drafted:
                # the row wrote K/V past its accept point: pin the
                # position (contiguous rollback is exactly that — the
                # stale cells above it are rewritten before any mask
                # reads them) and, paged, return draft-only blocks
                self._m_rollbacks.inc()
                if km is not None:
                    km[3].inc()
                if self.paged:
                    self.kv.truncate_to(i, int(self._positions[i]))
                    self._tables[i] = self.kv.table_row(i,
                                                        self.max_blocks)
            if reason is None:
                reason = self._finish_reason(toks[-1], slot, i)
            if reason is not None:
                finished.append(slot.rid)
                self._retire(slot, i, reason, now)
        return finished

    # -- chunked-prefill scheduler (mixed steps) ---------------------------

    def _admit_chunked(self) -> List[int]:
        """Move the FIFO head into a free slot as a partially-prefilled
        request — a cursor, not a prefill dispatch.  One prompt streams
        at a time (FIFO order; the chunk operand is single-slot by
        construction).  Queue-wait is recorded ONCE here — a request
        admitted at tick t waits zero extra queue time for its chunks."""
        if self.paged:
            self._service_swap_resumes()
        if (self._prefill is not None
                or not (self._resume_q or self._queue)):
            return []
        free = self._free_slots()
        if not free:
            return []
        src, req = self._next_admit()
        occ = self.num_slots - len(free)
        live = int(self._positions[self._active].sum()) if occ else 0
        if self._admission_defer(req, occ + 1,
                                 live + int(req.prompt.size),
                                 chunk_tokens=self.prefill_chunk):
            self._defer(req)
            return []
        si = free[0]
        m = self._pool_admit(si, req) if self.paged else 0
        if m is None:
            return []
        # remove by IDENTITY: a preemption inside the retry loop may
        # have re-ordered the resume queue under us
        self._dequeue(src, req)
        if self.quantized and not self.paged:
            # chunked admission streams into a reused row: drop the
            # previous tenant's granule scales before the first chunk
            self._cache = self._row_reset_fn(self._cache, jnp.int32(si))
        now = self._clock()
        self._m_prefill_total.inc(int(req.prompt.size))
        if req.resume is None:
            req.t_admit = now
            self._m_queue_wait.observe((now - req.t_submit) * 1e3)
            self._rlog.event(req.uid, "admitted", engine=self._eid,
                             slot=int(si),
                             queue_wait_ms=(now - req.t_submit) * 1e3,
                             blocked_ticks=int(req.blocked_ticks),
                             prefix_hit_tokens=int(m))
        self._prefill = _Prefill(req, si, int(m), self._prompt_commit(req))
        if self._prefill.cursor >= self._prefill.end:
            # a prompt shorter than a block commits nothing: it opens the
            # first block whole
            self._prefill = None
            self._install(req, si, None, now)
        return []

    def _advance_chunk(self, pf: _Prefill, clen: int, ctok: int,
                       now: float) -> List[int]:
        """Account one ingested chunk; when it completes the prompt, the
        sampled ``ctok`` is the request's first token and the slot flips
        from prefilling to decoding."""
        pf.cursor += clen
        self._m_chunks.inc()
        self._m_chunk_tokens.inc(clen)
        self._m_prefill_computed.inc(clen)
        self._rlog.event(pf.req.uid, "prefill_chunk", engine=self._eid,
                         tokens=int(clen), cursor=int(pf.cursor))
        if self.paged:
            # register the now-written full blocks for prefix sharing —
            # never earlier: an unwritten block must not satisfy a lookup
            self.kv.register_prompt_upto(pf.slot, pf.req.prompt, pf.cursor)
        if pf.cursor < pf.end:
            return []
        self._prefill = None
        return ([pf.req.request_id]
                if self._install(pf.req, pf.slot, ctok, now) else [])

    def _chunks_of(self, req: Request) -> int:
        """The chunks the cursor takes a queued prompt in."""
        return -(-self._prompt_commit(req) // self.prefill_chunk)

    def _enqueue(self, q: Deque[Request], req: Request):
        """Append ``req`` to the submit or the resume queue ``q``."""
        q.append(req)
        self._queued_chunks += self._chunks_of(req)

    def _dequeue(self, q: Deque[Request], req: Request):
        """Take ``req`` out of the submit or the resume queue ``q``."""
        q.remove(req)
        self._queued_chunks -= self._chunks_of(req)

    def _pending_chunks(self) -> int:
        """Chunks still to ingest: the active prompt's remainder plus
        every queued prompt's worth (the chunk-queue depth histogram),
        the latter from the count kept at the queues' ends: a walk of a
        backlog of thousands is a percent of a tick."""
        n = self._queued_chunks
        if self._prefill is not None:
            n += -(-(self._prefill.end - self._prefill.cursor)
                   // self.prefill_chunk)
        return n

    def drain(self) -> List[Tuple[int, List[int]]]:
        """Run ticks until every submitted request completes; returns
        ``[(request_id, generated_tokens)]`` in arrival order (outputs end
        at EOS inclusive — no pad tail, unlike the fixed-shape
        ``generate()`` rows)."""
        while (self._queue or self._resume_q or self._swap_resume
               or self._prefill is not None
               or any(s is not None for s in self._slots)):
            self.step()
        return [(rid, list(toks))
                for rid, toks in sorted(self._results.items())]

    def result(self, rid: int) -> List[int]:
        """Tokens generated so far for ``rid`` (complete once finished)."""
        return list(self._results[rid])

    @property
    def num_active(self) -> int:
        # _slots and _active are kept in lockstep (_clear_slot /
        # admission); list.count beats a numpy reduction at this size,
        # and the router's least-loaded probe calls this per replica
        # per submit — 1.6M times in a 100k-request fleet replay
        return self.num_slots - self._slots.count(None)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def num_pending(self) -> int:
        """Requests admitted but still prefilling (chunked mode: the
        prompt whose chunks are streaming in; wave mode: always 0 —
        admission prefills in the same tick)."""
        return int(self._prefill is not None)

    @property
    def num_preempted(self) -> int:
        """Preempted requests awaiting resume (swapped-out chains parked
        on the host tier plus recompute re-prefills still queued)."""
        return len(self._swap_resume) + len(self._resume_q)

    @property
    def pending_chunks(self) -> int:
        """Prompt chunks still to ingest (chunked mode; wave mode: 0) —
        the capacity signal BASELINE.md names, and the load term the dp
        replica router ranks engines by."""
        return self._pending_chunks() if self.chunked else 0

    # -- static analysis (graph lint) --------------------------------------

    def _lint_args(self, prefill_bucket: Optional[int] = None,
                   rows: Optional[int] = None) -> Tuple:
        """Representative arguments for an ABSTRACT trace of the step
        program (with ``prefill_bucket``: of the prefill program at that
        bucket length, with the rows the engine runs there or with
        ``rows``): the operand table's fill values packed as a
        tick's are (``_pack``), so the lint sees the program the scheduler
        runs — tests/test_step_signature.py holds these to the arguments
        of a real tick and a real wave."""
        table = (self._step_table if prefill_bucket is None
                 else self._wave_tables[
                     rows or self._wave_rows(prefill_bucket)])
        bucket = prefill_bucket or 0
        return (self._params, self._cache, *self._pack(
            table, lambda o: 0 if _is_key(o.dtype) else np.full(
                [bucket if d is None else d for d in o.shape],
                o.fill, o.dtype), bucket))

    def lint_step(self, mesh=None):
        """Graph-lint this engine's once-jitted step function (one
        abstract trace; the TrackedFunction's stored donate_argnums are
        honoured).  Returns the finding list — the serving contract is
        that it is EMPTY; ``FLAGS_graph_lint`` arms the same check at
        the first scheduler tick.

        ``mesh`` (a jax Mesh/AbstractMesh, ``{axis: size}`` dict, or a
        string like ``"mp2dp2"``) adds the mesh rule set, linting the
        step under this engine's DECLARED shardings
        (:func:`~paddle_tpu.models.generation.decode_mesh_specs`) —
        the same layout ``_place_on_mesh`` commits when a hybrid mesh
        is active, checked without any devices.

        The KERNEL pre-flight (ISSUE 14) rides the same call: the
        findings of :meth:`kernel_preflight` — the Pallas kernels this
        engine's dispatch would select at TPU scale — merge into the
        returned list under the shared deterministic ordering."""
        from .. import static_analysis as _sa
        if mesh is None:
            graph = _sa.analyze(self._step_fn, *self._lint_args())
        else:
            minfo = _sa.MeshInfo.of(mesh)
            graph = _sa.analyze(
                self._step_fn, *self._lint_args(), mesh=minfo,
                in_shardings=self._mesh_step_shardings(minfo))
        findings = list(graph) + list(self.kernel_preflight()["findings"])
        return _sa._sort_findings(findings)

    def _kernel_specs(self):
        """The KernelSpecs this engine's dispatch would select, PROJECTED
        to the Pallas-eligible regime.  Test configs run tiny CPU
        geometry (head_dim 16, max_length 64) that dispatch routes to
        XLA math; the kernels only ever see TPU-scale shapes, so the
        pre-flight analyzes this engine's LAYOUT (paged/contiguous,
        chunked/spec q shapes, kv dtype, block structure) at the
        smallest geometry the kernel would actually accept: head_dim
        rounded up to one lane tile, cache length up to
        FLAGS_decode_attention_min_len, paged block_len up to 128.
        A 'mixed' pool keeps bf16 device blocks (only 'int8' changes
        program shapes), so mixed engines get the bf16 specs.

        On a model-parallel mesh the kernel runs PER SHARD under
        shard_map — kv-heads are mp-sharded — so the pre-flighted
        geometry divides both head counts by the mp degree (that is
        the program each device actually compiles; whole-model heads
        would overstate VMEM by mp×)."""
        from .. import static_analysis as _sa
        extra = self._traits.kernel_specs
        if self._pool_entry is not None:
            # the registry's decode-attention spec models K and V rows of
            # Hkv·D; a declared entry's walk has no spec there yet
            return list(extra([self._pass_rows])) if extra else []
        lanes = 128
        c = self.config
        hkv = int(c.num_key_value_heads)
        hq = int(c.num_attention_heads)
        mp = (dict(getattr(self.mesh, "shape", {})).get("mp", 1)
              if self.mesh is not None else 1)
        shard = ""
        if mp > 1 and hq % mp == 0 and hkv % mp == 0:
            hq, hkv = hq // mp, hkv // mp
            shard = f",mp{mp}-shard"
        d_p = max(lanes, -(-int(c.head_dim) // lanes) * lanes)
        min_len = int(_flags.flag("decode_attention_min_len"))
        quantized = self.quantized
        layout = "paged" if self.paged else "contiguous"
        # q shapes per step mode: the decode rows (or the spec-verify
        # window), plus the chunked-prefill q chunk when armed
        shapes = [(self.num_slots, self.spec_k + 1, "spec_verify")
                  if self.spec
                  else (self.num_slots, self._block, "block_decode")
                  if self._block else (self.num_slots, 1, "decode")]
        if self.chunked:
            shapes.append((1, self.prefill_chunk, "chunked_prefill"))
        specs = []
        for b, s, label in shapes:
            tag = (f"{layout}{'+int8' if quantized else ''},"
                   f"{label},s={s}{shard}")
            if self.paged:
                bl_p = max(lanes, -(-self.block_len // lanes) * lanes)
                mb_p = max(self.max_blocks, -(-min_len // bl_p))
                specs.append(_sa.decode_attention_spec(
                    b, s, hq, hkv, d_p, block_len=bl_p,
                    max_blocks=mb_p,
                    num_blocks=self.num_slots * mb_p + 1,
                    num_layers=self._kv_layers,
                    quantized=quantized, variant=tag))
                # a window layer's call of the same kernel: the block
                # walk starts at the window's first block
                specs.extend(_sa.decode_attention_spec(
                    b, s, hq, hkv, d_p, block_len=bl_p, max_blocks=mb_p,
                    num_blocks=self.num_slots * mb_p + 1,
                    num_layers=self._kv_layers, window=w,
                    variant=f"{tag},window={w}")
                    for w in sorted(set(self._windows)))
            else:
                kv_p = max(min_len,
                           -(-self.max_length // lanes) * lanes)
                specs.append(_sa.decode_attention_spec(
                    b, s, hq, hkv, d_p, kv_len=kv_p,
                    quantized=quantized,
                    # init_kv_cache's granule layout: one scale per
                    # 128-token granule (kv_p is lane-aligned above)
                    n_granules=kv_p // lanes if quantized else None,
                    variant=tag))
        if extra is not None:
            # kernels only this model's steps build, token-wise: once over
            # the tokens of the step program's one pass
            specs.extend(extra([self._pass_rows]))
        return specs

    def kernel_preflight(self, rules=None) -> Dict[str, object]:
        """Static pre-flight of the Pallas kernels this engine's
        dispatch would select (ISSUE 14): per-kernel VMEM footprint,
        index-map bounds, alignment, and streamed-bytes checks — no
        compile, no device.  Returns ``{"findings", "kernels",
        "vmem_bytes" (max over kernels), "vmem_budget_bytes",
        "vmem_budget_frac", "streamed_bytes" (sum)}`` and publishes the
        ``kernels.predicted_*`` gauges.  Memoized for the default rule
        set (the specs depend only on ctor config)."""
        from .. import static_analysis as _sa
        if rules is None and self._kernel_preflight_cache is not None:
            return self._kernel_preflight_cache
        specs = self._kernel_specs()
        findings = _sa.analyze_kernels(specs, rules=rules)
        reports = [_sa.kernel_report(s, rules=rules) for s in specs]
        budget = int(_flags.flag("kernel_lint_vmem_bytes"))
        vmem = max((r["vmem_bytes"] for r in reports), default=0)
        streamed = sum(r["streamed_bytes"] for r in reports)
        out = {
            "findings": findings,
            "kernels": reports,
            "vmem_bytes": int(vmem),
            "vmem_budget_bytes": budget,
            "vmem_budget_frac": (vmem / budget) if budget else 0.0,
            "streamed_bytes": int(streamed),
        }
        reg = _obs.default_registry()
        reg.gauge("kernels.predicted_vmem_bytes",
                  "max per-grid-step VMEM footprint over the engine's "
                  "pre-flighted kernels").labels(
                      engine=self._eid).set(float(vmem))
        reg.gauge("kernels.predicted_streamed_bytes",
                  "summed per-call streamed-bytes model over the "
                  "engine's pre-flighted kernels").labels(
                      engine=self._eid).set(float(streamed))
        if rules is None:
            self._kernel_preflight_cache = out
        return out

    def _mesh_step_shardings(self, minfo):
        """Per-arg declared shardings for the step signature: params and
        cache per :func:`decode_mesh_specs`, the packed buffer (token/
        position/mask vectors, block tables, the tick's number) and an
        operand with a transfer of its own replicated — every device needs
        them whole."""
        param_specs, cache_spec, _ = decode_mesh_specs(
            self._bind, self._params, minfo.names,
            paged_cache=self.paged, quantized_cache=self.quantized)
        return (param_specs, cache_spec) + (None,) * (
            self._program_arity(self._step_table) - 2)

    def mesh_preflight(self, mesh=None, rules=None) -> Dict[str, object]:
        """Mesh pre-flight of the once-jitted step (ISSUE 8): findings
        (graph-lint + mesh rules), the per-axis collective-cost report,
        and the per-device HBM-liveness estimate, all from ONE abstract
        trace under this engine's declared shardings — run BEFORE any
        mesh compile, on a host that need not have the devices.

        The HBM estimate is cross-checked against ``cache_hbm_bytes``:
        the predicted per-device cache bytes, scaled back by the
        cache's shard count, must match within
        ``FLAGS_graph_lint_hbm_tol`` or an ``hbm-liveness`` error
        finding is appended (``cache_check`` carries the numbers).
        Predicted comm bytes per axis and predicted peak HBM land in
        the observability registry as ``mesh.predicted_comm_bytes`` /
        ``mesh.predicted_peak_hbm_bytes`` gauges, and in the serving
        bench rows as ``mesh_preflight``."""
        from .. import static_analysis as _sa
        if mesh is None:
            mesh = self.mesh
        if mesh is None:
            from ..distributed import env as _denv
            mesh = _denv.active_mesh()
            if mesh is None:
                raise ValueError(
                    "mesh_preflight needs a mesh: pass one (e.g. "
                    "'mp2dp2'), construct the engine with mesh=..., or "
                    "activate a hybrid group")
        minfo = _sa.MeshInfo.of(mesh)
        pf = _sa.preflight(self._step_fn, *self._lint_args(),
                           mesh=minfo, rules=rules,
                           in_shardings=self._mesh_step_shardings(minfo))
        hbm = pf["hbm"]
        cb = self.cache_hbm_bytes
        predicted = hbm["cache_bytes_per_device"] * hbm["cache_shards"]
        tol = float(_flags.flag("graph_lint_hbm_tol"))
        rel = abs(predicted - cb) / cb if cb else 0.0
        pf["cache_check"] = {
            "engine_cache_hbm_bytes": int(cb),
            "predicted_cache_bytes": int(predicted),
            "cache_bytes_per_device": int(hbm["cache_bytes_per_device"]),
            # informational: the KV tier's pinned host-RAM entitlement
            # — host-side by design, so it never enters the HBM
            # liveness comparison above
            "host_tier_bytes": int(self.host_cache_bytes),
            "rel_err": round(rel, 6), "tol": tol, "ok": rel <= tol}
        if rel > tol:
            pf["findings"].append(_sa.Finding(
                "hbm-liveness", "error", "",
                f"liveness estimate of the cache operand "
                f"({predicted} bytes over {hbm['cache_shards']} "
                f"shard(s)) disagrees with cache_hbm_bytes ({cb}) "
                f"beyond tol {tol} — the step signature and the "
                f"engine's cache accounting have drifted",
                bytes=int(abs(predicted - cb))))
        reg = _obs.default_registry()
        for axis, row in pf["comm"]["per_axis"].items():
            reg.gauge(
                "mesh.predicted_comm_bytes",
                "pre-flight predicted collective bytes per step, per "
                "mesh axis").labels(engine=self._eid, axis=axis).set(
                    row["bytes_per_step"])
        reg.gauge(
            "mesh.predicted_peak_hbm_bytes",
            "pre-flight predicted peak HBM per device for one step"
            ).labels(engine=self._eid).set(hbm["peak_bytes_per_device"])
        if (self.mesh is not None
                and minfo.axes == _sa.MeshInfo.of(self.mesh).axes):
            pf["placement_check"] = self.mesh_placement_check(pf)
        return pf

    def mesh_placement_check(self, pf) -> Dict[str, object]:
        """Predicted-vs-ACTUAL placement cross-check for a mesh engine
        (ISSUE 9 gauge hardening): the pre-flight's per-device HBM
        numbers are estimates from an abstract trace; this engine's
        params/cache are REAL ``device_put`` footprints.  Measured
        per-device cache bytes (max over mesh devices of the placed
        shards) must match ``hbm.cache_bytes_per_device`` within
        FLAGS_graph_lint_hbm_tol, and measured resident bytes
        (params + cache per device) must not exceed the predicted peak
        beyond the same tolerance.  Drift appends a structured
        ``hbm-liveness`` error finding to ``pf["findings"]`` — never a
        bare assert — and the measured number lands in the registry as
        ``mesh.measured_cache_bytes_per_device``."""
        from .. import static_analysis as _sa
        per_dev_cache: Dict[object, int] = {}
        per_dev_params: Dict[object, int] = {}
        for tree, acc in ((self._cache, per_dev_cache),
                          (self._params, per_dev_params)):
            for leaf in jax.tree_util.tree_leaves(tree):
                for sh in leaf.addressable_shards:
                    acc[sh.device] = (acc.get(sh.device, 0)
                                      + int(sh.data.nbytes))
        measured_cache = max(per_dev_cache.values())
        measured_resident = max(
            per_dev_cache.get(d, 0) + per_dev_params.get(d, 0)
            for d in per_dev_cache)
        hbm = pf["hbm"]
        predicted_cache = int(hbm["cache_bytes_per_device"])
        predicted_peak = int(hbm["peak_bytes_per_device"])
        tol = float(_flags.flag("graph_lint_hbm_tol"))
        rel = (abs(measured_cache - predicted_cache) / predicted_cache
               if predicted_cache else 0.0)
        cache_ok = rel <= tol
        peak_ok = measured_resident <= predicted_peak * (1.0 + tol)
        if not cache_ok:
            pf["findings"].append(_sa.Finding(
                "hbm-liveness", "error", "",
                f"placed cache footprint ({measured_cache} bytes on the "
                f"fullest device) drifts from the pre-flight prediction "
                f"({predicted_cache}) beyond tol {tol} — the declared "
                f"step shardings and the committed placement disagree",
                bytes=int(abs(measured_cache - predicted_cache))))
        if not peak_ok:
            pf["findings"].append(_sa.Finding(
                "hbm-liveness", "error", "",
                f"placed resident bytes (params+cache "
                f"{measured_resident}/device) exceed the pre-flight "
                f"peak prediction ({predicted_peak}) beyond tol {tol} — "
                f"the liveness estimator is missing real residency",
                bytes=int(measured_resident - predicted_peak)))
        _obs.default_registry().gauge(
            "mesh.measured_cache_bytes_per_device",
            "actual device_put cache footprint of a mesh-placed engine "
            "(max over mesh devices)").labels(engine=self._eid).set(
                measured_cache)
        return {"measured_cache_bytes_per_device": int(measured_cache),
                "predicted_cache_bytes_per_device": predicted_cache,
                "measured_resident_bytes_per_device":
                    int(measured_resident),
                "predicted_peak_hbm_bytes_per_device": predicted_peak,
                "rel_err": round(rel, 6), "tol": tol,
                "ok": bool(cache_ok and peak_ok)}

    def observe_dequant_error(self, max_abs_logit_delta: float):
        """Record one int8-KV parity-oracle observation — the max
        absolute logit delta vs a bf16 reference run on the same trace —
        into the ``serving.kv_dequant_error`` summary.  Called by the
        oracle tests and the ``int8_serving`` bench section; the serving
        hot path never computes logits twice."""
        self._m_dequant_err.observe(float(max_abs_logit_delta))

    @property
    def cache_hbm_bytes(self) -> int:
        """Bytes of the KV cache (contiguous rows or paged pool) this
        engine keeps resident on device.  With the step's cache operand
        donated, per-tick residency is 1x this; un-donated it would be
        2x (input + output live across the call) — the graph-lint
        donation rule's finding, and the bench rows' accounting."""
        return int(sum(leaf.nbytes
                       for leaf in jax.tree_util.tree_leaves(self._cache)))

    @property
    def host_cache_bytes(self) -> int:
        """Pinned host-RAM entitlement of the KV tier (0 without one).
        Kept OUT of ``cache_hbm_bytes`` and the HBM-liveness
        cross-check: swapped-out and demoted blocks are host-resident
        by design — that is the capacity multiplier."""
        if not self.paged:
            return 0
        return int(self.kv.host_cache_bytes())

    # -- telemetry (registry read-throughs + snapshot) ---------------------

    @property
    def step_traces(self) -> int:
        """Compilations of the step function (jit.traces read-through;
        the continuous-batching contract is exactly 1)."""
        return int(self._m_step_traces.value())

    @property
    def rows_step_traces(self) -> int:
        """Compilations of a cursor engine's rows-alone step program (its
        own ``jit.traces`` site, budget 1: 0 until the first chunk-free
        tick, then 1)."""
        return int(self._m_rows_traces.value())

    @property
    def rows_only_ticks(self) -> int:
        """Ticks on which a cursor engine ran its rows-alone step program:
        no prompt chunk, so the pass held the rows part and a stub."""
        return int(self._m_rows_only.value())

    @property
    def prefill_traces(self) -> int:
        """Compilations of the prefill function (one per padded bucket
        length actually seen)."""
        return int(self._m_prefill_traces.value())

    @property
    def last_occupancy(self) -> int:
        """Busy slots at the last scheduler tick (gauge read-through)."""
        return int(self._m_active.value())

    @property
    def prefill_tokens_computed(self) -> int:
        """Prompt tokens actually prefilled (pads excluded; paged prefix
        hits skip these — computed < total proves the cache worked)."""
        return int(self._m_prefill_computed.value())

    @property
    def prefill_tokens_total(self) -> int:
        return int(self._m_prefill_total.value())

    def _note_model_counters(self, load) -> None:
        """Per tick, what only some models have: the routed-expert load the
        step program returned beside the tokens (``load``: nothing, or one
        int array (weight passes, expert layers, held + 1) — per pass of
        the weights, one a program, and expert layer the (token, expert)
        pairs routed to each held expert, then the pairs routed to experts
        held elsewhere), and the live positions of window layers that lie
        behind their window."""
        if self._windows:
            pos = self._positions[self._active].astype(np.int64)
            dead = sum(int(np.maximum(pos + 1 - w, 0).sum())
                       for w in self._windows)
            self._window_dead = dead
            self._m_window_dead.set(float(dead))
        if not load:
            return
        load = load[0].astype(np.int64)
        held = load[..., :-1]
        by_layer = held.sum(axis=0)                 # (layers, held)
        if self._expert_pairs is None:
            # a series a held expert, summed over the expert layers: the
            # registry's cap on a family's children holds them all (a
            # child a (layer, expert) did not fit it, and cost a locked
            # call each a tick); ``expert_load`` has the pairs by layer
            fam = _obs.default_registry().counter(
                "moe.expert_load",
                "(token, expert) pairs routed to a held expert, by held "
                "expert, over every expert layer of every step program "
                "run")
            self._m_expert_load = [
                fam.labels(engine=self._eid, expert=str(e))
                for e in range(by_layer.shape[1])]
            self._expert_pairs = np.zeros(by_layer.shape, np.int64)
        self._expert_pairs += by_layer
        for child, n in zip(self._m_expert_load,
                            by_layer.sum(axis=0).tolist()):
            child.inc(n)
        elsewhere = int(load[..., -1].sum())
        touched = (held > 0).sum(axis=-1)           # (passes, layers)
        self._expert_totals += (elsewhere, int(touched.sum()), touched.size)
        self._m_pairs_elsewhere.inc(elsewhere)
        self._m_experts_touched.observe_many(touched.reshape(-1).tolist())

    @property
    def expert_load(self) -> Optional[Dict[str, object]]:
        """The routed-expert counters' totals since construction —
        ``pairs`` (int (expert layers, held): pairs routed to each held
        expert), ``pairs_elsewhere``, ``experts_touched`` (held experts
        with at least one pair, summed over expert-layer calls: the expert
        weights the grouped product had to read) and ``layer_calls`` — or
        None while no step of a model with routed experts has run."""
        if self._expert_pairs is None:
            return None
        elsewhere, touched, calls = (int(x) for x in self._expert_totals)
        return {"pairs": self._expert_pairs.copy(),
                "pairs_elsewhere": elsewhere, "experts_touched": touched,
                "layer_calls": calls}

    @property
    def state_rows(self) -> Optional[Tuple[int, int]]:
        """Of the fixed-size per-slot state: (rows held by a resident
        request at the last tick — decoding, or mid-prompt under the
        cursor — and rows allocated: one a slot and the null row); None
        for a model that declares no such state."""
        if not self._slot_leaves:
            return None
        return self._state_live, self.num_slots + 1

    @property
    def window_dead_positions(self) -> int:
        """Live (position, window layer) pairs that lay behind their
        layer's window at the last tick: what a window-aware allocator
        would have freed.  0 for a model without window layers."""
        return self._window_dead

    def metrics(self) -> Dict[str, object]:
        """This engine's serving-SLO metrics read from the shared
        registry: TTFT/TPOT/queue-wait/step-latency percentiles, slot
        occupancy, request/token counters, trace counts, and (paged) the
        pool's cache-accounting block.  ``bench.py --sections serving``
        embeds exactly this dict; ``observability.snapshot()`` is the
        full-process superset."""
        def hist(h):
            d = {"count": h.count}
            for q, k in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
                p = h.percentile(q)
                if p is not None:
                    d[k] = round(p, 3)
            return d

        out = {"ttft_ms": hist(self._m_ttft),
               "tpot_ms": hist(self._m_tpot),
               "queue_wait_ms": hist(self._m_queue_wait),
               "decode_step_ms": hist(self._m_step_ms),
               "slot_occupancy": round(self._m_occ.value(), 3),
               "requests_submitted": int(self._m_submitted.value()),
               "requests_finished": int(self._m_finished.value()),
               "tokens_generated": int(self._m_tokens.value()),
               "prefill_waves": int(self._m_waves.value()),
               "step_traces": self.step_traces,
               "rows_step_traces": self.rows_step_traces,
               "serving_rows_only_ticks": self.rows_only_ticks,
               "prefill_traces": self.prefill_traces,
               "slo_violations": {
                   str(c.labels["kind"]): int(c.value())
                   for c in self._f_slo_viol.children()
                   if c.labels.get("engine") == self._eid}}
        if self.chunked:
            out["chunked"] = {
                "prefill_chunk": self.prefill_chunk,
                "chunk_policy": self._chunk_policy,
                "prefill_chunks": int(self._m_chunks.value()),
                "prefill_chunk_tokens": int(self._m_chunk_tokens.value()),
                "chunk_queue_depth": hist(self._m_chunk_queue)}
        if self.spec:
            drafted = int(self._m_drafted.value())
            hits = int(self._m_draft_hits.value())
            acc = hist(self._m_spec_accept)
            if acc["count"]:
                acc["mean"] = round(
                    self._m_spec_accept.sum / acc["count"], 3)
            out["spec"] = {
                "spec_k": self.spec_k,
                "default_drafter": getattr(self._drafter, "kind",
                                           "custom"),
                "drafted_tokens": drafted,
                "draft_hit_tokens": hits,
                "draft_miss_tokens": int(self._m_draft_miss.value()),
                "draft_hit_rate": (round(hits / drafted, 3) if drafted
                                   else 0.0),
                "rollbacks": int(self._m_rollbacks.value()),
                "accepted_per_step": acc}
            by_drafter = {}
            for kind, (md, mh, mm, mr, ma) in sorted(
                    self._spec_children.items()):
                kd, kh = int(md.value()), int(mh.value())
                kacc = hist(ma)
                if kacc["count"]:
                    kacc["mean"] = round(ma.sum / kacc["count"], 3)
                by_drafter[kind] = {
                    "drafted_tokens": kd,
                    "draft_hit_tokens": kh,
                    "draft_miss_tokens": int(mm.value()),
                    # per-kind denominator: THAT drafter's proposals
                    # only (BASELINE.md "Rejection-sampling accounting
                    # conventions")
                    "draft_hit_rate": (round(kh / kd, 3) if kd
                                       else 0.0),
                    "rollbacks": int(mr.value()),
                    "accepted_per_step": kacc}
            if by_drafter:
                out["spec"]["by_drafter"] = by_drafter
        if self.paged:
            st = self.kv.stats
            total = self.prefill_tokens_total
            out["kv_cache"] = {
                "kv_dtype": self.kv_dtype,
                "quantized_blocks": self.kv.quantized_blocks(),
                "bytes_by_dtype": {
                    d: int(g.value())
                    for d, g in self.kv._g_bytes.items()},
                "blocks_in_use": self.kv.blocks_in_use(),
                "peak_blocks_in_use": st["peak_blocks_in_use"],
                "peak_pool_occupancy": round(
                    st["peak_blocks_in_use"] / self.kv.usable_blocks, 3),
                "prefix_hit_tokens": st["prefix_hit_tokens"],
                "prefix_hit_rate": round(st["prefix_hit_tokens"] / total,
                                         3) if total else 0.0,
                "evictions": st["evictions"],
                "cow_copies": st["cow_copies"],
                "admission_blocked": int(self._m_blocked.value())}
            if self._host_blocks > 0:
                out["kv_cache"]["host_tier"] = {
                    "host_blocks": self._host_blocks,
                    "host_blocks_used": self.kv.host_blocks_used(),
                    "host_trie_blocks": self.kv.host_trie_blocks(),
                    "host_demotions": st["host_demotions"],
                    "host_promotions": st["host_promotions"],
                    "swapped_out_blocks": st["swapped_out_blocks"],
                    "swapped_in_blocks": st["swapped_in_blocks"],
                    "swap_out_bytes": int(self._m_swap_out_bytes.value()),
                    "swap_in_bytes": int(self._m_swap_in_bytes.value())}
        if self.paged and self.preempt != "off":
            def by_mode(fam):
                return {str(c.labels["mode"]): int(c.value())
                        for c in fam.children()
                        if c.labels.get("engine") == self._eid}
            out["preempt"] = {
                "mode": self.preempt,
                "preemptions": by_mode(self._f_preempt),
                "resumes": by_mode(self._f_resumed),
                "awaiting_resume": self.num_preempted,
                "decisions": len(self._preempt_log),
                "signature": self.preempt_signature()}
        out["cancelled"] = int(self._m_cancelled.value())
        return out

    def _set_occupancy(self, n: int):
        self._m_active.set(n)
        self._m_occ.set(n / self.num_slots if self.num_slots else 0.0)

    def _retire(self, slot: _Slot, i: int, reason: str, now: float):
        """Per-request SLO readout at retirement, then release the slot.
        TPOT = decode time per token after the first (prefill excluded),
        the complement of TTFT in the usual serving-latency split."""
        n = len(self._results[slot.rid])
        tpot = None
        if n > 1 and slot.t_first > 0.0:
            tpot = (now - slot.t_first) * 1e3 / (n - 1)
            self._m_tpot.observe(tpot)
            if self._perf is not None:
                self._perf.on_tpot(tpot)
        self._m_finished.inc()
        self._f_retired.labels(engine=self._eid, reason=reason).inc()
        req = slot.req
        if req is not None:
            ttft = ((slot.t_first - req.t_submit) * 1e3
                    if slot.t_first > 0.0 else None)
            kind = self._slo_violation(req, ttft, tpot)
            if kind is not None:
                self._f_slo_viol.labels(engine=self._eid, kind=kind).inc()
            self._rlog.event(
                req.uid, "retired", engine=self._eid, reason=reason,
                tokens=int(n),
                ttft_ms=(round(ttft, 6) if ttft is not None else None),
                tpot_ms=(round(tpot, 6) if tpot is not None else None),
                violation=kind or "none")
        self._release(i)

    @staticmethod
    def _slo_violation(req: Request, ttft: Optional[float],
                       tpot: Optional[float]) -> Optional[str]:
        """Attribute a retired request's SLO miss to ONE cause
        (BASELINE.md "SLO accounting conventions"): a missed TTFT
        (measured from SUBMIT, not admit) splits by the larger segment
        — ``queue_wait`` (submit → admission) vs ``prefill`` (admission
        → first token); otherwise a missed TPOT is ``decode``.  A
        disabled deadline (target 0) never violates."""
        if req.ttft_slo_ms > 0 and ttft is not None \
                and ttft > req.ttft_slo_ms:
            qw = ((req.t_admit - req.t_submit) * 1e3
                  if req.t_admit > 0.0 else 0.0)
            return "queue_wait" if qw >= ttft - qw else "prefill"
        if req.tpot_slo_ms > 0 and tpot is not None \
                and tpot > req.tpot_slo_ms:
            return "decode"
        return None

    # -- scheduler internals ----------------------------------------------

    def _wave_bucket(self, plen: int) -> int:
        """Padded prefill length: next power of two (floor 8, ceiling
        max_length) — bounds the number of compiled prefill programs at
        log2(max_length)."""
        b = 8
        while b < plen:
            b *= 2
        return min(b, self.max_length)

    def _wave_buckets(self) -> List[int]:
        """Every bucket ``_wave_bucket`` can give: 8, 16, ... and
        ``max_length``."""
        return sorted({self._wave_bucket(1 << e)
                       for e in range(self.max_length.bit_length() + 1)})

    def _wave_rows(self, bucket: int) -> int:
        """The rows of the prefill program at ``bucket``: ``prefill_batch``
        where rows beside a row ride for nothing, one where a row fills
        the chip by itself (``_ROW_FILLS_CHIP``)."""
        return 1 if bucket >= self._lone_from else self.prefill_batch

    def _admit(self) -> List[int]:
        """Wave admission: move queued requests into free slots, one
        batched-prefill wave at a time — as many requests as there are
        free slots, ``prefill_batch`` at most; which of them share a
        program call is ``_wave_calls``' to say (a long prompt goes alone,
        and not beside dummy rows).  Returns ids that finished AT
        admission (first token was EOS / max_new_tokens=1).  The head that
        cannot be admitted blocks the queue: head-of-line order is the
        contract in both layouts.

        Contiguous: strict submit FIFO, a wave being a run of prompts that
        share one padded bucket.  Paged: a request enters once the block
        pool covers its worst case (``_pool_admit``), adopting any cached
        prompt prefix on the way in, and a wave shares one padded SUFFIX
        bucket (prefix-hit rows only compute what the cache missed).  With
        preemption on, admission drains BOTH the recompute-resume queue and
        the submit queue by priority class (stable FIFO within a class —
        ``_next_admit``, resume entries winning ties) and a pool-full head
        may instead evict a running victim and retry; swapped chains are
        restored first of all."""
        if self.paged:
            self._service_swap_resumes()
        finished: List[int] = []
        deferred = False
        while (self._resume_q or self._queue) and not deferred:
            free = self._free_slots()
            if not free:
                break
            occ = self.num_slots - len(free)
            live = int(self._positions[self._active].sum()) if occ else 0
            wave: List[Tuple[Request, int, int]] = []
            wave_tokens = 0
            while ((self._resume_q or self._queue)
                   and len(wave) < min(self.prefill_batch, len(free))):
                src, req = (self._next_admit() if self.paged
                            else (self._queue, self._queue[0]))
                if (wave and not self.paged
                        and self._wave_bucket(req.prompt.size)
                        != self._wave_bucket(wave[0][0].prompt.size)):
                    break
                if self._admission_defer(
                        req, occ + len(wave) + 1,
                        live + wave_tokens + int(req.prompt.size)):
                    self._defer(req)
                    deferred = True
                    break
                si = free[len(wave)]
                m = self._pool_admit(si, req) if self.paged else 0
                if m is None:
                    break
                # remove by IDENTITY: a preemption inside the retry loop
                # may have pushed a new resume entry ahead of req
                self._dequeue(src, req)
                wave.append((req, si, m))
                wave_tokens += int(req.prompt.size)
            if not wave:
                break
            finished.extend(self._prefill_wave(wave))
        return finished

    def _pool_admit(self, si: int, req: Request) -> Optional[int]:
        """Reserve the block pool for ``req`` in slot ``si``: the prompt
        tokens its adopted prefix covers, or None while the pool cannot
        cover the request's worst case — after preempting every victim
        ``_try_preempt`` allows and retrying, and with the blocked head
        counted every tick and logged once an episode."""
        while True:
            got = self.kv.admit(si, req.prompt, req.prompt.size,
                                self._reserved_new(req.prompt.size,
                                                   req.max_new_tokens),
                                chunked=self.chunked)
            if got is not None or not self._try_preempt(
                    priority=req.priority, rid=req.request_id,
                    blocked_ticks=req.blocked_ticks):
                break
        if got is None:              # pool full: wait for retirements
            self._m_blocked.inc()
            self._tracer.instant("serving.admission_blocked",
                                 rid=req.request_id)
            req.blocked_ticks += 1
            if req.blocked_ticks == 1:
                # the preemption-relevant wait: log once per wait
                # episode, not per blocked tick
                self._rlog.event(req.uid, "admission_wait",
                                 engine=self._eid, reason="pool_full")
        return got

    def _wave_calls(self, wave) -> List[Tuple[List, int]]:
        """The program calls that prefill ``wave``, as (rows, padded
        bucket) in the wave's order.  One call at the longest row's bucket
        where the rows share one stream of the weights.  A row whose own
        bucket fills the chip (``_wave_rows``) gains nothing from company
        and would make every shorter or dummy row beside it cost as much as
        itself: it goes alone, at its own bucket, and the runs of short
        rows between such rows share a call each — in the wave's order,
        since a row may have adopted blocks that an earlier row of its wave
        is still to write."""
        buckets = [self._wave_bucket(req.prompt.size - m)
                   for req, _, m in wave]
        calls = []
        for alone, run in itertools.groupby(
                zip(wave, buckets),
                key=lambda rb: self._wave_rows(rb[1]) == 1):
            run = list(run)
            calls += ([([row], b) for row, b in run] if alone else
                      [([row for row, _ in run], max(b for _, b in run))])
        return calls

    def _prefill_wave(self, wave: List[Tuple[Request, int, int]]
                      ) -> List[int]:
        """Prefill one admission wave of rows ``(request, slot, adopted
        prefix tokens)`` — 0 adopted on the contiguous cache — in the
        program calls ``_wave_calls`` gives, each at its padded bucket, and
        install each row into its slot.  Returns the ids that finished at
        admission."""
        t_adm = self._clock()
        calls = self._wave_calls(wave)
        for rows, bucket in calls:
            for req, si, m in rows:
                self._m_prefill_computed.inc(int(req.prompt.size) - m)
                self._m_prefill_total.inc(int(req.prompt.size))
                if req.resume is None:
                    self._m_queue_wait.observe((t_adm - req.t_submit) * 1e3)
                    req.t_admit = t_adm
                    self._rlog.event(
                        req.uid, "admitted", engine=self._eid, slot=int(si),
                        queue_wait_ms=(t_adm - req.t_submit) * 1e3,
                        blocked_ticks=int(req.blocked_ticks),
                        prefix_hit_tokens=int(m))
                self._rlog.event(req.uid, "prefill", engine=self._eid,
                                 bucket=int(bucket),
                                 tokens=int(req.prompt.size) - m)
        span = self._tracer.span
        if self.paged:
            with span(_GROW):
                self._flush_fresh_scales()
        tok: List[int] = []
        for rows, bucket in calls:
            self._m_waves.inc()
            self._f_bucket.labels(engine=self._eid, bucket=str(bucket)).inc()
            self._ticks += 1
            # (a padded call returns its dummy rows' tokens too)
            tok.extend(self._device_prefill(rows, bucket)[:len(rows)])
        with span(_ADVANCE):
            # queued demotions first (a wave's registration precedes its
            # prefill), then each row's slot state and first token
            self._apply_demotions()
            t_tok = self._clock()
            return [req.request_id for (req, si, _), t in zip(wave, tok)
                    if self._install(req, si, t, t_tok)]

    def _device_prefill(self, wave, bucket: int) -> Sequence[int]:
        """The wave's device seam (``serving.prefill``): pad the wave to
        the rows of ``bucket``'s program (``_wave_rows``) of ``bucket``
        tokens, upload the prefill program's operands, call it, fetch each
        row's first token.  ``fleet_sim.SimEngine`` overrides this and
        nothing else of the wave."""
        nb = self._wave_rows(bucket)
        paged, table = self.paged, self._wave_tables[nb]
        ids = np.full((nb, bucket), self.pad_token_id, np.int32)
        lens = np.ones((nb,), np.int32)
        temps = np.zeros((nb,), np.float32)
        topk = np.zeros((nb,), np.int32)
        topp = np.ones((nb,), np.float32)
        own = dict(ids=ids, lens=lens, temps=temps, topk=topk, topp=topp,
                   key=self._ticks)
        if paged:
            # dummy rows keep all-null tables: their writes land in the
            # scratch block and their sampled token is discarded
            prefix = own["prefix_lens"] = np.zeros((nb,), np.int32)
            where = own["tables"] = np.zeros((nb, self.max_blocks), np.int32)
        else:
            # dummy rows scatter to the out-of-bounds slot id and are dropped
            where = own["slot_ids"] = np.full((nb,), self.num_slots,
                                              np.int32)
        for r, (req, si, m) in enumerate(wave):
            suffix = req.prompt[m:]
            ids[r, :suffix.size] = suffix
            lens[r] = suffix.size
            if paged:
                prefix[r] = m
                where[r] = self.kv.table_row(si, self.max_blocks)
            else:
                where[r] = si
            temps[r] = req.sampling.temperature
            topk[r] = req.sampling.top_k
            topp[r] = req.sampling.top_p
        span = self._tracer.span
        with span(_ACCOUNT):
            facts = dict(
                sample_path=self._note_sample_path((temps, topk, topp)),
                # a contiguous wave reads no cache: the flash kernel
                **(self._kv_walk((prefix, bucket)) if paged else {}))
        with span("serving.prefill", bucket=bucket, rows=len(wave),
                  padded_rows=nb, tokens=int(lens[:len(wave)].sum()),
                  **facts):
            with span(_BUILD):
                args = self._upload(table, own, bucket)
            with span(_DISPATCH, leaves=self._program_leaves):
                tok, self._cache = self._prefill_fn(
                    self._params, self._cache, *args)
            with span(_READBACK):
                return np.asarray(tok)

    def _install(self, req: Request, si: int, tok, now: float) -> bool:
        """Install a request whose prompt is in the cache into slot ``si``
        (a wave's row, or the cursor's prompt at its last chunk): the
        slot's state and mirrors, then the first token ``tok`` with its
        TTFT.  True when the request finished right here (first token EOS
        / max_new_tokens=1) and was retired."""
        ri = req.resume
        if ri is not None:
            # recompute resume: the re-sampled token re-derives the last
            # committed one (greedy: identical); it is DISCARDED and the
            # committed token forced back with the original decode budget
            # and TTFT clock, so the resumed decode replays no token and
            # drops none
            first = ri.last_token
            slot = _Slot(req.request_id, ri.remaining, t_first=ri.t_first,
                         prompt=ri.orig.prompt, req=ri.orig)
        elif self._block:
            # block diffusion: the prompt's whole blocks are committed, the
            # rest open the first block; no token yet — the first comes
            # with the first block's delivery (``_advance_block``)
            slot = _Slot(req.request_id, req.max_new_tokens,
                         prompt=req.prompt, req=req)
            at = self._prompt_commit(req)
            self._seat(si, slot, self.pad_token_id, at, req.sampling)
            self._open_block(si, req.prompt[at:])
            self._unmasked_at[req.request_id] = []
            return False
        else:
            first = int(tok)
            slot = _Slot(req.request_id, req.max_new_tokens - 1,
                         t_first=now, prompt=req.prompt, req=req)
        self._seat(si, slot, first, req.prompt.size, req.sampling)
        if ri is not None:
            self._note_resumed(req, "recompute", si)
            return False
        self._results[req.request_id].append(first)
        self._m_tokens.inc()
        self._note_first_token(req, now)
        reason = self._finish_reason(first, slot, si)
        if reason is not None:
            self._retire(slot, si, reason, now)
        return reason is not None

    def _note_first_token(self, req: Request, now: float):
        """TTFT, where the request's first token reaches it: its last
        chunk's sampled token, or a block-diffusion model's first
        delivery."""
        ttft = (now - req.t_submit) * 1e3
        self._m_ttft.observe(ttft)
        if self._perf is not None:
            self._perf.on_ttft(ttft)
        self._rlog.event(req.uid, "first_token", engine=self._eid,
                         ttft_ms=ttft)

    def _seat(self, si: int, slot: _Slot, token: int, position: int,
              sampling: SamplingParams):
        """Put ``slot`` into slot ``si``: the host mirrors the step
        uploads and, paged, its row of the block table."""
        self._drafter_reset(si)
        self._slots[si] = slot
        self._active[si] = True
        self._tokens[si] = token
        self._positions[si] = position
        self._temps[si] = sampling.temperature
        self._topk[si] = sampling.top_k
        self._topp[si] = sampling.top_p
        if self._block:
            d = self._diffusion
            self._unmask_n[si] = d.static_count(sampling.unmask_strategy)
            self._unmask_thr[si] = (d.threshold
                                    if sampling.unmask_threshold is None
                                    else sampling.unmask_threshold)
        if self.paged:
            self._tables[si] = self.kv.table_row(si, self.max_blocks)
        self._share_stale = True

    def _note_resumed(self, req: Request, mode: str, si: int):
        self._rlog.event(req.uid, "resumed", engine=self._eid, mode=mode,
                         slot=int(si))
        self._f_resumed.labels(engine=self._eid, mode=mode).inc()
        self._tracer.instant("serving.resumed", rid=req.request_id,
                             mode=mode, slot=int(si))

    def _finish_reason(self, tok: int, slot: _Slot,
                       i: int) -> Optional[str]:
        """None while the request keeps going, else the retirement
        reason (the ``serving.retired`` counter's label)."""
        if self.eos_token_id is not None and tok == self.eos_token_id:
            return "eos"
        if slot.remaining <= 0:
            return "max_new_tokens"
        if int(self._positions[i]) >= self.max_length:
            return "max_length"
        return None

    def _release(self, i: int):
        if self.paged:
            self.kv.release(i)
        self._clear_slot(i)

    def _clear_slot(self, i: int):
        """Reset slot ``i``'s host mirrors WITHOUT touching the block
        pool — preemption already moved/freed the chain through
        ``swap_out``/``preempt_free``; ``_release`` adds the
        ``kv.release`` for normal retirement."""
        if self.paged:
            self._tables[i] = 0
        self._share_stale = True
        self._drafter_reset(i)
        self._slots[i] = None
        self._active[i] = False
        self._tokens[i] = self.pad_token_id
        self._positions[i] = 0
        self._temps[i] = 0.0
        self._topk[i] = 0
        self._topp[i] = 1.0
        if self._block:
            self._blocks[i] = self._diffusion.mask_token_id
            self._unmask_n[i] = 0
            self._unmask_thr[i] = 1.0
