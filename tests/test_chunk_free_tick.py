"""A tick with no prompt chunk runs the rows-alone program: a cursor engine
has two step programs, the mixed one (the rows part and the chunk part in one
pass) and the same body with the chunk part cut to a stub of its first 8
positions, and the host runs the one the tick calls for (``clen > 0``).  On
the recorded operands of a real chunk-free tick of every chunked layout the
rows-alone program is held against the form such a tick had before — both
parts whole, with an empty chunk whose writes are steered to the null block,
past ``max_length`` or to the null row: what a live row computes and writes
is equal, its tokens and expert load to the last bit, its logits and cache
to the CPU's last float32 roundings.  Each program is traced once, the tick's span says
which one ran, and the programs that were there — an engine's with no chunk
part, the mixed one — lower to the text they had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.harness.compile_log import CompileLog
from paddle_tpu import observability as obs
from paddle_tpu.distributed import moe
from paddle_tpu.models import (LatentMoeForCausalLM, LlamaForCausalLM,
                               SdarMoeForCausalLM, tiny_latent_moe_config,
                               tiny_llama_config, tiny_sdar_config)
from paddle_tpu.models.afmoe import AfmoeForCausalLM, tiny_afmoe_config
from paddle_tpu.models.lfm2 import Lfm2MoeForCausalLM, tiny_lfm2_config
from paddle_tpu.nn.layer import bind_params
from paddle_tpu.ops import _dispatch
from paddle_tpu.serving import ServingEngine

from lowered_step_text import kernel_calls, lowered, sha
from test_one_pass_step import _slot_axes

SLOTS, CHUNK = 3, 16        # a chunk longer than the stub kept of it
CASES = {
    "llama-paged": (LlamaForCausalLM, tiny_llama_config, dict(paged=True)),
    "llama-contiguous": (LlamaForCausalLM, tiny_llama_config,
                         dict(paged=False)),
    "llama-paged-spec": (LlamaForCausalLM, tiny_llama_config,
                         dict(paged=True, spec_decode=True, spec_k=2)),
    "afmoe": (AfmoeForCausalLM, tiny_afmoe_config, dict(paged=True)),
    "lfm2": (Lfm2MoeForCausalLM, tiny_lfm2_config,
             dict(paged=True, prefix_cache=False)),
    "sdar": (SdarMoeForCausalLM, tiny_sdar_config,
             dict(paged=True, prefix_cache=False)),
    "latent_moe": (LatentMoeForCausalLM, tiny_latent_moe_config,
                   dict(paged=True, prefix_cache=True, max_length=128,
                        num_blocks=80)),
}


@pytest.fixture(scope="module")
def compiles():
    return CompileLog()


@pytest.fixture(scope="module", params=list(CASES))
def served(request, compiles):
    """An engine of the case's layout after a run that mixes both kinds of
    tick, with every tick's cache and operands as the program received
    them, its rows span's arguments and what compiled after warm-up."""
    cls, config, layout = CASES[request.param]
    pt.seed(11)
    model = cls(config())
    model.eval()
    eng = ServingEngine(model, **{**dict(
        num_slots=SLOTS, max_length=64, block_len=8, chunked=True,
        prefill_chunk=CHUNK, seed=0), **layout})
    ticks, upload = [], eng._upload

    def spy(table, own):
        args = upload(table, own)
        # copies: the program donates the cache, and on the CPU an upload
        # aliases the host's buffer
        args = [jnp.array(np.array(x, copy=True)) for x in args]
        ticks.append((jax.tree_util.tree_map(jnp.copy, eng._cache), args,
                      eng._unpack(table, args[0], args[1:])))
        return args
    eng._upload = spy
    rng = np.random.default_rng(3)
    prompts = [np.tile(rng.integers(1, 250, 4), 8)[:n] for n in (19, 5, 11)]
    obs.get_tracer().clear()
    for p in prompts[:2]:
        eng.submit(p, max_new_tokens=6)
    for _ in range(6):          # chunk ticks, then ticks of rows alone
        eng.step()
    compiles.drain()
    eng.submit(prompts[2], max_new_tokens=4)
    eng.drain()
    late = compiles.drain()["programs"]
    del eng._upload
    spans = [e["args"] for e in obs.get_tracer().events()
             if e["ph"] == "X"
             and e["name"] in ("serving.decode", "serving.verify")]
    return dict(eng=eng, ticks=ticks, spans=spans, late_compiles=late,
                traces=(eng.step_traces, eng.rows_step_traces),
                rows_only=eng.rows_only_ticks,
                metrics=eng.metrics())


def _passes(eng, params, cache, a):
    """``decode_parts`` with the chunk part cut to its stub and with it
    whole (the parents' form of a chunk-free tick: the same operands, every
    write of the empty chunk dropped or sent where nobody reads): each
    side's (rows' logits, expert load, cache)."""
    out = []
    for stub in (True, False):
        with bind_params(eng._bind, eng._prepare(params)), \
                moe.expert_load() as load:
            (logits, *_), after = eng.model.decode_parts(
                eng._step_parts(a, stub=stub), cache)
        out.append((logits, list(load), after))
    return out


def _live_writes(eng, a):
    """What of the cache a chunk-free tick's REAL tokens write: the pool's
    blocks in the live rows' tables, the live slots' rows."""
    live = np.asarray(a["slot_mask"])
    blocks = (set(np.asarray(a["tables"])[live].ravel()) - {0}
              if eng.paged else set())
    return sorted(blocks), sorted(np.nonzero(live)[0])


def _same_where_written(eng, a, before, got, want, ulps=0):
    """``got`` (the rows-alone pass's cache) against ``want`` (the parents'
    form's): equal to the last bit wherever a real token wrote (within
    ``ulps`` float32 roundings where the two sides are two compilations),
    and ``got`` untouched everywhere else but the null block, which takes
    the idle rows' junk on both sides and is read by nobody."""
    blocks, slots = _live_writes(eng, a)
    flat = jax.tree_util.tree_leaves
    for b, g, w, axis in zip(flat(before), flat(got), flat(want),
                             flat(_slot_axes(eng, before))):
        b, g, w = (np.asarray(x) for x in (b, g, w))
        pool = axis < 0
        axis, wrote = (2, blocks) if pool else (axis, slots)
        rest = [i for i in range(b.shape[axis])
                if i not in wrote and not (pool and i == 0)]
        np.testing.assert_allclose(np.take(g, wrote, axis),
                                   np.take(w, wrote, axis),
                                   rtol=ulps * 2.0 ** -23,
                                   atol=ulps * 2.0 ** -23)
        np.testing.assert_array_equal(np.take(g, rest, axis),
                                      np.take(b, rest, axis))


def _rows_only_tick(served):
    """A recorded chunk-free tick with live rows."""
    for cache, args, a in served["ticks"]:
        if not int(a["clen"]) and np.asarray(a["slot_mask"]).any():
            return cache, args, a
    raise AssertionError("the run had no chunk-free tick with a live row")


def test_rows_alone_is_the_parents_form(served):
    eng = served["eng"]
    cache, args, a = _rows_only_tick(served)
    live = np.asarray(a["slot_mask"])
    # the passes themselves: logits, load and cache
    (rows1, load1, cache1), (rows2, load2, cache2) = jax.jit(
        _passes, static_argnums=0)(eng, eng._params, cache, a)
    assert rows1.shape == rows2.shape
    # (to a few float32 roundings: the CPU's products block their sums by
    # the pass's row count, 8 rows of a stub against CHUNK of a chunk)
    np.testing.assert_allclose(np.asarray(rows1)[live],
                               np.asarray(rows2)[live],
                               rtol=8 * 2.0 ** -23, atol=8 * 2.0 ** -23)
    assert len(load1) == len(load2) == eng._expert_layers
    for x, y in zip(load1, load2):
        np.testing.assert_array_equal(x, y)
    _same_where_written(eng, a, cache, cache1, cache2, ulps=8)
    # the programs: what the engine's own compiled rows-alone program
    # gives on this tick's buffer against the mixed one, which such a tick
    # ran before
    def copy():     # the programs donate the cache they are given
        return jax.tree_util.tree_map(jnp.copy, cache)
    *new, cache_new = eng._rows_fn(eng._params, copy(), *args)
    *old, cache_old = eng._step_fn(eng._params, copy(), *args)
    assert (eng.step_traces, eng.rows_step_traces) == (1, 1)
    names = eng._step_outputs[:-1]
    assert len(new) == len(old) == len(names)
    for name, x, y in zip(names, new, old):
        if name != "chunk_token":       # junk on such a tick, unread
            # tokens, n_acc, n_unmasked, expert_load
            np.testing.assert_array_equal(x, y, err_msg=name)
    # two programs, compiled apart: the CPU compiler contracts a multiply
    # and an add in one and not the other, a rounding or two in the cache
    _same_where_written(eng, a, cache, cache_new, cache_old, ulps=8)


def test_each_program_is_traced_once_for_both_kinds_of_tick(served):
    kinds = {int(a["clen"]) > 0 for _, _, a in served["ticks"]}
    assert kinds == {True, False}
    assert served["traces"] == (1, 1)
    # the first ticks ran both programs; nothing compiled after them
    assert served["late_compiles"] == 0
    assert served["eng"].lint_step() == []


def test_span_and_counter_say_which_program_ran(served):
    eng, spans = served["eng"], served["spans"]
    assert len(spans) == len(served["ticks"])
    rows = SLOTS * eng._row_tokens
    assert eng._pass_rows == rows + CHUNK and eng._stub_chunk == 8 < CHUNK
    rows_only = 0
    for args, (_, _, a) in zip(spans, served["ticks"]):
        chunk = int(a["clen"]) > 0
        rows_only += not chunk
        assert args["weight_passes"] == 1
        assert args["parts"] == 1 + chunk
        assert args["pass_rows"] == rows + (CHUNK if chunk else 8)
        assert args["pass_tokens"] <= args["pass_rows"]
    assert 0 < rows_only < len(spans)
    assert served["rows_only"] == rows_only
    assert served["metrics"]["serving_rows_only_ticks"] == rows_only
    assert served["metrics"]["step_traces"] == 1
    assert served["metrics"]["rows_step_traces"] == 1


def test_walk_counts_the_stubs_call_on_a_chunk_free_tick(served):
    """``kv_blocks`` / ``kv_walk`` of a tick's span count the flash-decode
    calls of the program it ran: the rows' and, on a chunk-free tick, the
    one of the stub of 8 positions (no row of this run shares a prefix, so
    the counts follow from the positions as uploaded)."""
    eng = served["eng"]
    if not eng._kv_walk(([0], 1)):
        pytest.skip("this layout's spans count no walk")
    kinds = set()
    for args, (_, _, a) in zip(served["spans"], served["ticks"]):
        chunk = CHUNK if int(a["clen"]) else 8
        want = eng._kv_walk(
            (np.asarray(a["positions"]), eng._row_tokens),
            ([int(a["cpos"])], chunk))
        assert (args["kv_blocks"], args["kv_walk"]) == (
            want["kv_blocks"], want["kv_walk"])
        kinds.add(chunk)
    assert kinds == {8, CHUNK}


# The step programs that were there before a cursor engine had a second one,
# at tiny llama geometry under the chip's dispatch, as ``lowered_step_text.py``
# hashed them at the parent commit (PR 43): (paged, spec, chunked)
PARENTS_TEXT = {(False, False, False): "998b023fa239542e",
                (False, True, False): "1032a6a3df8afe0a",
                (True, False, False): "20f8a217b7455a30",
                (True, True, False): "90ae45c0308bdf10",
                (False, False, True): "c843c2b19ac97972",
                (False, True, True): "a168a0b1e3bb4a3d",
                (True, False, True): "c4ea97a1bef44a86",
                (True, True, True): "0aaef5a950827141"}


@pytest.mark.parametrize("paged, spec, chunked", list(PARENTS_TEXT))
def test_the_programs_that_were_there_lower_to_the_text_they_had(
        monkeypatch, paged, spec, chunked):
    """An engine with no chunk part has the one program it had, and a tick
    WITH a chunk runs the mixed program it ran: both to the parent's text.
    The rows-alone program is the mixed one's body with the chunk part cut
    to a stub: the same kernels, call for call."""
    monkeypatch.setattr(_dispatch, "default_backend", lambda: "tpu")
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    eng = ServingEngine(
        model, num_slots=3, max_length=64, block_len=8, prefill_chunk=8,
        spec_k=2, seed=0, paged=paged, chunked=chunked, spec_decode=spec)
    low = lowered(eng._step_fn.python_fn, eng._lint_args())
    assert sha(low)[0] == PARENTS_TEXT[paged, spec, chunked]
    if not chunked:
        assert eng._rows_fn is None
        return
    assert eng._rows_fn.python_fn.__name__ == \
        eng._step_fn.python_fn.__name__.replace("mixed_", "rows_")
    rows = kernel_calls(lowered(eng._rows_fn.python_fn, eng._lint_args()))
    both = kernel_calls(low)
    assert rows == both
