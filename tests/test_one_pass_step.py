"""The mixed step program's ONE pass of the weights (``decode_parts`` over the
decode rows and the prompt chunk together) against the same tick composed of
two ``decode_step`` calls, a part each, as the step program was before: on the
operands of real ticks of every chunked layout — a chunk beside live rows, a
chunk-free tick, a tick with no live row — the rows' logits and the logits at
the sampled chunk position agree, the cache agrees wherever a real token
wrote, and is untouched everywhere else."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.models.afmoe import AfmoeForCausalLM, tiny_afmoe_config
from paddle_tpu.models.lfm2 import Lfm2MoeForCausalLM, tiny_lfm2_config
from paddle_tpu.nn.layer import bind_params
from paddle_tpu.serving import ServingEngine

CASES = {
    "llama-paged": (LlamaForCausalLM, tiny_llama_config, dict(paged=True)),
    "llama-paged-spec": (LlamaForCausalLM, tiny_llama_config,
                         dict(paged=True, spec_decode=True, spec_k=2)),
    "llama-contiguous": (LlamaForCausalLM, tiny_llama_config,
                         dict(paged=False)),
    "llama-contiguous-spec": (LlamaForCausalLM, tiny_llama_config,
                              dict(paged=False, spec_decode=True, spec_k=2)),
    "afmoe-paged": (AfmoeForCausalLM, tiny_afmoe_config, dict(paged=True)),
    "lfm2-paged": (Lfm2MoeForCausalLM, tiny_lfm2_config,
                   dict(paged=True, prefix_cache=False)),
}


def _recorded_ticks(eng):
    """Serve a few staggered requests and keep every tick's operands: the
    cache as the program received it and the operands by name."""
    ticks, upload = [], eng._upload

    def spy(table, own):
        packed, *alone = upload(table, own)
        # the operands as the program takes them out of the packed buffer
        # (new arrays), and a copy of the cache: the program donates it
        ticks.append((jax.tree_util.tree_map(jnp.copy, eng._cache),
                      eng._unpack(table, packed, alone)))
        return [packed, *alone]
    eng._upload = spy
    rng = np.random.default_rng(3)
    motif = rng.integers(1, 255, 4)
    for n in (19, 5):
        eng.submit(np.tile(motif, 8)[:n], max_new_tokens=5)
    for _ in range(6):
        eng.step()
    eng.submit(np.tile(motif, 8)[:11], max_new_tokens=3)
    eng.drain()
    del eng._upload
    return ticks


def _slot_axes(eng, cache):
    """Per leaf of ``cache``, the axis a slot indexes, or -1 (the paged
    pool): the contiguous cache's axis 2, a per-slot state leaf's axis 1."""
    if not eng.paged:
        return jax.tree_util.tree_map(lambda _: 2, cache)
    if eng._slot_leaves:
        return {k: 1 if k in eng._slot_leaves else -1 for k in cache}
    return jax.tree_util.tree_map(lambda _: -1, cache)


def _two_calls(eng, params, cache, a):
    """The tick as two ``decode_step`` calls, the rows' then the chunk's,
    each on its own view of the cache."""
    axes = _slot_axes(eng, cache)
    logits = []
    with bind_params(eng._bind, eng._prepare(params)):
        for p in eng._step_parts(a):
            def cut(leaf, axis):
                return leaf if axis < 0 else \
                    jax.lax.dynamic_slice_in_dim(leaf, *p.slots, axis=axis)

            def put(leaf, rows, axis):
                return rows if axis < 0 else \
                    jax.lax.dynamic_update_slice_in_dim(
                        leaf, rows, p.slots[0], axis=axis)
            sliced = p.slots is not None
            view = jax.tree_util.tree_map(cut, cache, axes) if sliced \
                else cache
            kw = {} if p.block_tables is None else {
                "block_tables": p.block_tables}
            if p.valid is not None:
                kw["valid"] = p.valid
            out, view = eng.model.decode_step(p.input_ids, view, p.pos, **kw)
            cache = jax.tree_util.tree_map(put, cache, view, axes) \
                if sliced else view
            logits.append(out if p.last is None else
                          out[:, jnp.maximum(p.last, 0)][:, None])
    return logits, cache


def _one_pass(eng, params, cache, a):
    with bind_params(eng._bind, eng._prepare(params)):
        return eng.model.decode_parts(eng._step_parts(a), cache)


def _written(eng, a):
    """What of the cache this tick's REAL tokens may write: the pool's
    blocks (paged) and the slot rows (contiguous cache, per-slot state)."""
    live = np.asarray(a["slot_mask"])
    clen = int(a["clen"])
    blocks = set()
    if eng.paged:
        blocks = set(np.asarray(a["tables"])[live].ravel())
        if clen:
            blocks |= set(np.asarray(a["cdst"]).ravel())
    slots = set(np.nonzero(live)[0])
    if clen:
        slots.add(int(a["cslot"] if "cslot" in a else
                      a["cdst"] if not eng.paged else -1))
    return sorted(blocks - {0}), sorted(slots - {-1})


@pytest.mark.parametrize("case", list(CASES))
def test_one_pass_is_the_two_calls(case):
    cls, config, layout = CASES[case]
    pt.seed(11)
    model = cls(config())
    model.eval()
    eng = ServingEngine(model, num_slots=3, max_length=64, block_len=8,
                        chunked=True, prefill_chunk=8, seed=0, **layout)
    ticks = _recorded_ticks(eng)
    kinds = {(bool(np.asarray(a["slot_mask"]).any()), int(a["clen"]) > 0)
             for _, a in ticks}
    # live rows beside a chunk, a chunk-free tick, a tick with no live row
    assert {(True, True), (True, False), (False, True)} <= kinds, kinds
    one, two = jax.jit(_one_pass, static_argnums=0), \
        jax.jit(_two_calls, static_argnums=0)
    seen = set()
    for cache, a in ticks:
        kind = (bool(np.asarray(a["slot_mask"]).any()), int(a["clen"]) > 0)
        if kind in seen and len(seen) == 3:
            continue
        seen.add(kind)
        (rows1, chunk1), cache1 = one(eng, eng._params, cache, a)
        (rows2, chunk2), cache2 = two(eng, eng._params, cache, a)
        live = np.asarray(a["slot_mask"])
        # logits: the live rows' (an idle row's are junk on both sides) and
        # the one chunk position the program samples from
        assert rows1.shape == rows2.shape and chunk1.shape == (1, 1) + \
            rows1.shape[2:]
        np.testing.assert_allclose(np.asarray(rows1)[live],
                                   np.asarray(rows2)[live],
                                   rtol=2e-4, atol=2e-4)
        if kind[1]:
            np.testing.assert_allclose(chunk1, chunk2, rtol=2e-4, atol=2e-4)
        blocks, slots = _written(eng, a)
        flat = jax.tree_util.tree_leaves
        for before, got, want, axis in zip(
                flat(cache), flat(cache1), flat(cache2),
                flat(_slot_axes(eng, cache))):
            before, got, want = (np.asarray(x) for x in (before, got, want))
            # the pool by block (axis 2), a slot-indexed leaf by slot
            pool = axis < 0
            axis, wrote = (2, blocks) if pool else (axis, slots)
            rest = [i for i in range(before.shape[axis])
                    if i not in wrote and not (pool and i == 0)]
            np.testing.assert_allclose(
                np.take(got, wrote, axis), np.take(want, wrote, axis),
                rtol=2e-4, atol=2e-4)
            # untouched where no real token wrote (the null block takes
            # padding's junk on both sides and is read by nobody)
            np.testing.assert_array_equal(np.take(got, rest, axis),
                                          np.take(before, rest, axis))
    assert len(seen) == 3
