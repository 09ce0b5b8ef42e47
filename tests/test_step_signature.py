"""The step program's signature, tested where it was only trusted (ISSUE 29).

The engine writes the operands of its two device programs down once
(``ServingEngine._operand_tables``) and hands them over as ``(params, cache,
packed[, the operands that are not small])`` (ISSUE 38).  The graph lint, the
mesh pre-flight, ``chip_smoke.py`` and ``tests/lowered_step_text.py`` take
``_lint_args()`` to be "the program the scheduler runs"; here a real tick's
and a real wave's arguments are held to it, in every layout.
"""

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.serving import ServingEngine

SLOTS, MAXLEN, K = 3, 64, 2


@pytest.fixture(scope="module")
def lm():
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    return model


def _types(tree):
    return jax.tree_util.tree_map(jax.typeof, tree)


@pytest.mark.parametrize("spec", [False, True])
@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("paged", [False, True])
def test_a_real_tick_runs_the_program_the_table_describes(
        lm, paged, chunked, spec):
    eng = ServingEngine(lm, num_slots=SLOTS, max_length=MAXLEN, block_len=8,
                        prefill_chunk=8, spec_k=K, paged=paged,
                        chunked=chunked, spec_decode=spec)
    step, prefill = eng._step_fn, eng._prefill_fn

    # (a) the jitted bodies keep the names the device trace is read by
    assert step.python_fn.__name__ == (
        "_" + "spec_" * spec + "mixed_" * chunked + "step_impl"
        + "_paged" * paged)
    assert (prefill is None) == chunked
    if prefill is not None:
        assert prefill.python_fn.__name__ == (
            "_prefill_impl" + "_paged" * paged)

    # (b) what a real tick and a real wave hand the device
    seen = {"step": [], "prefill": []}

    def spy(fn, where):
        def call(*args):
            seen[where].append(_types(args))
            return fn(*args)
        return call
    eng._linted = True      # the first tick's self-lint would trace the spy
    eng._step_fn = spy(step, "step")
    if chunked:     # a chunk-free tick's program: the same table and name
        assert eng._rows_fn.python_fn.__name__ == \
            step.python_fn.__name__.replace("mixed_", "rows_")
        eng._rows_fn = spy(eng._rows_fn, "step")
    if prefill is not None:
        eng._prefill_fn = spy(prefill, "prefill")
    rs = np.random.RandomState(3)
    for n in (5, 11):
        eng.submit(rs.randint(0, 256, n).astype(np.int32), max_new_tokens=4)
    out = eng.drain()
    assert [len(toks) for _, toks in out] == [4, 4]
    assert seen["step"] and all(
        got == _types(eng._lint_args()) for got in seen["step"])
    # params, cache, ONE packed buffer of 32-bit words; nothing here is big
    assert len(eng._lint_args()) == 3 and not eng._step_layout.own
    packed = eng._lint_args()[2]
    assert (packed.shape, packed.dtype) == (
        (eng._step_layout.words,), np.int32)
    assert bool(seen["prefill"]) == (prefill is not None)
    buckets = set()
    for got in seen["prefill"]:
        # the buffer's length states the wave's bucket: ids takes its rest
        # (these prompts are short: ``prefill_batch`` rows, a flat buffer)
        bucket, odd = divmod(got[2].shape[0] - eng._wave_layouts[
            eng.prefill_batch].words, eng.prefill_batch)
        assert not odd and len(got) == 3
        assert got == _types(eng._lint_args(bucket))
        buckets.add(bucket)

    # (c) the body returns what the engine declares
    s = SLOTS
    i32 = np.dtype(np.int32)
    want = {"tokens": ((s, K + 1) if spec else (s,), i32),
            "n_acc": ((s,), i32), "chunk_token": ((), i32)}
    res = jax.eval_shape(step.python_fn, *eng._lint_args())
    assert len(res) == len(eng._step_outputs)
    assert eng._step_outputs == (
        ("tokens",) + ("n_acc",) * spec + ("chunk_token",) * chunked
        + ("cache",))
    for name, got in zip(eng._step_outputs[:-1], res):
        assert (got.shape, got.dtype) == want[name], name
    assert _types(res[-1]) == _types(eng._cache)
    if prefill is not None:
        # a short bucket's program runs ``prefill_batch`` rows, a bucket's
        # that fills the chip one (ISSUE 41): one body, two signatures
        for bucket, rows in ((16, eng.prefill_batch), (MAXLEN, 1)):
            tok, cache = jax.eval_shape(prefill.python_fn,
                                        *eng._lint_args(bucket, rows))
            assert (tok.shape, tok.dtype) == ((rows,), i32)
            assert _types(cache) == _types(eng._cache)

    # (d) one step program for the engine's life
    assert eng.step_traces == 1
    assert eng.prefill_traces == (0 if chunked else len(buckets))
    assert buckets <= {8, 16}
