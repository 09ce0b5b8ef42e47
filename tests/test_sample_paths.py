"""The sampling epilogue does only what a live row's parameters ask for
(ISSUE 28): ``models.generation.sample_tokens``' traced branch runs its
argmax always, everything else under ``any(temperature > 0)``, and ONE
full-vocabulary sort under ``any(a sampling row truncates)``.

What is held here:

* tokens are what they were — against a frozen copy of the branch as it
  stood before the guards (two unconditional sorts), the same key gives the
  same tokens over every mixture of knobs; the one change (a ``top_p ==
  1.0`` row now keeps its whole vocabulary, as documented) is shown on the
  masked logits;
* a row's token does not depend on its batch;
* the lowered step programs hold every vocabulary sort inside a
  conditional's branch, one an epilogue;
* ``_target_probs`` is the distribution ``sample_tokens`` draws from;
* the engine's ``sample_path`` span arg and ``serving.sample_path`` counter
  say what the device's predicates decide, tick by tick, in one trace.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.extend.core import Literal

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.models.generation import (SAMPLE_PATHS, _target_probs,
                                          _truncate, sample_path,
                                          sample_tokens)
from paddle_tpu.serving import SamplingParams, ServingEngine

from lowered_step_text import lowered, sorts_over

V = 160                     # not a width of the tiny model: sorts_over keys
#                             on the vocabulary's length


# -- the traced branch as it stood at e7ae4f8 (PR 27), kept to compare ------

def _frozen_nucleus_mask(logits, top_p):
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    drop = (cum - probs) >= top_p
    kth = jnp.min(jnp.where(drop, jnp.inf, sorted_logits), axis=-1,
                  keepdims=True)
    return jnp.where(logits < kth, -jnp.inf, logits)


def _frozen_masked(logits, temperature, top_k, top_p):
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    srt = jnp.sort(scaled, axis=-1)[..., ::-1]
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, vocab), vocab)
    kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return _frozen_nucleus_mask(scaled, top_p[:, None])


def _frozen_sample_tokens(logits, key, temperature, top_k, top_p):
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    scaled = _frozen_masked(logits, temperature, top_k, top_p)
    samp = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, samp)


# -- knobs -------------------------------------------------------------------

def _logits(rows, seed=0, scale=3.0):
    return jnp.asarray(np.random.RandomState(seed).normal(
        0.0, scale, (rows, V)), jnp.float32)


def _knobs(rows):
    """(temperature, top_k, top_p) vectors from a list of per-row triples."""
    t, k, p = zip(*rows)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


IDLE = (0.0, 0, 1.0)
GREEDY_WITH_KNOBS = (0.0, 7, 0.5)       # temperature 0: the knobs are dead
TEMP = (0.8, 0, 1.0)
TOPK = (1.1, 5, 1.0)
TOPP = (0.9, 0, 0.7)
BOTH = (1.3, 12, 0.85)

MIXTURES = {
    "all_greedy": [IDLE, GREEDY_WITH_KNOBS, IDLE, IDLE],
    "temperature_only": [TEMP, (1.5, 0, 1.0), (0.3, 0, 1.0), TEMP],
    "top_k_only": [TOPK, (0.7, 1, 1.0), (1.0, 40, 1.0), (1.0, V + 9, 1.0)],
    "top_p_only": [TOPP, (1.0, 0, 0.2), (0.6, 0, 0.95), (1.4, 0, 0.5)],
    "both": [BOTH, (0.9, 3, 0.4), (1.0, 50, 0.9), BOTH],
    "mixed_with_idle": [IDLE, TEMP, TOPK, IDLE, TOPP, BOTH,
                        GREEDY_WITH_KNOBS, TEMP],
    "temperature_beside_idle": [IDLE, TEMP, IDLE, IDLE],
    "one_truncating_row": [IDLE, IDLE, BOTH, IDLE],
}
PATH_OF = {"all_greedy": "greedy", "temperature_only": "categorical",
           "temperature_beside_idle": "categorical"}


# -- the device's own predicates, read off the traced program ----------------

def _has_sort(jaxpr):
    """A sort that runs whenever ``jaxpr`` does: not one under a further
    conditional (``_run`` descends into the branch taken and asks again)."""
    return any(e.primitive.name == "sort" or (
        e.primitive.name != "cond" and any(
            _has_sort(getattr(sub, "jaxpr", sub))
            for sub in jax.core.jaxprs_in_params(e.params)))
        for e in jaxpr.eqns)


def _run(jaxpr, consts, args, taken):
    """Evaluate a jaxpr eagerly; at every ``cond`` met on the way record
    (branch taken, that branch sorts) and descend into that branch."""
    env = dict(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]
    for e in jaxpr.eqns:
        ins = [read(v) for v in e.invars]
        if e.primitive.name == "cond":
            branch = e.params["branches"][int(ins[0])]
            taken.append((int(ins[0]), _has_sort(branch.jaxpr)))
            outs = _run(branch.jaxpr, branch.consts, ins[1:], taken)
        else:
            outs = e.primitive.bind(*ins, **e.params)
            if not e.primitive.multiple_results:
                outs = [outs]
        env.update(zip(e.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


def _device_path(temperature, top_k, top_p):
    """The way ``sample_tokens`` goes for these vectors, by evaluating the
    predicates of its OWN traced conditionals (not the host's mirror): the
    name, the tokens, and whether a sort ran."""
    args = (_logits(len(temperature), seed=11), jax.random.key(5),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32))
    closed = jax.make_jaxpr(sample_tokens)(*args)
    taken = []
    out, = _run(closed.jaxpr, closed.consts, args, taken)
    sorted_ = any(s for _, s in taken)
    assert len(taken) == 1 + taken[0][0], taken     # outer, then inner
    return SAMPLE_PATHS[sum(i for i, _ in taken)], np.asarray(out), sorted_


# -- (a) tokens are what they were -------------------------------------------

@pytest.mark.parametrize("name", sorted(MIXTURES))
def test_same_key_same_tokens_as_the_frozen_branch(name):
    t, k, p = _knobs(MIXTURES[name])
    for seed in range(6):
        logits, key = _logits(len(t), seed), jax.random.key(100 + seed)
        want = _frozen_sample_tokens(logits, key, t, k, p)
        got = jax.jit(sample_tokens)(logits, key, t, k, p)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    path, _, sorted_ = _device_path(t, k, p)
    assert path == PATH_OF.get(name, "truncated")
    assert sorted_ == (path == "truncated")
    assert SAMPLE_PATHS[sample_path(*map(np.asarray, (t, k, p)))] == path


def test_masked_logits_are_the_frozen_ones_where_a_knob_is_on():
    """The kept set of every truncating row is bit for bit the old one (one
    sort gives what two gave); rows whose knobs are off come back whole."""
    rows = MIXTURES["mixed_with_idle"] + MIXTURES["top_k_only"] \
        + MIXTURES["top_p_only"] + MIXTURES["both"]
    t, k, p = _knobs(rows)
    logits = _logits(len(rows), seed=3)
    scaled = logits / jnp.maximum(t, 1e-6)[:, None]
    got = np.asarray(_truncate(scaled, (t > 0)[:, None], k[:, None],
                               p[:, None]))
    old = np.asarray(_frozen_masked(logits, t, k, p))
    for r, (temp, tk, tp) in enumerate(rows):
        if temp > 0 and (tk > 0 or tp < 1.0):
            np.testing.assert_array_equal(got[r], old[r])
            assert np.isinf(got[r]).any() or tk >= V
        else:
            np.testing.assert_array_equal(got[r], np.asarray(scaled)[r])


def test_top_p_one_row_keeps_its_whole_vocabulary_beside_a_truncating_one():
    """The one allowed difference.  Sharp logits: the sorted probabilities'
    running sum reaches 1.0 in float32 well before the row ends, so the old
    ``(cum - probs) >= 1.0`` dropped the far tail of a ``top_p == 1.0`` row
    (which the docstring calls off).  Now that row comes back untouched,
    whether or not a neighbour makes the truncation run."""
    logits = jnp.asarray(np.random.RandomState(2).normal(
        0.0, 12.0, (2, V)), jnp.float32)
    t, k, p = _knobs([(1.0, 0, 1.0), BOTH])
    old = np.asarray(_frozen_masked(logits, t, k, p))
    scaled = logits / t[:, None]
    got = np.asarray(_truncate(scaled, (t > 0)[:, None], k[:, None],
                               p[:, None]))
    dropped = np.isinf(old[0])
    assert dropped.any(), "the case no longer shows the old rounding"
    # what the old code dropped had no float32 mass: under 1e-7 of the row
    probs = np.asarray(jax.nn.softmax(scaled[0]))
    assert probs[dropped].max() < 1e-7
    np.testing.assert_array_equal(got[0], np.asarray(scaled)[0])
    np.testing.assert_array_equal(old[0][~dropped], got[0][~dropped])
    np.testing.assert_array_equal(got[1], old[1])     # the neighbour: as was
    # and alone (nothing truncates: the guard skips) it reads the same
    alone = _truncate(scaled[:1], (t > 0)[:1, None], k[:1, None],
                      p[:1, None])
    np.testing.assert_array_equal(np.asarray(alone)[0], got[0])


# -- (b) a row's token does not depend on its batch --------------------------

@pytest.mark.parametrize("row", [IDLE, GREEDY_WITH_KNOBS, TEMP, TOPK, TOPP,
                                 BOTH],
                         ids=["idle", "greedy_knobs", "temp", "top_k",
                              "top_p", "both"])
def test_a_rows_token_is_independent_of_its_batch(row):
    """Same logits row, same key, same place in the batch (the key material
    of a row is the key and its index): whatever the other rows ask for —
    nothing, a temperature, a truncation, other logits — the row's token is
    the one it gets with idle neighbours, and the one it gets alone."""
    sample = jax.jit(sample_tokens)
    mine = _logits(1, seed=21)
    for at in (0, 3):
        for seed in range(4):
            key = jax.random.key(seed)
            tokens = set()
            for others in ([IDLE] * 5, [TEMP] * 5, [BOTH] * 5,
                           [TOPP, IDLE, TEMP, TOPK, GREEDY_WITH_KNOBS]):
                for other_logits in (7, 8):
                    rows = list(others)
                    rows.insert(at, row)
                    logits = jnp.concatenate(
                        [_logits(5, other_logits)[:at], mine,
                         _logits(5, other_logits)[at:]])
                    tokens.add(int(sample(logits, key, *_knobs(rows))[at]))
            assert len(tokens) == 1, (row, at, seed, tokens)
            if at == 0:     # partitionable threefry: row 0 of any batch
                alone = int(sample(mine, key, *_knobs([row]))[0])
                assert tokens == {alone}


def test_target_probs_of_a_row_are_independent_of_its_batch():
    logits = _logits(4, seed=5)[:, None, :].repeat(3, axis=1)
    for row in (IDLE, GREEDY_WITH_KNOBS, TEMP, BOTH):
        alone = np.asarray(_target_probs(logits[:1], *_knobs([row])))
        for others in ([IDLE] * 3, [BOTH] * 3, [TEMP, TOPK, TOPP]):
            got = np.asarray(_target_probs(logits, *_knobs([row] + others)))
            np.testing.assert_array_equal(got[0], alone[0])


# -- (c) the lowered step programs -------------------------------------------

@pytest.fixture(scope="module")
def lm():
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(
        vocab_size=V, context_parallel="gspmd"))
    model.eval()
    return model


@pytest.mark.parametrize("chunked, program, shapes", [
    (False, "_step_impl_paged", [(3, V)]),
    (True, "_mixed_step_impl_paged", [(3, V), (1, V)]),
])
def test_every_vocabulary_sort_of_a_step_program_is_in_a_branch(
        lm, chunked, program, shapes):
    """One sort an epilogue (the mixed program has two epilogues: the rows'
    and the chunk's one row), each inside a conditional's branch; none at
    the program's top level.  Before the guards: two an epilogue, all at
    the top."""
    eng = ServingEngine(lm, paged=True, chunked=chunked, num_slots=3,
                        max_length=64, block_len=8, prefill_chunk=8)
    step = eng._step_fn.python_fn
    assert step.__name__ == program
    found = sorts_over(lowered(step, eng._lint_args()), V)
    assert sorted(s for s, _ in found) == sorted(shapes), found
    assert all(in_branch for _, in_branch in found), found


def test_the_frozen_branch_would_fail_that_reading():
    """The reading is not vacuous: the old epilogue lowers to two top-level
    sorts over the vocabulary."""
    def program(logits, cache, key, t, k, p):
        return _frozen_sample_tokens(logits, key, t, k, p), cache
    args = (_logits(3), jnp.zeros(()), jax.random.key(0),
            *_knobs([IDLE] * 3))
    found = sorts_over(lowered(program, args), V)
    assert found == [((3, V), False)] * 2, found


# -- (d) _target_probs is what sample_tokens draws from ----------------------

def test_target_probs_sum_to_one_and_match_the_masked_logits():
    rows = MIXTURES["mixed_with_idle"]
    t, k, p = _knobs(rows)
    logits = _logits(len(rows), seed=9)
    grid = jnp.stack([logits, logits[::-1], 0.5 * logits], axis=1)  # (B,3,V)
    probs = np.asarray(_target_probs(grid, t, k, p))
    np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-5)
    for j in range(grid.shape[1]):
        scaled = grid[:, j] / jnp.maximum(t, 1e-6)[:, None]
        masked = _truncate(scaled, (t > 0)[:, None], k[:, None], p[:, None])
        want = np.asarray(jax.nn.softmax(masked, axis=-1))
        np.testing.assert_array_equal(probs[:, j], want)
        # a sampling row's support is exactly its kept set
        live = np.asarray(t) > 0
        np.testing.assert_array_equal((probs[:, j] > 0)[live],
                                      (np.asarray(masked) > -np.inf)[live])
    # static scalars broadcast to the same thing
    np.testing.assert_array_equal(
        np.asarray(_target_probs(grid, 0.9, 4, 0.8)),
        np.asarray(_target_probs(grid, *_knobs([(0.9, 4, 0.8)] * len(rows)))))


# -- the host's mirror of the predicates -------------------------------------

def test_host_path_names_agree_with_the_devices_predicates():
    """Every combination of three rows drawn from the row kinds, plus float
    edges: the host's ``sample_path`` over numpy copies names the branch the
    traced program takes."""
    kinds = [IDLE, GREEDY_WITH_KNOBS, TEMP, TOPK, TOPP, BOTH,
             (-1.0, 3, 0.2),                    # negative: greedy
             (1e-9, 0, 1.0),                    # tiny but live
             (1.0, 0, float(np.nextafter(np.float32(1), np.float32(0)))),
             (1.0, 0, 1.0 - 1e-9)]              # rounds to 1.0 in float32
    seen = set()
    for a in kinds:
        for b in kinds:
            t, k, p = _knobs([a, b, IDLE])
            want, tokens, sorted_ = _device_path(t, k, p)
            got = SAMPLE_PATHS[sample_path(
                np.asarray(t), np.asarray(k), np.asarray(p))]
            assert got == want, (a, b)
            assert sorted_ == (want == "truncated")
            seen.add(want)
    assert seen == set(SAMPLE_PATHS)


# -- (e) the engine names each tick's path -----------------------------------

def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, V, n).astype(np.int32)


@pytest.mark.parametrize("layout", ["wave", "chunked", "chunked_spec"])
def test_engine_counts_each_ticks_path_as_the_device_decides_it(lm, layout):
    """Greedy, temperature-only and truncating requests through ONE engine:
    every program call's ``sample_path`` (span arg and counter) is what the
    device's predicates give for the vectors that call was handed, all
    three paths occur, and the step program was traced once."""
    obs.reset()
    kw = {"wave": {}, "chunked": {"chunked": True, "prefill_chunk": 8},
          "chunked_spec": {"chunked": True, "prefill_chunk": 8,
                           "spec_decode": True, "spec_k": 2}}[layout]
    eng = ServingEngine(lm, paged=True, num_slots=3, max_length=64,
                        block_len=8, **kw)
    calls = []          # per program call: the knob vectors it was handed

    def spy(fn, where, table):
        def call(params, cache, packed, *own):
            # what the program takes out of the packed buffer, by name:
            # (temps, topk, topp)[, (ctemps, ctopk, ctopp)]
            a = eng._unpack(table, packed, own)
            triples = [tuple(np.asarray(a[c + n]) for n in
                             ("temps", "topk", "topp"))
                       for c in ("", "c") if c + "temps" in a]
            calls.append((where, triples))
            return fn(params, cache, packed, *own)
        return call
    eng._linted = True      # the first tick's self-lint would trace the spy
    step_fn, prefill_fn = eng._step_fn, eng._prefill_fn
    eng._step_fn = spy(step_fn, "step", eng._step_table)
    if eng._rows_fn is not None:    # a cursor engine's chunk-free ticks
        eng._rows_fn = spy(eng._rows_fn, "step", eng._step_table)
    if prefill_fn is not None:
        eng._prefill_fn = spy(prefill_fn, "prefill", eng._prefill_table)

    def serve(*samplings):
        rids = [eng.submit(_prompt(11 + 3 * i, 40 + i), max_new_tokens=5,
                           sampling=s) for i, s in enumerate(samplings)]
        out = dict(eng.drain())
        assert all(len(out[r]) == 5 for r in rids)

    serve(None, SamplingParams())                                  # greedy
    serve(SamplingParams(temperature=0.8), None)              # categorical
    serve(SamplingParams(temperature=0.9, top_p=0.7),
          SamplingParams(temperature=0.5))                      # truncated
    serve(SamplingParams(temperature=1.0, top_k=4), None)
    serve(None)                                             # greedy again

    want = []
    for where, triples in calls:
        mixed = layout != "wave" and where == "step"
        assert len(triples) == (2 if mixed else 1)      # rows[, the chunk]
        want.append(SAMPLE_PATHS[max(
            SAMPLE_PATHS.index(_device_path(*tr)[0]) for tr in triples)])
    names = ("serving.decode", "serving.verify", "serving.prefill")
    spans = [ev for ev in obs.get_tracer().events()
             if ev["name"] in names and ev.get("ph") == "X"]
    spans.sort(key=lambda ev: ev["ts"])
    got = [ev["args"]["sample_path"] for ev in spans]
    assert got == want
    assert set(got) == set(SAMPLE_PATHS)
    series = obs.snapshot()["serving.sample_path"]["series"]
    counted = {r["labels"]["path"]: r["value"] for r in series
               if r["labels"]["engine"] == eng._eid}
    assert counted == {p: want.count(p) for p in SAMPLE_PATHS}
    assert eng.step_traces == 1
