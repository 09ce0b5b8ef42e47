"""One packed upload a program call (ISSUE 38).

The operands of a device program after ``(params, cache)`` cross to the
device as ONE buffer of 32-bit words, laid out once from the operand table
(``engine._lay_out``), filled on the host (``ServingEngine._pack``) and taken
apart in the program's first lines (``ServingEngine._unpack``).  Held here:
(a) what the program takes out of a real tick's buffer is, bit for bit and
dtype for dtype, what the table's sources held, and the key is the eager
fold of the base key and the tick; (b) a program call makes exactly the
host->device calls its span states — one, or two where an operand is not
small; (c) a tick's values are still on the device once the next tick's
buffer is written; (d) a served mix of greedy and sampled rows gives the
tokens a per-operand upload of the same values gives (the old walk, kept
here and nowhere else).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu import observability as obs
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving import engine as engine_mod

SLOTS, MAXLEN, K = 3, 64, 2
LLAMA = {f"{'paged' if p else 'contiguous'}{'-chunked' * c}{'-spec' * s}":
         dict(paged=p, chunked=c, spec_decode=s)
         for p in (True, False) for c in (False, True) for s in (False, True)}
OTHERS = ("block-rows", "slot-state")


@pytest.fixture(scope="module")
def models():
    from benchmark.harness import weights_lfm2, weights_sdar
    from paddle_tpu.models import (Lfm2MoeForCausalLM, LlamaForCausalLM,
                                   SdarMoeForCausalLM, tiny_llama_config,
                                   tiny_lfm2_config, tiny_sdar_config)
    import test_lfm2
    import test_sdar

    pt.seed(7)
    llama = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    llama.eval()
    with nn.abstract_parameters():
        lfm2 = Lfm2MoeForCausalLM(tiny_lfm2_config(
            max_position_embeddings=256, ep_size=2, ep_rank=1))
        sdar = SdarMoeForCausalLM(tiny_sdar_config(ep_size=2, ep_rank=1))
    for model, made, ref in ((lfm2, weights_lfm2, test_lfm2.REF),
                             (sdar, weights_sdar, test_sdar.REF)):
        model.eval()
        model.set_state_dict({made.program_name(n): w for n, w in
                              made.make_weights(ref, 3, "float32").items()})
    return {"llama": llama, "slot-state": lfm2, "block-rows": sdar}


def _engine(models, layout, **over):
    if layout in OTHERS:          # the one layout such a model is served in
        kw = dict(num_slots=4, paged=True, chunked=True, prefix_cache=False)
        model = models[layout]
    else:
        kw, model = dict(num_slots=SLOTS, spec_k=K, **LLAMA[layout]), \
            models["llama"]
    return ServingEngine(model, max_length=MAXLEN, block_len=8,
                         prefill_chunk=8, **{**kw, **over})


def _serve(eng, sampled=True, ticks=None, new=6):
    """A few requests of differing lengths, greedy and (fixed seeds)
    sampled: drained, or run for ``ticks`` ticks."""
    rs = np.random.RandomState(3)
    knobs = [None, SamplingParams(temperature=0.7, top_k=5, top_p=0.9),
             SamplingParams(temperature=1.3)]
    if eng._block:      # a block engine's rows follow the unmasking rule
        knobs[2] = SamplingParams(unmask_strategy="low_confidence_static")
    for n, sp in zip((5, 11, 19), knobs):
        eng.submit(rs.randint(1, 250, n).astype(np.int32),
                   max_new_tokens=new, sampling=sp if sampled else None)
    if ticks is None:
        return eng.drain()
    for _ in range(ticks):
        eng.step()


def _spy_uploads(eng):
    """Every upload of the engine: (table, bucket, the sources' values as
    the host held them — copies —, what ``_upload`` returned)."""
    seen, upload = [], eng._upload

    def spy(table, own, bucket=0):
        held = {o.name: np.array(o.src if isinstance(o.src, np.ndarray)
                                 else own[o.src], copy=True) for o in table}
        args = upload(table, own, bucket)
        seen.append((table, bucket, held, args))
        return args
    eng._upload = spy
    return seen


def _bits(x):
    return np.ascontiguousarray(np.asarray(x)).tobytes()


def _check_unpacked(eng, table, bucket, held, args):
    a = eng._unpack(table, args[0], args[1:])
    assert list(a) and set(a) == {o.name for o in table}
    for o in table:
        got, want = a[o.name], held[o.name]
        shape = tuple(bucket if d is None else d for d in o.shape)
        if o.name == "key":
            assert got.dtype == eng._base_key.dtype
            assert _bits(jax.random.key_data(got)) == _bits(
                jax.random.key_data(jax.random.fold_in(eng._base_key,
                                                       int(want))))
            continue
        assert got.dtype == np.dtype(o.dtype), o.name
        assert got.shape == shape, o.name
        assert _bits(got) == _bits(np.asarray(want, o.dtype).reshape(shape)), \
            o.name


# -- (a) the program takes out what the host put in --------------------------

@pytest.mark.parametrize("layout", list(LLAMA) + list(OTHERS))
def test_a_real_ticks_buffer_unpacks_to_what_its_sources_held(models, layout):
    eng = _engine(models, layout)
    seen = _spy_uploads(eng)
    _serve(eng)
    steps = [u for u in seen if u[0] is eng._step_table]
    assert len(steps) >= 5
    for table, bucket, held, args in steps:
        assert len(args) == 1 and args[0].dtype == np.int32
        _check_unpacked(eng, table, bucket, held, args)
    # the ticks differ: sampled knobs, masks and positions did cross
    assert len({_bits(args[0]) for *_, args in steps}) > 3
    assert any(held["temps"].any() for _, _, held, _ in steps)
    if eng.chunked:
        assert {int(held["clen"]) for _, _, held, _ in steps} >= {0, 8}
    assert eng.step_traces == 1


@pytest.mark.parametrize("bucket", [8, 16, 32])
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_a_waves_buffer_unpacks_at_its_bucket(models, layout, bucket):
    """The wave's ``ids`` takes what is left of the buffer, so one layout
    serves every bucket and the buffer's length states the bucket.  The
    program of a bucket that fills the chip runs ONE row (ISSUE 41; the
    chip's 256 positions brought down to 32 for the tiny model): the two
    row counts have a table and a layout each, and the one-row wave's
    buffer says its rows by a leading axis."""
    eng = _engine(models, layout)
    eng._lone_from = 32
    seen = _spy_uploads(eng)
    rs = np.random.RandomState(bucket)
    eng.submit(rs.randint(1, 250, bucket - 3).astype(np.int32),
               max_new_tokens=2,
               sampling=SamplingParams(temperature=0.9, top_p=0.8))
    eng.drain()
    rows = eng._wave_rows(bucket)
    assert rows == (1 if bucket == 32 else eng.prefill_batch)
    (wave,) = [u for u in seen if u[0] is not eng._step_table]
    table, got_bucket, held, args = wave
    assert table is eng._wave_tables[rows]
    assert (rows == 1) == (table is not eng._prefill_table)
    assert got_bucket == bucket and len(args) == 1
    lay = eng._layout(table)
    assert args[0].shape == ((lay.words + rows * bucket,) if rows > 1
                             else (1, lay.words + bucket))
    assert lay.packed[-1][0].name == "ids" and lay.a_token == rows
    _check_unpacked(eng, table, bucket, held, args)
    assert held["ids"].shape == (rows, bucket)
    assert eng.prefill_traces == 1


@pytest.mark.parametrize("layout", ["paged-chunked-spec", "block-rows"])
def test_every_bit_pattern_survives_the_buffer(models, layout):
    """float32 rides as its bits: NaN payloads, -0.0 and denormals come out
    as they went in; a mask comes out as the same truth values."""
    eng = _engine(models, layout)
    rs = np.random.RandomState(11)

    def value(o):
        if o.name == "key":
            return 123456789
        if o.dtype is bool:
            return rs.randint(0, 2, o.shape).astype(bool)
        bits = rs.randint(0, 1 << 32, o.shape, dtype=np.uint64).astype(
            np.uint32)
        # a NaN with a payload, then -0.0, a denormal, ... where they fit
        odd = np.array([0x7fc12345, 0x80000000, 1, 0xff800000], np.uint32)
        bits.reshape(-1)[:odd.size] = odd[:bits.size]
        return bits.view(o.dtype)
    held = {o.name: value(o) for o in eng._step_table}
    args = eng._pack(eng._step_table, lambda o: held[o.name])
    assert all(np.isnan(held[o.name].reshape(-1)[0])
               for o in eng._step_table if o.dtype is np.float32)
    _check_unpacked(eng, eng._step_table, 0, held, args)
    # under jit too, where XLA fuses the slices into their consumers
    a = jax.jit(lambda p, *own: {
        n: x for n, x in eng._unpack(eng._step_table, p, own).items()
        if n != "key"})(*args)
    for o in eng._step_table[:-1]:
        assert _bits(a[o.name]) == _bits(held[o.name]), o.name


@pytest.mark.parametrize("layout", ["paged", "paged-chunked", "block-rows"])
def test_a_programs_text_does_not_hold_the_engines_seed(models, layout):
    """The base key crosses as data.  Folded in as a constant it would put
    the seed into the program's text, and every run on a new seed would miss
    the compile cache (a chip run of this PR's first draft did: the step
    program compiled anew in every process)."""
    texts = set()
    for seed in (0, 1, 2**31 + 7):
        eng = _engine(models, layout, seed=seed)
        programs = [(eng._step_fn, eng._lint_args())]
        if eng._rows_fn is not None:
            programs.append((eng._rows_fn, eng._lint_args()))
        if eng._prefill_fn is not None:
            programs.append((eng._prefill_fn, eng._lint_args(16)))
        texts.add(tuple(jax.jit(fn.python_fn).lower(*args).as_text()
                        for fn, args in programs))
    assert len(texts) == 1


# -- the layout ---------------------------------------------------------------

def _op(name, shape, dtype):
    return engine_mod._Operand(name, shape, dtype, name)


def test_layout_starts_every_operand_on_a_lane_tile():
    table = [_op("a", (3,), np.int32), _op("b", (5, 70), np.float32),
             _op("m", (3,), bool), _op("s", (), np.int32)]
    lay = engine_mod._lay_out(table, 64)
    assert [(o.name, at) for o, at in lay.packed] == [
        ("a", 0), ("b", 128), ("m", 512), ("s", 640)]
    assert (lay.words, lay.a_token, lay.own) == (768, 0, ())
    assert lay.nbytes() == 4 * 768


def test_layout_puts_the_bucketed_operand_last():
    table = [_op("ids", (4, None), np.int32), _op("lens", (4,), np.int32)]
    lay = engine_mod._lay_out(table, 64)
    assert [(o.name, at) for o, at in lay.packed] == [("lens", 0),
                                                      ("ids", 128)]
    assert (lay.words, lay.a_token) == (128, 4)
    assert lay.nbytes(16) == 4 * (128 + 64)


@pytest.mark.parametrize("vocab,alone", [(32768, True), (256, False)])
def test_an_operand_over_a_mebibyte_crosses_alone(vocab, alone):
    """The size rule reads the table: the spec engine's proposal
    distributions at a real vocabulary (8 x 4 x 32768 float32 = 4 MiB)
    keep a transfer of their own; a tiny model's ride in the buffer."""
    table = [_op("tokens", (8, 5), np.int32),
             _op("draft_probs", (8, 4, vocab), np.float32),
             _op("temps", (8,), np.float32)]
    lay = engine_mod._lay_out(table, 64)
    assert [o.name for o in lay.own] == ["draft_probs"] * alone
    assert [o.name for o, _ in lay.packed] == [
        o.name for o in table if not (alone and o.name == "draft_probs")]
    assert lay.nbytes() == 4 * lay.words + alone * 8 * 4 * vocab * 4
    # a wave's ids is judged at the engine's largest bucket, whatever the
    # wave's: one signature a program
    wave = [_op("ids", (8, None), np.int32)]
    assert bool(engine_mod._lay_out(wave, 1 << 16).own) and not \
        engine_mod._lay_out(wave, 1 << 15).own


# -- (b) one host->device call a program call ---------------------------------

def _count_puts(monkeypatch):
    puts = []
    put = engine_mod._put

    def counting(x, *a, **kw):
        puts.append(np.asarray(x).nbytes)
        return put(x, *a, **kw)
    monkeypatch.setattr(engine_mod, "_put", counting)
    return puts


@pytest.mark.parametrize("layout,transfers", [
    ("paged", 1), ("contiguous", 1), ("paged-chunked", 1),
    ("contiguous-chunked-spec", 1), ("block-rows", 1), ("slot-state", 1),
    ("paged-spec", 2), ("paged-chunked-spec", 2)])
def test_a_program_call_makes_the_transfers_its_span_states(
        models, monkeypatch, layout, transfers):
    if transfers == 2:
        # the tiny model's (3, 2, 256) float32 proposals are 6 KB: bring the
        # fixed size down to them, under every other operand's nothing
        monkeypatch.setattr(engine_mod, "_OWN_TRANSFER_BYTES", 4096)
    puts = _count_puts(monkeypatch)
    eng = _engine(models, layout)
    eng._linted = True          # the first tick's lint packs a buffer too
    calls = []      # per program call: (its arguments, the puts so far)

    def spy(fn):
        def call(*args):
            calls.append((len(args), len(puts)))
            return fn(*args)
        return call
    eng._step_fn = spy(eng._step_fn)
    if eng._rows_fn is not None:    # a cursor engine's chunk-free ticks
        eng._rows_fn = spy(eng._rows_fn)
    if eng._prefill_fn is not None:
        eng._prefill_fn = spy(eng._prefill_fn)
    obs.get_tracer().clear()
    _serve(eng)
    uploads = [e for e in obs.get_tracer().events()
               if e["name"] == "serving.upload"]
    assert len(uploads) == len(calls) >= 6
    before = 0
    for up, (arity, after) in zip(uploads, calls):
        wave = up["args"]["operands"] != len(eng._step_table)
        table = eng._prefill_table if wave else eng._step_table
        want = 1 if wave else transfers
        assert after - before == want == up["args"]["transfers"]
        assert arity == 2 + want
        assert sum(puts[before:after]) == up["args"]["bytes"]
        assert up["args"]["operands"] == len(table)
        before = after
    assert len(puts) == before          # nothing crossed outside an upload
    own = [o.name for o in eng._step_layout.own]
    assert own == ["draft_probs"] * (transfers - 1)
    assert eng.step_traces == 1


# -- (c) a tick's values outlive the next tick's buffer ----------------------

@pytest.mark.parametrize("layout", ["paged", "paged-chunked",
                                    "contiguous-spec"])
def test_a_ticks_values_are_on_the_device_after_the_next_buffer_is_written(
        models, layout):
    """On the CPU ``device_put`` may alias the host's memory: a staging
    buffer reused across ticks would show tick n + 1's values through tick
    n's array.  Each tick's buffer is its own."""
    eng = _engine(models, layout)
    seen = _spy_uploads(eng)
    kept = []
    upload = eng._upload

    def keep(table, own, bucket=0):
        args = upload(table, own, bucket)
        kept.append(np.array(args[0], copy=True))   # as it was when sent
        return args
    eng._upload = keep
    _serve(eng, ticks=8, new=12)
    assert len(seen) == len(kept) >= 8
    sent = [args[0] for *_, args in seen]
    assert len({_bits(k) for k in kept}) > 3        # the ticks do differ
    for was, now in zip(kept, sent):                # read back at the end
        assert _bits(now) == _bits(was)
    for (table, bucket, held, args) in seen[:-1]:
        _check_unpacked(eng, table, bucket, held, args)


# -- (d) the tokens a per-operand upload gives -------------------------------

def _old_walk(eng):
    """The upload as it was before ISSUE 38, for a program that takes its
    operands one by one: a ``jnp.asarray`` a mirror array, a ``jnp.int32`` a
    chunk scalar, an eager fold of the key — the same values, one transfer
    each.  The program's body is the engine's own; only its first line is
    swapped (before its one trace)."""
    def upload(table, own, bucket=0):
        out = []
        for o in table:
            v = o.src if isinstance(o.src, np.ndarray) else own[o.src]
            out.append(
                jax.random.fold_in(eng._base_key, v) if o.name == "key"
                else jnp.int32(v) if o.shape == ()
                else jnp.asarray(np.array(v, o.dtype)))
        return [jnp.zeros((1,), jnp.int32), *out]

    def unpack(table, packed, own):
        return dict(zip((o.name for o in table), own))
    eng._upload, eng._unpack = upload, unpack
    eng._linted = True          # ``_lint_args`` states the packed signature


@pytest.mark.parametrize("layout", ["paged", "paged-chunked",
                                    "contiguous-chunked-spec", "paged-spec",
                                    "block-rows", "slot-state"])
def test_served_tokens_are_those_of_a_per_operand_upload(models, layout):
    packed, each = _engine(models, layout), _engine(models, layout)
    _old_walk(each)
    got, want = _serve(packed), _serve(each)
    assert [len(t) for _, t in got] == [6, 6, 6]
    assert got == want
    # the sampled rows did sample: greedy serving gives other tokens
    greedy = _serve(_engine(models, layout), sampled=False)
    assert greedy[0][1] == got[0][1] and greedy != got
    assert packed.step_traces == each.step_traces == 1
