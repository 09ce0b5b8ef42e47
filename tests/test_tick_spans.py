"""The serving tick measured from inside (ISSUE 24).

One span call, two sinks, one clock: the engine's phase spans
(``serving.engine.TICK_PHASES``) tile ``serving.step`` in the tracer's ring
AND reach a ``jax.profiler`` trace as ``TraceAnnotation``s; span ``ts``,
request-log ``t_ms`` and ``perf_counter`` stamps convert into each other;
``FLAGS_observability_spans`` off silences both sinks; and every Pallas
kernel carries a name the device trace can show.
"""

import ast
import glob
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import TICK_COSTS, TICK_PHASES

MAXLEN = 64
MODES = {"wave": {}, "chunked": {"chunked": True, "prefill_chunk": 8},
         "spec": {"spec_decode": True, "spec_k": 2}}
PROMPTS = (5, 11)


@pytest.fixture(scope="module")
def lm():
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    return model


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def _engine(lm, mode, new_tokens=6):
    eng = ServingEngine(lm, num_slots=3, max_length=MAXLEN, paged=True,
                        block_len=8, **MODES[mode])
    for i, n in enumerate(PROMPTS):
        eng.submit(_prompt(n, i + 1), max_new_tokens=new_tokens)
    return eng


def _inside(a, b):
    return (b["ts"] <= a["ts"]
            and a["ts"] + a["dur"] <= b["ts"] + b["dur"] + 1e-6)


def _tick_events():
    """(the one serving.step span, the phase spans) of the ring, which the
    caller cleared before the tick."""
    evs = [e for e in obs.get_tracer().events() if e["ph"] == "X"]
    (step,) = [e for e in evs if e["name"] == "serving.step"]
    return step, [e for e in evs if e["name"] in TICK_PHASES], evs


# -- (a) the tick in phases --------------------------------------------------

@pytest.mark.parametrize("mode", sorted(MODES))
def test_tick_phases_tile_the_step(lm, mode):
    eng = _engine(lm, mode, new_tokens=24)
    outside = []
    for tick in range(6):
        obs.get_tracer().clear()
        eng.step()
        step, phases, evs = _tick_events()
        # exactly the engine's tuple, every tick, in every step body
        assert {e["name"] for e in phases} == set(TICK_PHASES)
        assert "tick" in step["args"]
        for e in evs:
            if e["name"].startswith("serving."):
                assert _inside(e, step) and e["tid"] == step["tid"]
        # nested or disjoint, never overlapping
        for a in phases:
            for b in phases:
                if a is not b and not (_inside(a, b) or _inside(b, a)):
                    assert (a["ts"] + a["dur"] <= b["ts"] + 1e-6
                            or b["ts"] + b["dur"] <= a["ts"] + 1e-6)
        # the outermost phases tile the step: what is left outside them
        top = [e for e in phases
               if not any(o is not e and _inside(e, o) for o in phases)]
        outside.append(step["dur"] - sum(e["dur"] for e in top))
        assert outside[-1] >= 0
        if tick == 0:           # it compiles inside serving.dispatch
            assert outside[0] < 0.02 * step["dur"]
    # The share is a statement about the chip's 25-80 ms ticks (PERF.md:
    # under 2 %); a warm tick of this model on a CPU is 2-4 ms, of which
    # the spans' own bookkeeping is ~0.1 ms, so warm ticks get an absolute
    # bound, and over their MEDIAN: a worker the machine deschedules for a
    # tick (six run side by side) cannot fail it
    assert sorted(outside[1:])[2] < 1000.0                    # us


# -- (a') the two costs inside the phases ------------------------------------

def _spy_programs(eng):
    """Record, per call of the engine's step and prefill programs, what was
    handed over after (params, cache): (table, the arrays, their bytes)."""
    calls = []

    def spy(fn, table):
        def call(params, cache, *args):
            calls.append((table, len(args),
                          sum(int(a.nbytes) for a in args)))
            return fn(params, cache, *args)
        return call
    eng._linted = True              # or the first tick's lint traces the spy
    eng._step_fn = spy(eng._step_fn, eng._step_table)
    if eng._rows_fn is not None:    # a cursor engine's chunk-free ticks
        eng._rows_fn = spy(eng._rows_fn, eng._step_table)
    if eng._prefill_fn is not None:
        eng._prefill_fn = spy(eng._prefill_fn, eng._prefill_table)
    return calls


@pytest.mark.parametrize("mode", sorted(MODES))
def test_upload_span_says_what_crossed_to_the_device(lm, mode):
    """``serving.upload``: once a program call, inside that call's
    ``serving.build_inputs``, with the operand table's length, the
    uploaded arrays' bytes and how many arrays crossed (ONE: the packed
    buffer); ``serving.dispatch`` is the call alone and says how many
    leaves of params and cache it flattens."""
    eng = _engine(lm, mode)
    calls = _spy_programs(eng)
    leaves = len(jax.tree_util.tree_leaves((eng._params, eng._cache)))
    waves = 0
    for _ in range(4):
        obs.get_tracer().clear()
        before = len(calls)
        eng.step()
        _, phases, evs = _tick_events()
        uploads = [e for e in evs if e["name"] == "serving.upload"]
        assert len(uploads) == len(calls) - before >= 1
        waves += len(uploads) - 1
        builds = [e for e in phases if e["name"] == "serving.build_inputs"]
        launches = [e for e in phases if e["name"] == "serving.dispatch"]
        assert len(launches) == len(uploads)
        for up, launch, (table, arrays, nbytes) in zip(uploads, launches,
                                                       calls[before:]):
            assert sum(_inside(up, b) for b in builds) == 1
            assert up["ts"] + up["dur"] <= launch["ts"] + 1e-6
            assert arrays == 1
            assert up["args"] == {"operands": len(table), "bytes": nbytes,
                                  "transfers": arrays}
            assert launch["args"] == {"leaves": leaves}
    assert waves == (mode != "chunked")     # the wave engines' one wave


@pytest.mark.parametrize("mode", sorted(MODES))
def test_account_span_gathers_the_ticks_own_bookkeeping(lm, mode):
    """``serving.account``: at most twice a tick — inside
    ``serving.build_inputs`` before the device seam, inside
    ``serving.advance`` after it — and once a wave, before its
    ``serving.prefill`` opens; what it computed still reaches the rows
    span's arguments."""
    eng = _engine(lm, mode)
    for tick in range(4):
        obs.get_tracer().clear()
        eng.step()
        _, phases, evs = _tick_events()
        by = {n: [e for e in evs if e["name"] == n] for n in (
            "serving.account", "serving.prefill", "serving.build_inputs",
            "serving.advance", "serving.admit", "serving.dispatch")}
        accounts, waves = by["serving.account"], by["serving.prefill"]
        assert len(accounts) == 2 + len(waves) <= 3
        *of_waves, before, after = accounts
        (step_launch,) = [d for d in by["serving.dispatch"]
                          if not any(_inside(d, w) for w in waves)]
        assert any(_inside(before, b) for b in by["serving.build_inputs"])
        assert before["ts"] + before["dur"] <= step_launch["ts"] + 1e-6
        assert any(_inside(after, a) for a in by["serving.advance"])
        assert step_launch["ts"] + step_launch["dur"] <= after["ts"] + 1e-6
        for acc, wave in zip(of_waves, waves):
            assert _inside(acc, by["serving.admit"][0])
            assert acc["ts"] + acc["dur"] <= wave["ts"] + 1e-6
            assert {"sample_path", "kv_blocks", "kv_walk"} <= set(
                wave["args"])
        (rows,) = [e for e in evs
                   if e["name"] in ("serving.decode", "serving.verify")]
        assert {"kv_blocks", "kv_walk", "sample_path", "weight_passes",
                "pass_rows", "pass_tokens", "slots"} <= set(rows["args"])
        # what the two names add to the ring: three events a tick, and
        # two for a wave in it
        assert sum(e["name"] in TICK_COSTS for e in evs) \
            == 3 + 2 * len(waves)


def _queued_chunks_by_walk(eng):
    return sum(-(-eng._prompt_commit(r) // eng.prefill_chunk)
               for q in (eng._resume_q, eng._queue) for r in q)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_the_kept_chunk_queue_count_is_the_walk(lm, mode):
    """The chunk-queue depth comes from a count kept where a request
    enters or leaves a queue, not from a walk of the backlog each tick:
    equal to the walk after submit, admit, preempt, resume and cancel."""
    eng = ServingEngine(lm, num_slots=4, max_length=MAXLEN, paged=True,
                        block_len=8, num_blocks=13, prefill_batch=2,
                        preempt="recompute", **MODES[mode])
    seen = set()

    def check(what):
        assert eng._queued_chunks == _queued_chunks_by_walk(eng), what
        pf = eng._prefill
        assert eng._pending_chunks() == eng._queued_chunks + (
            0 if pf is None else -(-(pf.end - pf.cursor)
                                   // eng.prefill_chunk)), what
        seen.add(what)

    low = [eng.submit(_prompt(n, n), max_new_tokens=12, priority=0)
           for n in (12, 10)]
    check("submit")
    assert eng._queued_chunks == sum(-(-n // eng.prefill_chunk)
                                     for n in (12, 10))
    for _ in range(6 if eng.chunked else 3):
        eng.step()
        check("admit")
    assert not eng.queue_depth
    high = [eng.submit(_prompt(n, n), max_new_tokens=12, priority=5)
            for n in (14, 9)]
    last = eng.submit(_prompt(20, 20), max_new_tokens=4, priority=0)
    check("submit")
    preempted = resumed = 0
    for _ in range(200):
        if not (eng.queue_depth or eng.num_preempted or eng.num_active
                or eng.num_pending):
            break
        if eng.queue_depth and preempted and "cancel" not in seen:
            assert eng.cancel(last)       # still queued behind the rest
            check("cancel")
        eng.step()
        m = eng.metrics()["preempt"]
        if sum(m["preemptions"].values()) > preempted:
            preempted = sum(m["preemptions"].values())
            check("preempt")
        if sum(m["resumes"].values()) > resumed:
            resumed = sum(m["resumes"].values())
            check("resume")
        check("tick")
    assert seen == {"submit", "admit", "preempt", "resume", "cancel", "tick"}
    assert eng._queued_chunks == 0 == eng._pending_chunks()
    assert all(len(eng.result(r)) == 12 for r in low + high)
    if eng.chunked:
        assert eng.metrics()["chunked"]["chunk_queue_depth"]["count"] \
            == eng._ticks


@pytest.mark.parametrize("mode", ["wave", "spec"])
def test_wave_prefill_span_says_what_it_padded(lm, mode):
    eng = _engine(lm, mode)
    obs.get_tracer().clear()
    eng.step()
    step, phases, evs = _tick_events()
    (wave,) = [e for e in evs if e["name"] == "serving.prefill"]
    (admit,) = [e for e in phases if e["name"] == "serving.admit"]
    assert _inside(wave, admit)
    # both requests enter in one wave: padded to prefill_batch rows of the
    # longest prompt's power-of-two bucket
    # ... and the paged wave's rows go through the flash-decode kernel at
    # prefix 0: the blocks its q tiles need and the block slots it walks
    # for them, by the kernel's own bounds, over the model's layers
    from paddle_tpu.ops.pallas.decode_attention import walk_counts
    c = lm.config
    need, walk = walk_counts(
        np.zeros(eng.prefill_batch), 16,
        c.num_attention_heads // c.num_key_value_heads, bk=8,
        n_cols=MAXLEN // 8)
    assert 0 < need <= walk
    assert wave["args"] == {"bucket": 16, "rows": len(PROMPTS),
                            "padded_rows": eng.prefill_batch,
                            "tokens": sum(PROMPTS),
                            "sample_path": "greedy",
                            "kv_blocks": c.num_hidden_layers * need,
                            "kv_walk": c.num_hidden_layers * walk}
    # the wave's own upload, launch and fetch are phases inside it
    inner = {e["name"] for e in phases if _inside(e, wave)}
    assert inner == {"serving.build_inputs", "serving.dispatch",
                     "serving.readback"}


WALK_MODES = dict(MODES, contiguous_chunked={
    "paged": False, "chunked": True, "prefill_chunk": 8})


@pytest.mark.parametrize("mode", sorted(WALK_MODES))
def test_tick_spans_count_the_kernels_block_walk(lm, mode):
    """``kv_blocks=`` / ``kv_walk=`` on the tick's rows span: the flash-decode
    kernel's own bounds (``walk_counts``) over the positions the tick
    UPLOADS — the rows' (a verify window's q length with them) and, on a
    cursor engine, the chunk part's (a chunk-free tick's program keeps a stub
    of it) — times the layers."""
    from paddle_tpu.ops.pallas.decode_attention import walk_counts

    kw = dict({"paged": True, "block_len": 8}, **WALK_MODES[mode])
    eng = ServingEngine(lm, num_slots=3, max_length=MAXLEN, **kw)
    for i, n in enumerate(PROMPTS):
        eng.submit(_prompt(n, i + 1), max_new_tokens=6)
    eng._linted = True              # or the first tick's lint traces the spy
    handed = []

    def spy(fn):
        def call(params, cache, packed, *own):
            a = eng._unpack(eng._step_table, packed, own)
            handed.append({n: np.asarray(a[n])
                           for n in ("positions", "cpos", "clen") if n in a})
            return fn(params, cache, packed, *own)
        return call
    eng._step_fn = spy(eng._step_fn)
    if eng._rows_fn is not None:    # a cursor engine's chunk-free ticks
        eng._rows_fn = spy(eng._rows_fn)
    c = lm.config
    g = c.num_attention_heads // c.num_key_value_heads
    bk, cols = (8, MAXLEN // 8) if eng.paged else (MAXLEN, 1)
    seen = 0
    for _ in range(8):
        obs.get_tracer().clear()
        before = len(handed)
        eng.step()
        rows = [e for e in obs.get_tracer().events() if e["ph"] == "X"
                and e["name"] in ("serving.decode", "serving.verify")]
        assert len(rows) == len(handed) - before <= 1
        if not rows:
            continue
        (ops,) = handed[before:]
        calls = [(ops["positions"], eng.spec_k + 1 if eng.spec else 1)]
        if eng.chunked:     # a chunk-free tick's program keeps a stub
            calls.append(([int(ops["cpos"])], eng.prefill_chunk
                          if ops["clen"] else eng._stub_chunk))
        need, walk = (sum(x) for x in zip(*(
            walk_counts(p, s, g, bk=bk, n_cols=cols) for p, s in calls)))
        assert 0 < need <= walk
        assert (rows[0]["args"]["kv_blocks"], rows[0]["args"]["kv_walk"]) \
            == (c.num_hidden_layers * need, c.num_hidden_layers * walk)
        seen += 1
    assert seen >= 4


PASS_MODES = dict(MODES, spec_chunked={
    "spec_decode": True, "spec_k": 2, "chunked": True, "prefill_chunk": 8})


@pytest.mark.parametrize("mode", sorted(PASS_MODES))
def test_tick_spans_say_what_the_one_weight_pass_ran_over(lm, mode):
    """``weight_passes=`` / ``pass_rows=`` / ``pass_tokens=`` on the tick's
    rows span: the step program streams the token-wise weights once, over
    ``num_slots·(k+1)`` token rows and ``prefill_chunk`` more on a tick with
    a chunk, the rows-alone program's stub of them on a cursor engine's tick
    without (``parts=`` 1 or 2: the program the tick runs), of which the live rows' tokens (a verify window's real drafts with them)
    and the chunk's real tokens are real."""
    eng = ServingEngine(lm, num_slots=3, max_length=MAXLEN, paged=True,
                        block_len=8, **PASS_MODES[mode])
    from paddle_tpu.serving.drafter import Drafter

    class TwoTokens(Drafter):       # proposes on every tick it may
        def propose(self, history):
            return np.asarray([3, 4], np.int32)
    for i, n in enumerate((13, 7)):
        eng.submit(_prompt(n, i + 1), max_new_tokens=8,
                   drafter=TwoTokens() if eng.spec else None)
    rows_of = eng.num_slots * (eng.spec_k + 1 if eng.spec else 1)
    kinds = set()
    while eng.queue_depth or eng.last_occupancy or not kinds:
        obs.get_tracer().clear()
        eng.step()
        evs = [e for e in obs.get_tracer().events() if e["ph"] == "X"]
        rows = [e["args"] for e in evs
                if e["name"] in ("serving.decode", "serving.verify")]
        chunk = [e["args"] for e in evs if e["name"] == "serving.chunk"]
        if not rows:
            continue
        (a,) = rows
        ran = rows_of + (eng.prefill_chunk if chunk
                         else eng._stub_chunk * eng.chunked)
        assert (a["weight_passes"], a["parts"], a["pass_rows"]) == (
            1, 1 + len(chunk), ran)
        real = a["slots"] + a.get("drafted", 0) + sum(
            c["tokens"] for c in chunk)
        assert a["pass_tokens"] == real <= ran
        kinds.add((a["slots"] > 0, bool(chunk), a.get("drafted", 0) > 0))
    # live rows with and (on a cursor engine) without a chunk beside them,
    # a chunk with no live row; a verify window with real drafts
    assert any(live and not chunk for live, chunk, _ in kinds)
    if eng.chunked:
        assert any(live and chunk for live, chunk, _ in kinds)
        assert any(chunk and not live for live, chunk, _ in kinds)
    if eng.spec:
        assert any(drafted for *_, drafted in kinds)


# -- (b) the same spans in a jax.profiler trace ------------------------------

def test_phases_reach_the_profiler_trace(lm, tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(lm, "chunked")
    eng.step()                                   # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serving.")]
    steps = [sp for sp in spans if sp[0] == "serving.step"]
    assert len(steps) == 2
    for name in TICK_PHASES:
        mine = [sp for sp in spans if sp[0] == name]
        assert len(mine) >= 2, name
        for _, s, e in mine:
            assert any(s0 <= s and e <= e0 for _, s0, e0 in steps)


# -- (c) one clock -----------------------------------------------------------

def test_span_ts_request_t_ms_and_perf_counter_are_one_clock():
    clock = obs.clock
    log = obs.get_request_log()
    uid = log.new_uid()
    t_before = time.perf_counter()
    with obs.span("probe"):
        log.event(uid, "submitted")
    t_after = time.perf_counter()
    (sp,) = [e for e in obs.get_tracer().events() if e["name"] == "probe"]
    t_span = clock.span_ts_to_perf_counter(sp["ts"])
    t_event = clock.event_ms_to_perf_counter(log.timeline(uid)[0]["t_ms"])
    # exact, not "within a millisecond": the converted stamps lie between
    # the two perf_counter() readings taken around them
    assert t_before <= t_span <= t_event <= t_after
    # and back, exactly
    assert clock.perf_counter_to_span_ts(t_span) == pytest.approx(
        sp["ts"], abs=1e-3)
    assert clock.perf_counter_to_event_ms(t_event) == pytest.approx(
        log.timeline(uid)[0]["t_ms"], abs=1e-6)
    # a private tracer and a private log share the origin too
    other = obs.SpanTracer(max_events=4, enabled=True)
    with other.span("again"):
        pass
    assert clock.span_ts_to_perf_counter(other.events()[0]["ts"]) \
        == pytest.approx(time.perf_counter(), abs=0.5)
    assert clock.origin_s() == obs.RequestLog(max_requests=1)._t0


# -- (d) the flag silences both sinks ----------------------------------------

class _CountingAnnotation:
    entered = exited = 0

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        type(self).exited += 1


def test_spans_off_records_nothing_and_enters_no_annotation(
        lm, monkeypatch):
    import jax.profiler

    _CountingAnnotation.entered = _CountingAnnotation.exited = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _CountingAnnotation)
    eng = _engine(lm, "wave")
    tracer = obs.get_tracer()
    eng.step()                                    # on: both sinks written
    on = len(tracer.events())
    assert on and _CountingAnnotation.entered == on \
        == _CountingAnnotation.exited
    tracer.clear()
    monkeypatch.setattr(tracer, "enabled", False)
    eng.step()                                    # off: neither
    from paddle_tpu.profiler import RecordEvent
    with RecordEvent("user_scope"):
        pass
    assert tracer.events() == []
    assert _CountingAnnotation.entered == on


@pytest.mark.parametrize("mode", sorted(MODES))
def test_spans_off_silences_the_two_costs_too(lm, monkeypatch, mode):
    """``serving.upload`` and ``serving.account`` go through the one span
    call: with the flag off neither is recorded nor annotated, in any
    step body, and the tick serves what it served."""
    import jax.profiler

    named = []

    class Annotation(_CountingAnnotation):
        def __init__(self, name, **kw):
            named.append(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    served = _engine(lm, mode).drain()
    eng = _engine(lm, mode)
    tracer = obs.get_tracer()
    tracer.clear()
    del named[:]
    eng.step()
    on = [e["name"] for e in tracer.events()]
    assert {"serving.upload", "serving.account"} <= set(on)
    assert sorted(named) == sorted(on)
    tracer.clear()
    del named[:]
    monkeypatch.setattr(tracer, "enabled", False)
    assert eng.drain() == served
    assert tracer.events() == [] and named == []


# -- (e) kernels carry names -------------------------------------------------

_PALLAS_DIR = os.path.join(os.path.dirname(pt.__file__), "ops", "pallas")
_CALL_SITES = [("decode_attention.py", 0), ("flash_attention.py", 0),
               ("flash_attention.py", 1), ("flash_attention.py", 2),
               ("gated_delta.py", 0), ("gated_delta.py", 1),
               ("grouped_matmul.py", 0), ("int8_matmul.py", 0),
               ("rms_norm.py", 0)]


def _pallas_calls(filename):
    with open(os.path.join(_PALLAS_DIR, filename)) as f:
        tree = ast.parse(f.read())
    return [n for n in ast.walk(tree) if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "pallas_call"]


def test_the_call_sites_are_the_nine():
    found = [(os.path.basename(p), i)
             for p in sorted(glob.glob(os.path.join(_PALLAS_DIR, "*.py")))
             for i in range(len(_pallas_calls(os.path.basename(p))))]
    assert found == _CALL_SITES


@pytest.mark.parametrize("filename,index", _CALL_SITES)
def test_every_pallas_call_has_a_name(filename, index):
    call = sorted(_pallas_calls(filename), key=lambda n: n.lineno)[index]
    (name,) = [k.value for k in call.keywords if k.arg == "name"]
    if isinstance(name, ast.Name):
        # handed down as the static ``name=`` of the jitted function round
        # the call (decode_attention._flash_call, gated_delta._step_call /
        # _chunk_call): read it where it is made, the file's index-th
        with open(os.path.join(_PALLAS_DIR, filename)) as f:
            made = sorted(
                (k.value for n in ast.walk(ast.parse(f.read()))
                 if isinstance(n, ast.Call) for k in n.keywords
                 if k.arg == "name" and isinstance(k.value, ast.Call)),
                key=lambda v: v.lineno)
        assert len(made) == len(_pallas_calls(filename))
        name = made[index]
    # built by ops._dispatch.kernel_name, so a program part can lead it
    assert isinstance(name, ast.Call) and "kernel_name" in ast.dump(name.func)
    # (one constant, or one of two by the pool's layout: the latent walk
    # has its own name)
    base = name.args[0]
    bases = [base.body, base.orelse] if isinstance(base, ast.IfExp) else [base]
    assert all(isinstance(b, ast.Constant) and b.value for b in bases)


def test_program_part_leads_the_kernel_name_in_the_traced_program():
    import jax.numpy as jnp

    from paddle_tpu.ops import _dispatch as disp
    from paddle_tpu.ops.pallas.decode_attention import \
        paged_decode_attention_pallas

    assert disp.kernel_name("flash_decode") == "flash_decode"
    q = jnp.zeros((2, 1, 4, 128), jnp.float32)
    pool = jnp.zeros((2, 2, 5, 128, 2 * 128), jnp.float32)
    tables = jnp.zeros((2, 2), jnp.int32)

    def rows(q, pool):
        with disp.program_part("_step_impl", "decode_rows"):
            return paged_decode_attention_pallas(
                q, pool, 1, jnp.zeros((2,), jnp.int32), tables,
                interpret=True)

    text = str(jax.make_jaxpr(rows)(q, pool))
    assert "_step_impl_decode_rows_flash_decode" in text
    assert disp.kernel_name("flash_decode") == "flash_decode"   # restored
