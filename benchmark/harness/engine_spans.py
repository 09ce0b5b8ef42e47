"""The serving tick read from inside: what the engine's own spans, its
tracer ring and its request log say about a run.

The program (``paddle_tpu/serving/engine.py``) opens, under one
``serving.step`` span a tick, the child spans named in its ``TICK_PHASES``
(``serving.admit``, ``.grow``, ``.build_inputs``, ``.dispatch``,
``.readback``, ``.advance``).  Each span is written twice: as a
``jax.profiler.TraceAnnotation`` into the ``.xplane.pb`` of a traced run,
where the device's events are, and into the tracer's ring
(``paddle_tpu.observability.get_tracer().events()``), which holds the whole
window.  The ring's ``ts``, the request log's ``t_ms`` and the harness's
``time.perf_counter()`` stamps share one origin
(``paddle_tpu.observability.clock``).

A metric file calls one function here and returns its value:

- ``idle_split(run)``: the first chip's idle time inside ``bench.window``
  of the traced part, by the innermost tick phase the host was in, as four
  parts that sum to the idle share ``trace_reduce.reduce_profile`` gives.
  The device's stamps are first moved onto the host's clock by the skew the
  trace itself bounds (``skew_bounds``).
- ``ring_spans(run, name)``: the ring's spans of one name that lie in the
  45 s window, clipped to it, with their args.
- ``request_waits(run)``: per judged request, due -> ``admitted`` and
  ``admitted`` -> ``first_token`` from the request log.

Against a program that has none of this (the parent of the PR that added
it) every function returns None and the result line leaves the metric out.
"""

import bisect
import functools
import glob
import os
import re

from benchmark.harness import manifest as mf
from benchmark.harness import trace_reduce as tr

STEP_SPAN = "serving.step"
OUTSIDE = "outside_step"
# which part of the idle share a span's idle time counts under; time inside
# serving.step but in no phase (under 2 % of a tick) is the scheduler's own
PARTS = {"serving.admit": "schedule", "serving.grow": "schedule",
         "serving.advance": "schedule", STEP_SPAN: "schedule",
         "serving.build_inputs": "dispatch", "serving.dispatch": "dispatch",
         "serving.readback": "readback"}
ENGINE_PROGRAM = re.compile(r"^jit_.*(step_impl|prefill_impl)")


def _observability():
    """``paddle_tpu.observability`` where it has the one clock, or None."""
    from paddle_tpu import observability as obs
    return obs if hasattr(obs, "clock") else None


def newest_xplane():
    """The ``.xplane.pb`` the run in this process just wrote (``run.py``
    traces into ``benchmark/_out/trace/<cell>``), or None."""
    files = glob.glob(os.path.join(mf.BENCH, "_out", "trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def flatten(spans, keep):
    """Nested (name, start, end) spans of one thread as a sorted list of
    disjoint (start, end, name) segments, each named by the innermost span
    of ``keep`` open there; spans of other names are transparent."""
    out, stack, at = [], [], 0

    def advance(to):            # [at, to) lies under the innermost open span
        nonlocal at
        if stack and to > at:
            out.append((at, to, stack[-1][0]))
        at = max(at, to) if stack else to

    for name, s, e in sorted((sp for sp in spans if sp[0] in keep),
                             key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        stack.append((name, e))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return out


def attribute(gaps, segments):
    """Seconds of ``gaps`` (disjoint (start, end) in ns) by the name of the
    segment each part falls in; time in no segment goes to OUTSIDE."""
    starts = [s for s, _, _ in segments]
    total = {}
    for g0, g1 in gaps:
        at = g0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while at < g1 and i < len(segments):
            s, e, name = segments[i]
            if e <= at:
                i += 1
                continue
            if s > at:
                upto = min(s, g1)
                total[OUTSIDE] = total.get(OUTSIDE, 0.0) + (upto - at)
                at = upto
                continue
            upto = min(e, g1)
            total[name] = total.get(name, 0.0) + (upto - at)
            at = upto
        if at < g1:
            total[OUTSIDE] = total.get(OUTSIDE, 0.0) + (g1 - at)
    return {k: v * 1e-9 for k, v in total.items()}


def skew_bounds(programs, ticks):
    """How far the device's stamps run ahead of the host's, bounded from
    the trace itself.  ``programs``: (start, end) of the engine's step and
    prefill programs on the device's clock; ``ticks``: (dispatch opens,
    readback closes) of the host spans around each launch.  No program
    starts before its ``serving.dispatch`` opens and none ends after its
    ``serving.readback`` closes, so over the pairs

        max(program end - readback close) <= skew
                                          <= min(program start - dispatch open)

    Each program is paired with the tick it overlaps most (the skew is a
    millisecond, a tick tens of them).  Returns (low, high, pairs) in ns,
    or None without a pair."""
    if not programs or not ticks:
        return None
    ticks = sorted(ticks)
    opens = [d for d, _ in ticks]
    low = high = None
    pairs = 0
    for s, e in programs:
        k = bisect.bisect_right(opens, s)
        near = ticks[max(0, k - 2):k + 1]
        d, r = max(near, key=lambda t: (min(e, t[1]) - max(s, t[0]),
                                        -abs(t[0] - s)))
        low = e - r if low is None else max(low, e - r)
        high = s - d if high is None else min(high, s - d)
        pairs += 1
    return low, high, pairs


def split_profile(profile):
    """The idle split of one loaded trace: {"window_s", "idle_s": {part:
    seconds}, "skew_ns": [low, high], "skew_applied_ns", "pairs"}, or None
    where the trace holds no ``serving.step`` span."""
    spans = tr.host_spans(profile, prefix="serving.")
    if not any(n == STEP_SPAN for n, _, _ in spans):
        return None
    window = [(s, e) for n, s, e in tr.host_spans(profile)
              if n == tr.WINDOW_SPAN]
    plane = tr.device_planes(profile)[0]
    ops = [(s, e) for _, s, e in tr._events(plane, tr.OPS_LINE)]
    lo, hi = window[0] if window else (min(s for s, _ in ops),
                                       max(e for _, e in ops))
    busy = tr.union(tr.clip(ops, lo, hi))
    gaps = tr.gaps(busy, lo, hi)

    opens = sorted(s for n, s, _ in spans if n == "serving.dispatch")
    closes = sorted(e for n, _, e in spans if n == "serving.readback")
    ticks = []
    for d in opens:             # the readback that follows each dispatch
        k = bisect.bisect_left(closes, d)
        if k < len(closes):
            ticks.append((d, closes[k]))
    programs = [(s, e) for n, s, e in tr._events(plane, tr.MODULES_LINE)
                if ENGINE_PROGRAM.match(n) and e > lo and s < hi]
    bounds = skew_bounds(programs, ticks)
    skew = 0 if bounds is None else (bounds[0] + bounds[1]) // 2

    by_name = attribute([(s - skew, e - skew) for s, e in gaps],
                        flatten(spans, PARTS))
    idle = {"schedule": 0.0, "dispatch": 0.0, "readback": 0.0,
            OUTSIDE: 0.0}
    for name, sec in by_name.items():
        idle[PARTS.get(name, OUTSIDE)] += sec
    return {"window_s": (hi - lo) * 1e-9, "idle_s": idle,
            "skew_ns": list(bounds[:2]) if bounds else None,
            "skew_applied_ns": skew, "pairs": bounds[2] if bounds else 0}


@functools.lru_cache(maxsize=2)
def split_file(path):
    return split_profile(tr.load(path))


def idle_split(run):
    """{part: % of the traced window} for this run's trace, or None."""
    path = newest_xplane()
    split = split_file(path) if path else None
    if split is None:
        return None
    return {part: 100.0 * sec / split["window_s"]
            for part, sec in split["idle_s"].items()}


def ring_spans(run, name):
    """The tracer ring's complete spans called ``name`` that overlap the
    run's window, as (seconds inside the window, args), or None where the
    program has no shared clock or the ring no longer reaches back to the
    window's opening."""
    obs = _observability()
    if obs is None:
        return None
    tracer = obs.get_tracer()
    events = tracer.events()
    w0, w1 = (obs.clock.perf_counter_to_span_ts(t) for t in run["window"])
    if not events or (tracer.dropped and events[0]["ts"] > w0):
        return None
    out = []
    for ev in events:
        if ev["name"] != name or ev.get("ph") != "X":
            continue
        s, e = max(ev["ts"], w0), min(ev["ts"] + ev["dur"], w1)
        if e > s:
            out.append(((e - s) * 1e-6, ev.get("args", {})))
    return out


def request_waits(run):
    """Per judged request that reached a slot: (ms from when it was DUE to
    the engine's ``admitted`` event, ms from ``admitted`` to
    ``first_token`` or None).  The request log has no harness id: its
    records are joined to the harness's ``order`` by order of successful
    submission (warm-up requests come first, refused ones carry a
    ``rejected`` event).  Due -> ``submitted`` is the generator's lateness,
    which the harness's own queue wait holds too."""
    obs = _observability()
    if obs is None:
        return None
    order = run["order"]
    records = [rec for rec in obs.get_request_log().records().values()
               if not any(ev["name"] == "rejected" for ev in rec)]
    if len(records) < len(order):
        return None
    at = {id(rec): i for i, rec in enumerate(order)}
    records = records[len(records) - len(order):]
    out = []
    for rec in run["judged"]:
        if rec.slot is None or id(rec) not in at:
            continue
        t = {}
        for ev in records[at[id(rec)]]:
            t.setdefault(ev["name"], ev["t_ms"])
        if "admitted" not in t:
            continue
        due_ms = obs.clock.perf_counter_to_event_ms(rec.due + run["t_zero"])
        first = t.get("first_token")
        out.append((t["admitted"] - due_ms,
                    None if first is None else first - t["admitted"]))
    return out
