"""The host's part of a tick, from the tracer's ring over the whole window.

The engine (``paddle_tpu/serving/engine.py``) opens one ``serving.step`` a
tick, tiles it with the six spans of its ``TICK_PHASES``, and inside them
opens ``serving.upload`` (the walk of a program's operand table: the
host->device transfers) and ``serving.account`` (work that only feeds a span
argument, a counter, a gauge, a histogram or the cost model): its
``TICK_COSTS``.  The ring holds every tick of the window, traced or not.

Here every microsecond of a tick goes to the innermost of those nine names
open at it (self time: ``engine_spans.flatten``), so the names' times add up
to the tick.  A tick is a ``serving.step`` that closed inside the window AND
holds a ``serving.dispatch``: one that returned before the device seam is
not a tick.  A prefill wave's ``build_inputs`` / ``upload`` / ``dispatch`` /
``readback`` lie inside ``serving.admit`` and count under their own names.

- ``stalls(run)``: over the window, the sum of (tick - median tick) over the
  ticks longer than the median by more than ``STALL_MS``, and the part of it
  inside those ticks' ``serving.readback`` beyond the median readback.
- ``part_ms(run, *names)``: mean milliseconds a tick inside those names, over
  the ticks that did not stall: one tick of seconds (the first upload after
  the profiler starts is one, in a traced run) would double a mean that is
  there to size the host's steady work, and the stalls have their own sum.
- ``summary(run)``: what a builder puts beside them in ``PERF.md``.

Against a program without ``serving.upload`` (the parent of the PR that
added it) every reader returns None, as ``engine_spans``' do.
"""

from benchmark.harness import engine_spans
from benchmark.harness import stats

STEP = engine_spans.STEP_SPAN
UPLOAD, ACCOUNT = "serving.upload", "serving.account"
DISPATCH, READBACK = "serving.dispatch", "serving.readback"
NAMES = frozenset(engine_spans.PARTS) | {UPLOAD, ACCOUNT}
STALL_MS = 250.0        # PERF.md section 6: a stall is a tick this far over


def split_ticks(spans, w0, w1):
    """``spans``: (name, start, end) of one thread, any unit.  Per
    ``serving.step`` that ends in [w0, w1] and holds a ``serving.dispatch``,
    {name: self time} over ``NAMES``; the values of one tick sum to it."""
    segments = engine_spans.flatten(spans, NAMES)
    steps = sorted((s, e) for n, s, e in spans if n == STEP and w0 <= e <= w1)
    ticks, k = [], 0
    for s, e in steps:
        while k < len(segments) and segments[k][1] <= s:
            k += 1
        parts = {}
        while k < len(segments) and segments[k][0] < e:
            a, b, name = segments[k]
            parts[name] = parts.get(name, 0.0) + (b - a)
            k += 1
        if DISPATCH in parts:
            ticks.append(parts)
    return ticks


def ticks(run):
    """The window's ticks as {name: self time in ms}, or None where the
    program has no ``serving.upload``, no shared clock, or a ring that no
    longer reaches back to the window's opening."""
    if "tick_host" not in run:
        run["tick_host"] = None
        if engine_spans.ring_spans(run, UPLOAD):    # None, or none: no split
            obs = engine_spans._observability()
            w0, w1 = (obs.clock.perf_counter_to_span_ts(t)
                      for t in run["window"])
            by_thread = {}
            for ev in obs.get_tracer().events():
                if ev.get("ph") == "X" and ev["name"] in NAMES:
                    by_thread.setdefault(ev["tid"], []).append(
                        (ev["name"], ev["ts"], ev["ts"] + ev["dur"]))
            run["tick_host"] = [
                {name: us * 1e-3 for name, us in tick.items()}
                for spans in by_thread.values()
                for tick in split_ticks(spans, w0, w1)] or None
    return run["tick_host"]


def _over(found):
    """Per tick, how far it runs over the median tick."""
    total = [sum(t.values()) for t in found]
    mid = stats.median(total)
    return [d - mid for d in total]


def calm(found):
    """The ticks that did not stall."""
    return [t for t, d in zip(found, _over(found)) if d <= STALL_MS]


def part_ms(run, *names):
    """Mean milliseconds a tick that did not stall spent inside ``names``,
    or None."""
    found = ticks(run)
    if found is None:
        return None
    found = calm(found)
    return sum(t.get(n, 0.0) for t in found for n in names) / len(found)


def stall_sums(found):
    """(stall, of it readback) in the unit of ``found``'s self times."""
    fetch = [t.get(READBACK, 0.0) for t in found]
    mid_fetch = stats.median(fetch)
    over = [(d, f - mid_fetch) for d, f in zip(_over(found), fetch)
            if d > STALL_MS]
    return (float(sum(d for d, _ in over)),
            float(sum(min(d, max(f, 0.0)) for d, f in over)))


def stalls(run):
    found = ticks(run)
    return None if found is None else stall_sums(found)


def summary(run):
    """Beside the seven metrics: the ticks read and how many stalled, the
    ring's mean tick over all of them and over the calm ones (which the
    parts sum to), the harness's over the same, the mean readback, a
    tick's uploads (operands, bytes) and ring events, the ring's
    evictions."""
    found = ticks(run)
    if found is None:
        return None
    obs = engine_spans._observability()
    tracer = obs.get_tracer()
    events = tracer.events()
    w0, w1 = (obs.clock.perf_counter_to_span_ts(t) for t in run["window"])
    uploads = engine_spans.ring_spans(run, UPLOAD)
    n, quiet = len(found), calm(found)
    outer = [(b - a) * 1e3 for a, b, *_ in run["ticks"]]
    outer_calm = [d for d in outer if d - stats.median(outer) <= STALL_MS]
    return {
        "ticks": n, "stalled_ticks": n - len(quiet),
        "harness_ticks": len(outer),
        "ring_tick_ms": sum(sum(t.values()) for t in quiet) / len(quiet),
        "harness_tick_ms": sum(outer_calm) / len(outer_calm),
        "ring_tick_all_ms": sum(sum(t.values()) for t in found) / n,
        "harness_tick_all_ms": sum(outer) / len(outer),
        "readback_ms": part_ms(run, READBACK),
        "parts_ms": {name: part_ms(run, name) for name in sorted(NAMES)},
        "uploads_a_tick": len(uploads) / n,
        "operands_a_tick": sum(a["operands"] for _, a in uploads) / n,
        "bytes_a_tick": sum(a["bytes"] for _, a in uploads) / n,
        "events_a_tick": sum(w0 <= ev["ts"] <= w1 for ev in events) / n,
        "ring_events": len(events), "ring_dropped": tracer.dropped}
