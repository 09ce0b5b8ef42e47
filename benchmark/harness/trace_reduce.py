"""From a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to numbers: the device's busy union, its idle
gaps by what the host was doing, and time by operation and by program name.

What the planes look like on a TPU v5e host (looked at by hand, PR 23):
one plane ``/device:TPU:<n>`` a chip, with the lines ``XLA Ops`` (one event
per executed HLO operation, named by the whole text of its HLO instruction;
a Pallas kernel is a ``custom-call`` to ``tpu_custom_call`` whose
instruction carries the name of the JITTED function, not of the kernel: see
``op_key``) and
``XLA Modules`` (one event per executed program, named
``jit_<function>(<fingerprint>)``); and one plane ``/host:CPU`` with a line
per thread; the ``TraceAnnotation`` spans of the main thread are on its line
``python3``.  Device and host events are on one time axis, but in the
recorded test trace the device's stamps run about 1.1 ms ahead of the host
spans that caused them: good enough to name the span a 5 ms gap falls in,
not to order events a millisecond apart.

Run ``python -m benchmark.harness.trace_reduce <file.xplane.pb>`` to print
what a trace holds before writing a reader against it.
"""

import glob
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(plane, line_name):
    out = []
    for line in plane.lines:
        if line.name == line_name:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events)
    return out


def device_planes(profile):
    return [p for p in profile.planes if p.name.startswith("/device:TPU:")]


def host_spans(profile, prefix=SPAN_PREFIX):
    """(name, start_ns, end_ns) of every host span whose name starts with
    ``prefix``, over all host threads."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith(prefix))
    return out


def union(intervals):
    """Merged, sorted list of (start, end) covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi):
    """The complement of the merged ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


_HLO = re.compile(r"^%?(?P<instr>[^ ]+) = (?P<shape>\(.*?\)|[^ ]+) "
                  r"(?P<op>[\w\-]+)\(")


def op_key(name):
    """A short, stable key of one device operation.  The ``XLA Ops`` line
    names an event by its whole HLO instruction; the key keeps the opcode,
    the instruction's name without its number where that says more than the
    opcode (a Pallas kernel is ``%<jitted function>.<n> = ... custom-call``
    with ``custom_call_target="tpu_custom_call"``: opcode ``pallas``), and
    the result's shape without layouts:

        pallas:_step_impl:bf16[24,8,8,128]
        fusion:(bf16[1,1,24,4096,8,128],bf16[1,1,24,4096,8,128])
    """
    m = _HLO.match(name)
    if not m:
        return re.sub(r"[.:]\d+$", "", name.lstrip("%"))[:120]
    op = m["op"]
    if op == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', name)
        op = {"tpu_custom_call": "pallas"}.get(
            target[1] if target else "", "custom-call:" + (
                target[1] if target else "?"))
    instr = re.sub(r"[.\-]\d+$", "", m["instr"])
    shape = re.sub(r"\{[^}]*\}", "", m["shape"]).replace(" ", "")
    mid = "" if instr.replace("_", "-") in op or instr in (
        "fusion", "copy", "bitcast") else instr + ":"
    return f"{op}:{mid}{shape}"[:120]


def _innermost(spans, t):
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside-harness-spans"


def reduce_profile(profile, top=10):
    """The reduced trace:

    window_s   seconds of the ``bench.window`` host span (or, without one,
               from the first to the last device operation)
    busy_s     seconds in which an operation ran, mean over the chips
    ops        {key: [seconds, count]} summed over the chips
    modules    {name: [durations in s]} of the first chip
    idle_gaps  [[host span name, idle seconds]] of the first chip, summed
               by the innermost harness span open at the gap's middle
    device_ops [[key, seconds]] the ``top`` operations by time
    """
    planes = device_planes(profile)
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane: "
                         f"{[p.name for p in profile.planes]}")
    spans = host_spans(profile)
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    per_device = []
    for plane in planes:
        ops = _events(plane, OPS_LINE)
        if window:
            lo, hi = window[0]
        else:
            lo = min(s for _, s, _ in ops)
            hi = max(e for _, _, e in ops)
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        by_op = {}
        for name, s, e in ops:
            if e <= lo or s >= hi:
                continue
            row = by_op.setdefault(op_key(name), [0.0, 0])
            row[0] += (min(e, hi) - max(s, lo)) * 1e-9
            row[1] += 1
        modules = {}
        for name, s, e in _events(plane, MODULES_LINE):
            if s >= lo and e <= hi:
                modules.setdefault(re.sub(r"\(\d+\)$", "", name),
                                   []).append((e - s) * 1e-9)
        per_device.append({"busy_s": sum(e - s for s, e in busy) * 1e-9,
                           "ops": by_op, "modules": modules,
                           "busy": busy, "lo": lo, "hi": hi})
    first = per_device[0]
    idle = {}
    for s, e in gaps(first["busy"], first["lo"], first["hi"]):
        name = _innermost(spans, (s + e) // 2)
        idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9
    ops_all = {}
    for d in per_device:
        for k, (sec, n) in d["ops"].items():
            row = ops_all.setdefault(k, [0.0, 0])
            row[0] += sec
            row[1] += n
    rank = sorted(ops_all.items(), key=lambda kv: -kv[1][0])
    return {
        "window_s": (first["hi"] - first["lo"]) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "ops": ops_all, "modules": first["modules"],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
        "device_ops": [[k, v[0]] for k, v in rank[:top]],
    }


def reduce_file(path, top=10):
    return reduce_profile(load(path), top=top)


def describe(path, top=25):
    """Print planes, lines, and the heaviest event names of each line."""
    for plane in load(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            tot = {}
            n = 0
            for e in line.events:
                row = tot.setdefault(e.name, [0, 0])
                row[0] += e.duration_ns
                row[1] += 1
                n += 1
            print(f"  line {line.name!r}: {n} events, {len(tot)} names")
            for name, (ns, k) in sorted(tot.items(),
                                        key=lambda kv: -kv[1][0])[:top]:
                print(f"    {ns * 1e-6:12.3f} ms  x{k:<6d} {name[:150]}")


if __name__ == "__main__":
    describe(sys.argv[1])
