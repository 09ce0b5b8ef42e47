"""How many times a tick's step program streams the model's token-wise
weights (norms, projections, FFNs, held experts, the head): the mean, over
the window's ``serving.decode`` / ``serving.verify`` spans in the tracer's
ring, of their ``weight_passes`` arg, which the engine states from how its
step program is composed (one ``decode_parts`` call over the decode rows
and the prompt chunk together: 1; a layout left on a call a part would say
2).  None against a program whose spans carry no such arg."""

from benchmark.harness import engine_spans


def ticks_of(run):
    """The window's rows spans of either kind, or None where there is no
    ring to read or a span lacks the pass args."""
    found = [engine_spans.ring_spans(run, name)
             for name in ("serving.decode", "serving.verify")]
    ticks = [t for spans in found if spans for t in spans]
    if not ticks or any("weight_passes" not in a for _, a in ticks):
        return None
    return ticks


def read(run):
    ticks = ticks_of(run)
    if ticks is None:
        return None
    return sum(a["weight_passes"] for _, a in ticks) / len(ticks)
