"""Share of the step program's token rows that are real tokens: over the
window's ``serving.decode`` / ``serving.verify`` spans in the tracer's ring,
the sum of their ``pass_tokens`` arg (live rows' tokens plus the prompt
chunk's real ones) over the sum of their ``pass_rows`` arg (the padded rows
the one pass of the weights runs over: ``num_slots·(k+1) + prefill_chunk``).
The rest is idle slots and a chunk's empty tail — or a whole empty chunk on
a chunk-free tick — which a compute-bound pass pays for in full: it prices
skipping an empty chunk and compacting idle rows.  None against a program
whose spans carry no such args."""

from benchmark.harness import manifest as mf


def read(run):
    ticks = mf.load_metric("step.weight_passes").ticks_of(run)
    if ticks is None:
        return None
    rows = sum(a["pass_rows"] for _, a in ticks)
    if not rows:
        return None
    return 100.0 * sum(a["pass_tokens"] for _, a in ticks) / rows
