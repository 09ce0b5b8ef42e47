"""Share of the flash-decode kernel's block walk that is live blocks: over
the window's ``serving.decode`` spans in the tracer's ring, the sum of their
``kv_blocks`` arg (the KV blocks the tick's rows and prompt chunk need, the
window applied per layer kind, summed over layers) over the sum of their
``kv_walk`` arg (the block slots the kernel walks for them: whole copy
groups), both counted on the host by the kernel's own bounds
(``ops.pallas.decode_attention.walk_counts``).  The rest is the padding of
each walk's last group, which is neither copied nor needed; it is what the
size of the group costs.  None against a program whose spans carry no such
args."""

from benchmark.harness import engine_spans


def read(run):
    ticks = engine_spans.ring_spans(run, "serving.decode")
    if not ticks or any("kv_walk" not in a for _, a in ticks):
        return None
    walked = sum(a["kv_walk"] for _, a in ticks)
    if not walked:
        return None
    return 100.0 * sum(a["kv_blocks"] for _, a in ticks) / walked
