"""Share of the window's ticks that carried a prompt chunk: the
``serving.chunk`` spans in the tracer's ring (one a tick whose chunk part
held real prompt tokens) over the ``serving.decode`` spans.  The token gap
is bimodal in a chunked cell — plain ticks and chunk ticks — so its 95th
percentile sits on one mode or the other by which side of 5 % this share
lies.  None where the ring has no decode spans in the window."""

from benchmark.harness import engine_spans


def read(run):
    ticks = engine_spans.ring_spans(run, "serving.decode")
    if not ticks:
        return None
    chunks = engine_spans.ring_spans(run, "serving.chunk") or []
    return 100.0 * len(chunks) / len(ticks)
