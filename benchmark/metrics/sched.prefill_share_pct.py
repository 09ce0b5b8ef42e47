"""Share of the window's wall time spent inside ``serving.prefill`` spans
(upload, dispatch and token fetch of an admission wave), during which no
request decodes; from the tracer's ring, over the whole window."""

from benchmark.harness import engine_spans


def read(run):
    waves = engine_spans.ring_spans(run, "serving.prefill")
    if waves is None:
        return None
    return 100.0 * sum(sec for sec, _ in waves) / run["seconds"]
