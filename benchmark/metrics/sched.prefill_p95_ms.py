"""95th percentile of the time from the engine's ``admitted`` event to its
``first_token`` event in the request log (prompt ingest: the chunks of one
cursor), over the same requests as ``sched.admit_wait_p95_ms``; the two
together are a request's time to first token from when it was due."""

from benchmark.harness import engine_spans, stats


def read(run):
    waits = engine_spans.request_waits(run)
    if not waits:
        return None
    return stats.percentile([p for _, p in waits if p is not None], 95)
