"""95th percentile of the time from when a request was DUE to the engine's
own ``admitted`` event in the request log, over the requests
``sched.queue_wait_p95_ms`` judges.  The inside twin of that metric: exact
stamps on the one clock where the outside one waits for ``queue_depth`` to
fall at the end of a tick (``engine_spans.request_waits``)."""

from benchmark.harness import engine_spans, stats


def read(run):
    waits = engine_spans.request_waits(run)
    return stats.percentile([w for w, _ in waits], 95) if waits else None
