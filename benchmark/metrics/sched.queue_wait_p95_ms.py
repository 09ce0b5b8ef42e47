"""95th percentile of the time from when a request was due to the end of
the first tick after which it had left the engine's queue for a slot (read
from ``queue_depth``: admission is first in, first out)."""

from benchmark.harness import stats


def read(run):
    return stats.percentile(run["queue_wait_ms"], 95)
