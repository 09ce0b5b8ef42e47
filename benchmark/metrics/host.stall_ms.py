"""Milliseconds of the window lost to stalled ticks: the sum of (tick -
median tick) over the window's dispatched ticks in the tracer's ring that
are longer than the median by more than 250 ms (``tick_host.STALL_MS``); 0
where there is none.  None against a program without ``serving.upload``."""

from benchmark.harness import tick_host


def read(run):
    found = tick_host.stalls(run)
    return None if found is None else found[0]
