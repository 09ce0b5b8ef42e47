"""Mean milliseconds a tick of the host's own time in the scheduler: the
self time of ``serving.admit`` + ``.grow`` + ``.advance`` and of
``serving.step`` outside every phase (queue scan, pool reservation, table
growth, the per-slot advance, retirement), over the window's dispatched
ticks in the tracer's ring that did not stall (``tick_host``).  None against
a program without ``serving.upload``."""

from benchmark.harness import tick_host


def read(run):
    return tick_host.part_ms(run, "serving.admit", "serving.grow",
                             "serving.advance", "serving.step")
