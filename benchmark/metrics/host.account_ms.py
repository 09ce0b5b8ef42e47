"""Mean milliseconds a tick inside ``serving.account``: host work that
exists only to feed a span argument, a counter, a gauge, a histogram or the
cost model, so what the program's own measurement costs with the profiler
off, over the window's dispatched ticks in the tracer's ring that did not
stall (``tick_host``).  None against a program without ``serving.upload``."""

from benchmark.harness import tick_host


def read(run):
    return tick_host.part_ms(run, "serving.account")
