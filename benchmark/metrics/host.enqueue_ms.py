"""Mean milliseconds a tick inside ``serving.dispatch``: the call of the
jitted step (and a wave's prefill) program alone, which flattens
``leaves=`` leaves of params and cache and returns when the program is
enqueued, over the window's dispatched ticks in the tracer's ring that did
not stall (``tick_host``).  None against a program without
``serving.upload``."""

from benchmark.harness import tick_host


def read(run):
    return tick_host.part_ms(run, "serving.dispatch")
