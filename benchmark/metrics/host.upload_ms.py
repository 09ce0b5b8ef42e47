"""Mean milliseconds a tick inside ``serving.upload``: the walk of the
step (and a wave's prefill) program's operand table, one host->device
transfer an operand, over the window's dispatched ticks in the tracer's
ring that did not stall (``tick_host``); the span's ``operands=`` and
``bytes=`` say what was moved.  None against a program without
``serving.upload``."""

from benchmark.harness import tick_host


def read(run):
    return tick_host.part_ms(run, "serving.upload")
