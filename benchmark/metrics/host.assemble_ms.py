"""Mean milliseconds a tick of ``serving.build_inputs``' self time: what
is left of it outside ``serving.upload`` and ``serving.account`` (the chunk
operand's assembly, a wave's padding, the mirrors), over the window's
dispatched ticks in the tracer's ring that did not stall (``tick_host``).
None against a program without ``serving.upload``."""

from benchmark.harness import tick_host


def read(run):
    return tick_host.part_ms(run, "serving.build_inputs")
