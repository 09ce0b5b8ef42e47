"""Of ``host.stall_ms``, the milliseconds inside the stalled ticks'
``serving.readback`` beyond the window's median readback: a stall the host
sat out waiting for the device's tokens; 0 where there is none.  None
against a program without ``serving.upload``."""

from benchmark.harness import tick_host


def read(run):
    found = tick_host.stalls(run)
    return None if found is None else found[1]
