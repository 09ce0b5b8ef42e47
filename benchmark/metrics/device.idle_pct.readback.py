"""Share of the traced window in which the device was idle while the host
waited for tokens inside ``serving.readback``: the program not yet started,
between two of its operations, or already done.  One of four parts of
``device.idle_pct.serve`` (``engine_spans.idle_split``)."""

from benchmark.harness import engine_spans


def read(run):
    split = engine_spans.idle_split(run)
    return None if split is None else split["readback"]
