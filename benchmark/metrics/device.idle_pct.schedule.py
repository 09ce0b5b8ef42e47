"""Share of the traced window in which the device was idle while the host
was scheduling: the innermost engine span open was ``serving.admit``,
``serving.grow`` or ``serving.advance`` (or ``serving.step`` itself, between
phases).  One of four parts of ``device.idle_pct.serve``
(``engine_spans.idle_split``)."""

from benchmark.harness import engine_spans


def read(run):
    split = engine_spans.idle_split(run)
    return None if split is None else split["schedule"]
