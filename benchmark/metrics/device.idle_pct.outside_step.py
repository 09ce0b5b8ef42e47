"""Share of the traced window in which the device was idle under no
``serving.step`` span: the time belongs to the caller of ``step()``, here the
harness loop.  One of four parts of ``device.idle_pct.serve``
(``engine_spans.idle_split``)."""

from benchmark.harness import engine_spans


def read(run):
    split = engine_spans.idle_split(run)
    return None if split is None else split["outside_step"]
