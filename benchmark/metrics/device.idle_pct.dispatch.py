"""Share of the traced window in which the device was idle while the host
built and launched the next program: the innermost engine span open was
``serving.build_inputs`` (the uploads) or ``serving.dispatch`` (the call of
the jitted program).  One of four parts of ``device.idle_pct.serve``
(``engine_spans.idle_split``)."""

from benchmark.harness import engine_spans


def read(run):
    split = engine_spans.idle_split(run)
    return None if split is None else split["dispatch"]
