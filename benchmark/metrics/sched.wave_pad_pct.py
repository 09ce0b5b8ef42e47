"""Share of the prefill waves' token positions that were padding, over the
window's waves: 1 - sum(tokens) / sum(padded_rows * bucket), from the args
of the ``serving.prefill`` spans in the tracer's ring (``tokens``: real
prompt tokens computed; ``padded_rows``: rows the program ran,
``prefill_batch``; ``bucket``: positions a row)."""

from benchmark.harness import engine_spans


def read(run):
    waves = engine_spans.ring_spans(run, "serving.prefill")
    if not waves or any("padded_rows" not in a for _, a in waves):
        return None
    real = sum(a["tokens"] for _, a in waves)
    return 100.0 * (1.0 - real / sum(a["padded_rows"] * a["bucket"]
                                     for _, a in waves))
