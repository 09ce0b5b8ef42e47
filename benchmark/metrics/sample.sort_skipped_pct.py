"""Share of the window's decode ticks whose sampling epilogue ran no
full-vocabulary sort: the ``serving.decode`` spans in the tracer's ring whose
``sample_path`` arg (the engine's name for the way the tick's
``sample_tokens`` calls went: ``greedy`` | ``categorical`` | ``truncated``,
the heavier of a mixed tick's two) is not ``truncated``, over all of them.
None against a program whose spans carry no such arg."""

from benchmark.harness import engine_spans


def read(run):
    ticks = engine_spans.ring_spans(run, "serving.decode")
    if not ticks or any("sample_path" not in a for _, a in ticks):
        return None
    skipped = sum(a["sample_path"] != "truncated" for _, a in ticks)
    return 100.0 * skipped / len(ticks)
