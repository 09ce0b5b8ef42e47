"""How often the decode rows' walks read a block: over the window's
``serving.decode`` spans in the tracer's ring, the blocks the rows' walks
read (``rows_blocks``, a layer) over the DIFFERENT physical blocks among
them (``rows_distinct``).  1 where no two rows share a block; about the
number of rows on a document where a long shared document is most of every
row's depth.  It is what a walk that reads a shared prefix once for all its
rows would save.  None against a program whose spans carry no such args."""

from benchmark.harness import engine_spans


def read(run):
    ticks = engine_spans.ring_spans(run, "serving.decode")
    if not ticks or any("rows_distinct" not in a for _, a in ticks):
        return None
    distinct = sum(a["rows_distinct"] for _, a in ticks)
    if not distinct:
        return None
    return sum(a["rows_blocks"] for _, a in ticks) / distinct
