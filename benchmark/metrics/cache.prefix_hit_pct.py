"""Share of the admitted prompt tokens that the prefix trie served: over the
requests the engine admitted inside the window (the request log's
``admitted`` event, whose ``prefix_hit_tokens`` is what ``BlockManager.admit``
adopted), adopted tokens over prompt tokens.  The runner joins the log to its
own records (``run["admissions"]``: admission time on the harness's clock,
prompt tokens, adopted tokens).  None where the runner found no such
events."""


def read(run):
    rows = run.get("admissions")
    if not rows:
        return None
    w0, w1 = run["window"]
    inside = [(p, h) for t, p, h in rows if w0 <= t <= w1]
    if not inside:
        return None
    return 100.0 * sum(h for _, h in inside) / sum(p for p, _ in inside)
