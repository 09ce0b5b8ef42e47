"""Share of the fixed-size per-slot state's rows that a resident request
held (``kv_cache.state_rows_live`` over ``kv_cache.state_rows``, counted by
the program each tick: a row is held while its request decodes or is
mid-prompt under the cursor; one row a slot and the null row are
allocated), as the mean over the window's ticks.  Beside
``cache.live_kv_pct.saturated`` it says which of the two stores binds
admission: the paged pool, or the slots.  None against a program that
counts no such rows."""


def read(run):
    rows = (run.get("counters") or {}).get("state")
    if not rows:
        return None
    return 100.0 * sum(live / total for live, total in rows) / len(rows)
