"""Share of the cache's reserved positions that held a live token, as the
mean over the window's ticks: prompt plus generated tokens of every request
that has produced its first token (the harness's own count from
``result()``), over the paged pool's blocks or every slot's ``max_length``.
``memory_peak_bytes`` counts the reservation; this says how much of it the
traffic filled."""


def read(run):
    live = [depth for _, _, _, depth in run["ticks"]]
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / run["cache"]["positions_reserved"]
