"""Share of the live KV that no layer can read any more: (position, window
layer) pairs behind their layer's sliding window
(``kv_cache.window_dead_positions``, counted by the program each tick),
over every (live position, layer) pair, as the mean over the window's
ticks.  It is what an allocator that frees blocks behind the window, with
tables per layer kind, would give back to the pool (ROADMAP R5)."""


def read(run):
    counted = (run.get("counters") or {}).get("window")
    if not counted:
        return None
    layers = run["config"]["num_hidden_layers"]
    shares = [dead / (depth * layers)
              for (_, _, _, depth), dead in counted["dead_by_tick"]
              if depth and dead is not None]
    return 100.0 * sum(shares) / len(shares) if shares else None
