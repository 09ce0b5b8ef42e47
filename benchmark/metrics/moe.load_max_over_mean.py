"""How unevenly the window's pairs fell on the held experts: per expert
layer, the busiest held expert's pairs over the mean held expert's, and of
those the mean over the layers.  1 is an even load; the dropless layer
computes whatever it is given, so skew costs rows, not tokens."""


def read(run):
    counted = (run.get("counters") or {}).get("window")
    if not counted:
        return None
    pairs = counted["pairs"]                    # (expert layers, held)
    mean = pairs.mean(axis=-1)
    if not (mean > 0).all():
        return None
    return float((pairs.max(axis=-1) / mean).mean())
