"""Mean busy slots over the window's ticks (``last_occupancy``, the
engine's own gauge, read after every tick)."""


def read(run):
    occ = [o for _, _, o, _ in run["ticks"]]
    return sum(occ) / len(occ) if occ else None
