"""Share of the traced part of the window in which no operation ran on the
device: 1 - busy union / window."""


def read(run):
    tr = run["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
