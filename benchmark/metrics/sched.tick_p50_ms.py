"""Median wall time of one ``ServingEngine.step()`` in the window: host
scheduling, dispatch, the device step and the token readback together."""

from benchmark.harness import stats


def read(run):
    return stats.median([(b - a) * 1e3 for a, b, _, _ in run["ticks"]])
