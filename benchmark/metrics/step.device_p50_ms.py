"""Median device time of one execution of the engine's step program in the
traced part of the window, by the name the trace's ``XLA Modules`` line
prints for it: ``jit_<impl>`` of the step implementations in
``serving/engine.py`` (prefill programs are left out)."""

import re

from benchmark.harness import stats

STEP_PROGRAM = re.compile(r"^jit_.*step_impl")


def read(run):
    times = [t * 1e3 for name, ts in run["trace"]["modules"].items()
             if STEP_PROGRAM.match(name) for t in ts]
    return stats.median(times)
