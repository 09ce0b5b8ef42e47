"""The one clock origin of the observability layer.

Every host-side stamp in this package is ``time.perf_counter`` read
against ONE origin taken when the package is first imported: a span's
``ts`` (µs since the origin, :mod:`.tracing`), a request event's ``t_ms``
(ms since the origin, :mod:`.request_log`) and a caller's own
``time.perf_counter()`` stamp (seconds; the serving engine's
``t_submit``/``t_admit``, a benchmark's window) convert into each other
exactly — no per-object origin, nothing to estimate.  Exported
Chrome/Perfetto files stay relative to the origin, so their timestamps
start near the process's start, as before.
"""

from __future__ import annotations

import time

__all__ = ["ORIGIN_NS", "origin_s", "span_ts_to_perf_counter",
           "event_ms_to_perf_counter", "perf_counter_to_span_ts",
           "perf_counter_to_event_ms"]

#: ``time.perf_counter_ns()`` at the origin
ORIGIN_NS = time.perf_counter_ns()


def origin_s() -> float:
    """The origin as a ``time.perf_counter()`` reading (seconds)."""
    return ORIGIN_NS / 1e9


def span_ts_to_perf_counter(ts_us: float) -> float:
    """A span's ``ts`` (µs since the origin) as ``perf_counter`` seconds."""
    return (ORIGIN_NS + ts_us * 1e3) / 1e9


def event_ms_to_perf_counter(t_ms: float) -> float:
    """A request event's ``t_ms`` as ``perf_counter`` seconds."""
    return (ORIGIN_NS + t_ms * 1e6) / 1e9


def perf_counter_to_span_ts(t_s: float) -> float:
    """A ``perf_counter`` stamp (seconds) as a span ``ts`` (µs)."""
    return (t_s * 1e9 - ORIGIN_NS) / 1e3


def perf_counter_to_event_ms(t_s: float) -> float:
    """A ``perf_counter`` stamp (seconds) as a request-log ``t_ms``."""
    return (t_s * 1e9 - ORIGIN_NS) / 1e6
