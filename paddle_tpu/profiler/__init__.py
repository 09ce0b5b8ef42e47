"""Profiler facade.

TPU-native equivalent of the reference's profiler (upstream layout:
python/paddle/profiler/profiler.py — ``Profiler``, ``make_scheduler``,
``export_chrome_tracing``, ``RecordEvent``; the C++ tracers at
paddle/fluid/platform/profiler/ are replaced by XLA's profiler, reached via
``jax.profiler`` — device traces come from the TPU runtime itself).

The scheduler-state machine (CLOSED/READY/RECORD) and the step() protocol
match the reference; traces land as TensorBoard/XPlane dumps (viewable in
TensorBoard's profile plugin or Perfetto, the successor of chrome://tracing
— the artifact the reference's ChromeTracingLogger produced).
"""

from __future__ import annotations

import enum
import os
import time
from typing import Callable, Iterable, Optional

import jax

__all__ = ["ProfilerState", "ProfilerTarget", "make_scheduler",
           "export_chrome_tracing", "Profiler", "RecordEvent"]


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last record step of a cycle


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0
                   ) -> Callable[[int], ProfilerState]:
    """Step → state schedule (parity: paddle.profiler.make_scheduler)."""
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None
                          ) -> Callable:
    """on_trace_ready callback directing trace output under ``dir_name``
    (parity: paddle.profiler.export_chrome_tracing; format note in module
    doc).  The Profiler reads ``handler.dir_name`` at construction, so the
    XLA trace dump actually lands where the exporter points."""
    def handler(prof: "Profiler"):
        prof._last_export = dir_name
    handler.dir_name = dir_name
    os.makedirs(dir_name, exist_ok=True)
    return handler


class RecordEvent:
    """User-scope annotation visible in the trace (parity:
    paddle.profiler.RecordEvent; ≙ jax.profiler.TraceAnnotation).

    A thin wrapper over ``paddle_tpu.observability``'s span tracer, the
    one mechanism the serving engine's spans use too: one call records
    the scope as a host span (Chrome-trace/Perfetto export) AND enters a
    jax TraceAnnotation of the same name (the XLA/XPlane device dump) —
    the same labelled region in both timelines."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        from .. import observability
        self._span = observability.get_tracer().start(self.name, cat="user")

    def end(self):
        from .. import observability
        observability.get_tracer().finish(self._span)
        self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


class Profiler:
    """Parity: paddle.profiler.Profiler.

    with Profiler(scheduler=make_scheduler(closed=1, ready=1, record=3),
                  on_trace_ready=export_chrome_tracing("./prof")) as p:
        for batch in loader:
            train_step(...)
            p.step()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 log_dir: str = "./profiler_log", timer_only: bool = False):
        del targets  # one backend: whatever jax runs on
        if isinstance(scheduler, tuple):  # (start, stop) parity form
            lo, hi = scheduler
            scheduler = make_scheduler(closed=max(0, lo), ready=0,
                                       record=hi - lo, repeat=1)
        self.scheduler = scheduler or (lambda step: ProfilerState.RECORD)
        self.on_trace_ready = on_trace_ready
        # an export_chrome_tracing handler declares where traces belong
        if on_trace_ready is not None and hasattr(on_trace_ready, "dir_name"):
            log_dir = on_trace_ready.dir_name
        self.log_dir = log_dir
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._tracing = False
        self._in_export = False
        self._step_times = []
        self._last_t: Optional[float] = None
        self._last_export: Optional[str] = None

    # -- state machine -------------------------------------------------------

    def _finish_trace(self):
        """Close the current trace segment and fire on_trace_ready
        EXACTLY once for it.  ``_tracing`` is cleared before anything
        else runs, so the method is idempotent per segment however
        ``stop()`` and scheduler transitions interleave (the historical
        double-export: ``stop()`` right after a RECORD_AND_RETURN
        transition re-ran the export path), and ``_in_export`` guards a
        handler that itself calls ``stop()`` from recursing back in."""
        if not self._tracing:
            return
        self._tracing = False
        jax.profiler.stop_trace()
        if self.on_trace_ready is not None and not self._in_export:
            self._in_export = True
            try:
                self.on_trace_ready(self)
            finally:
                self._in_export = False

    def _transition(self):
        new = self.scheduler(self.step_num)
        recording = new in (ProfilerState.RECORD,
                            ProfilerState.RECORD_AND_RETURN)
        # RECORD_AND_RETURN means "last record step of a cycle": leaving
        # it is a segment boundary even when the next state records again
        # (repeat cycles) — previously back-to-back cycles merged into
        # one ever-growing trace and only exported once at the very end
        if self.current_state is ProfilerState.RECORD_AND_RETURN:
            self._finish_trace()
        if recording and not self._tracing and not self.timer_only:
            jax.profiler.start_trace(self.log_dir)
            self._tracing = True
        if not recording:
            self._finish_trace()
        self.current_state = new

    def start(self):
        self._last_t = time.perf_counter()
        self._transition()
        return self

    def stop(self):
        self._finish_trace()
        self.current_state = ProfilerState.CLOSED

    def step(self):
        now = time.perf_counter()
        if self._last_t is not None:
            self._step_times.append(now - self._last_t)
        self._last_t = now
        self.step_num += 1
        self._transition()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- summaries -----------------------------------------------------------

    def step_info(self) -> str:
        if not self._step_times:
            return "no steps recorded"
        ts = self._step_times
        return (f"steps: {len(ts)}  avg: {sum(ts) / len(ts) * 1e3:.2f} ms  "
                f"min: {min(ts) * 1e3:.2f} ms  max: {max(ts) * 1e3:.2f} ms")

    def summary(self, sorted_by=None, op_detail: bool = False,
                thread_sep: bool = False, time_unit: str = "ms") -> str:
        return self.step_info()
