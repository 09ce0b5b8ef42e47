"""Seconds jax spent producing each executable, by jitted-function name.
Copied from ``chip_smoke.py: CompileLog`` (sound there): it reports the
parts of ``setup_s`` and counts compilations inside the window (must be 0).
"""

import jax


class CompileLog:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name="?", **_):
        if event == self.EVENT:
            self.rows.append((str(fun_name), float(secs)))

    def drain(self, floor=1.0):
        """{name: seconds} of programs at or over ``floor`` seconds, the
        total and the count of all — and forget them."""
        rows, self.rows = self.rows, []
        out = {}
        for name, secs in rows:
            if secs >= floor:
                out[name] = round(out.get(name, 0.0) + secs, 2)
        out["total_s"] = round(sum(s for _, s in rows), 2)
        out["programs"] = len(rows)
        return out
