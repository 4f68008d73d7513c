"""What JAX itself reports about compiling (jax.monitoring): every
trace, lowering and backend compile with the function's name and the
moment it ended, so that a compile inside the measured window is
counted and named, and set-up's compile seconds are known."""

from __future__ import annotations

import time
from typing import List, Tuple

_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}


class CompileWatch:
    def __init__(self):
        import jax
        self.events: List[Tuple[float, str, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        kind = _EVENTS.get(event)
        if kind is not None:
            self.events.append((time.perf_counter(), kind,
                                str(kw.get("fun_name", "?")), float(secs)))

    def inside(self, t0: float, t1: float) -> List[str]:
        """Names of the functions compiled (backend compile or cache
        load) with their end inside [t0, t1]."""
        return [name for at, kind, name, _s in self.events
                if kind == "compile" and t0 <= at <= t1]

    def seconds_before(self, t: float) -> float:
        """Trace + lower + compile seconds that ended before `t`."""
        return sum(s for at, _k, _n, s in self.events if at < t)
