"""Seconds JAX spends tracing, lowering and compiling, from its own
monitoring events, each with the host time it was reported at."""
from __future__ import annotations

import time

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Listens to JAX's compile events from construction on.  ``events``
    holds ``(perf_counter at report, event, seconds, function)``."""

    def __init__(self):
        import jax
        self.events: list[tuple[float, str, float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="?", **_):
        if event in (TRACE, LOWER, BACKEND):
            self.events.append((time.perf_counter(), event, duration,
                                fun_name))

    def seconds(self, lo: float, hi: float) -> float:
        """Trace, lower and compile seconds reported in ``[lo, hi]``."""
        return sum(d for t, _, d, _ in self.events if lo <= t <= hi)

    def backend_compiles(self, lo: float, hi: float) -> list[str]:
        """Functions compiled by the backend in ``[lo, hi]``."""
        return [f for t, e, _, f in self.events
                if e == BACKEND and lo <= t <= hi]
