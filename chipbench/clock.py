"""Compile accounting and the harness's host spans.

``CompileClock`` is copied from ``chip_smoke.py``: it sums JAX's lowering
and backend-compile durations, and also counts backend compiles, so a run can
show that nothing compiled inside its measured window.  ``span`` records a
named host span into the profiler's trace (a no-op when no trace runs); the
trace reduction names idle gaps on the device by the innermost span open.
"""

from __future__ import annotations

import contextlib

SPAN_PREFIX = "bench."


class CompileClock:
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.BACKEND:
            self.compiles += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on_event)


def span(name: str):
    """A host span ``bench.<name>`` in the profiler trace."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # pragma: no cover
        return contextlib.nullcontext()
    return TraceAnnotation(SPAN_PREFIX + name)
