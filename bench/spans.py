"""The benchmark's own host spans and the compile clock.

Spans are recorded around the calls into each layer (generate, solve,
check, window).  Each is kept in memory and also written into the
profiler's trace as a ``bench.<name>`` annotation, so a traced
run can name the device's idle gaps by what the host was doing.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax

#: jax.monitoring duration events that are compile work: tracing,
#: lowering, compiling, and loading from the persistent cache.
COMPILE_EVENTS = ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile",
                  "cache_retrieval")


class Spans:
    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))


class CompileClock:
    """Seconds of compile work that JAX reports, summed since creation.

    JAX offers no way to remove a listener, so one clock is made per
    process (see :func:`compile_clock`).
    """

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if any(k in event for k in COMPILE_EVENTS):
            self.seconds += duration


_CLOCK: list[CompileClock] = []


def compile_clock() -> CompileClock:
    if not _CLOCK:
        _CLOCK.append(CompileClock())
    return _CLOCK[0]
