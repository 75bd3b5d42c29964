"""Host spans the benchmark records around its calls into the program,
and JAX's compile events.  Each span is also a
``jax.profiler.TraceAnnotation``, so in a traced run the device trace
shows what the host was doing."""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple


class Recorder:
    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self.compile_events: List[Tuple[str, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = 0.0) -> List[float]:
        return [b - a for n, a, b in self.spans if n == name and a >= since]


# JAX's own compile events: tracing, lowering, and getting the executable
# (compiling it, or loading it from the persistent cache)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def listen_compiles(rec: Recorder) -> None:
    import jax

    def on_event(event: str, duration: float, **_kw):
        if event in COMPILE_EVENTS:
            rec.compile_events.append((event, float(duration)))

    jax.monitoring.register_event_duration_secs_listener(on_event)
