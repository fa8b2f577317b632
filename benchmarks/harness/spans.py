"""Host-clock spans around the benchmark's calls into the program, and a
count of what JAX compiled (or loaded from its cache) meanwhile."""

from __future__ import annotations

import contextlib
import time
from typing import Dict


class Spans:
    """Seconds by span name. With ``annotate`` set, each span is also a
    ``TraceAnnotation`` named ``bench:<name>`` in the profiler's trace, so
    an idle gap on the device can be laid against what the host did."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)


class CompileCounter:
    """Counts JAX's backend-compile events: one for every program that was
    not in the process's jit cache, whether XLA compiled it or the
    persistent cache supplied it. None may fall inside a measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.count += 1

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._on_event)
