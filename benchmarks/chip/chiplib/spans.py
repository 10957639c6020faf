"""Host spans and counters that the benchmark records around its calls into
the program's layers.

A span is written twice: into ``Spans.records`` on the host clock, and into
the profiler's trace as a ``jax.profiler.TraceAnnotation`` named
``bench/<name>``, so that the trace reduction can say which span was open
while the device sat idle.  Compilations are counted through JAX's
monitoring events, inside and outside the measured window alike.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

ANNOTATION_PREFIX = "bench/"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


@dataclass
class Spans:
    records: List[Tuple[str, float, float]] = field(default_factory=list)
    counters: Dict[str, float] = field(default_factory=dict)
    compiles: List[float] = field(default_factory=list)  # host clock at each

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def listen_for_compiles(self) -> None:
        """From now on, note the host time of every executable that XLA
        builds or loads from the persistent cache, and count the cache's
        misses."""
        import jax.monitoring

        def on_duration(event: str, _duration: float, **_kw) -> None:
            if event == COMPILE_EVENT:
                self.compiles.append(time.perf_counter())

        def on_event(event: str, **_kw) -> None:
            if event == CACHE_MISS_EVENT:
                self.count("compile_cache_misses")

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.compiles)

    def total(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> Tuple[int, float]:
        """(count, summed seconds) of the ``name`` spans that started in
        ``[t0, t1]``."""
        hits = [(b - a) for n, a, b in self.records if n == name and t0 <= a <= t1]
        return len(hits), sum(hits)
