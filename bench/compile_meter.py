"""XLA compiles and persistent-cache hits, from JAX's monitoring events.

A copy of the compile meter of the repository's chip smoke run, kept with
the benchmark so that the count does not move when the program does.  The
harness names a phase ("setup", "window", ...) and every backend compile
or cache hit is charged to the phase that is current when it happens.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Tuple

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        self._lock = threading.Lock()
        self.phase = "setup"
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.cache_hits: Dict[str, int] = {}
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start_time, end_time, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.spans.setdefault(self.phase, []).append(
                    (start_time, end_time))

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits[self.phase] = \
                    self.cache_hits.get(self.phase, 0) + 1

    def close(self) -> None:
        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_event_listener(self._event)

    def __enter__(self) -> "CompileMeter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def compiles(self, phase: str) -> int:
        return len(self.spans.get(phase, ()))

    def compile_s(self, phase: str) -> float:
        return sum(e - s for s, e in self.spans.get(phase, ()))

    def hits(self, phase: str) -> int:
        return self.cache_hits.get(phase, 0)
