"""Counts of what JAX traced and compiled, from ``jax.monitoring``
(the smoke run's ``Phases`` counter, copied): a run reads the counts at
the window's edges, so a shape that set-up did not warm shows up as a
compile inside the window."""
from __future__ import annotations

import threading

_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.traces = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **_) -> None:
        with self._lock:
            if event == _COMPILE:
                self.compiles += 1
                self.compile_s += secs
            elif event == _TRACE:
                self.traces += 1

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles, "compile_s": self.compile_s,
                    "traces": self.traces, "cache_hits": self.cache_hits}
