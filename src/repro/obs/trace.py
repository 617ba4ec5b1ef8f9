"""Span-based tracing for the query path, on the profiler's clock.

A *span* is one timed region of the request path — ``frontend.execute``,
``router.assign``, ``executor.knn_execute``, ``executor.d2h`` — entered
as a context manager::

    with span("executor.knn_execute", {"B": 64}):
        ...

What a span costs depends on ``REPRO_OBS``:

* ``off`` — :func:`span` returns a shared no-op singleton; entering and
  exiting it does nothing and allocates nothing.
* ``on`` — the span's wall duration lands in the registry histogram
  ``span.<name>`` (seconds), and the span also enters a
  ``jax.profiler.TraceAnnotation`` named ``lims.<name>`` whose metadata
  is ``args``.  With no profiler capturing, the annotation is one
  inactive TraceMe check (about a microsecond); under a capture
  (``jax.profiler.trace``, ``python -m repro.obs.report --trace DIR``)
  the program's spans sit in the same trace as the device's ops, on one
  clock, nested per host thread — so an idle gap of the chip can be
  named by the span the host was in.

This module also counts backend compiles: a ``jax.monitoring`` listener,
registered once when the module is imported, bumps a per-thread count
(:func:`thread_compiles`, which a batch's cost record differences around
the work it charges) and the registry counter ``jax.backend_compiles``.
"""
from __future__ import annotations

import threading
import time

import jax
from jax.profiler import TraceAnnotation

from . import registry as _reg

PREFIX = "lims."
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Span:
    """Live span: duration → histogram, plus a profiler annotation."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str, args=None):
        self.name = name
        self._ann = TraceAnnotation(PREFIX + name, **args) if args \
            else TraceAnnotation(PREFIX + name)
        self._t0 = 0.0

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        _reg.REGISTRY.histogram("span." + self.name).observe(t1 - self._t0)


class _NullSpan:
    """Shared no-op span for the disabled path (never allocates)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL = _NullSpan()


def span(name: str, args=None):
    """A context manager timing the enclosed region (see module doc).
    ``args`` (a small dict, or None) becomes the profiler annotation's
    metadata; hot callers pass None to avoid building it."""
    if _reg._MODE == "off":
        return _NULL
    return _Span(name, args)


def instant(name: str, args=None) -> None:
    """A zero-length profiler annotation — e.g. a snapshot swap or a
    shed decision, things with a *moment* rather than a duration."""
    if _reg._MODE == "off":
        return
    with TraceAnnotation(PREFIX + name, **(args or {})):
        pass


_TLS = threading.local()


def thread_compiles() -> int:
    """Backend compiles this thread has run since the process began."""
    return getattr(_TLS, "compiles", 0)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _TLS.compiles = getattr(_TLS, "compiles", 0) + 1
        _reg.count("jax.backend_compiles")


jax.monitoring.register_event_duration_secs_listener(_on_duration)

__all__ = ["PREFIX", "instant", "span", "thread_compiles"]
