"""The traced run's instruments, installed from the benchmark's side.

Each layer's entry point is wrapped in a ``jax.profiler.TraceAnnotation``
(a ``bench.<layer>`` span on the profiler's own clock), so an idle gap
of the device can be named by what the host was doing.  Two further
wrappers read what the program already computes: the frontend's
per-batch queue waits, and the shapes of every eager ``pdist`` and
``range_filter`` call, from which the roofline functions count bytes
and operations.  Nothing is installed in an untraced run.
"""
from __future__ import annotations

import functools
import threading

# (module path, attribute path, span name)
SPANS = (
    ("repro.serving.frontend", "ServingFrontend._execute",
     "bench.frontend.execute"),
    ("repro.serving.router", "PlanRouter._assign", "bench.router.assign"),
    ("repro.core.planner", "Planner.plan_knn", "bench.planner.plan"),
    ("repro.core.planner", "Planner.plan_range", "bench.planner.plan"),
    ("repro.core.executor", "_ResidentBackend.knn_candidates",
     "bench.executor.knn_candidates"),
    ("repro.core.executor", "_ResidentBackend.range_hits",
     "bench.executor.range_hits"),
    ("repro.core.executor", "QueryExecutor._refine_topk",
     "bench.refine.knn"),
    # execute_range's time outside range_hits is the range refinement
    ("repro.core.executor", "QueryExecutor.execute_range",
     "bench.refine.range"),
    ("repro.core.executor", "QueryExecutor._emit_profile",
     "bench.obs.emit_profile"),
)
KERNELS = ("pdist", "range_filter")


class Instruments:
    """Installs the wrappers; ``recording`` gates what they collect."""

    def __init__(self):
        self.recording = False
        self.waits_s: list = []
        self.calls = {k: [] for k in KERNELS}
        self._lock = threading.Lock()
        self._undo: list = []

    def _patch(self, owner, attr: str, wrapper) -> None:
        self._undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, wrapper)

    def install(self) -> None:
        import importlib

        import jax
        for mod_name, path, span in SPANS:
            cls_name, meth = path.split(".")
            cls = getattr(importlib.import_module(mod_name), cls_name)
            fn = getattr(cls, meth)

            def wrapped(*a, _fn=fn, _span=span, **kw):
                with jax.profiler.TraceAnnotation(_span):
                    return _fn(*a, **kw)
            self._patch(cls, meth, functools.wraps(fn)(wrapped))

        from repro.serving.frontend import ServingFrontend
        record = ServingFrontend._obs_record

        def obs_record(fe, size, waits, _fn=record):
            if self.recording:
                with self._lock:
                    self.waits_s.extend(waits)
            return _fn(fe, size, waits)
        self._patch(ServingFrontend, "_obs_record", obs_record)

        from repro.kernels import ops
        for name in KERNELS:
            fn = getattr(ops, name)

            def counted(q, p, *a, _fn=fn, _name=name, **kw):
                if self.recording and not isinstance(q, jax.core.Tracer):
                    with self._lock:
                        self.calls[_name].append(
                            (int(q.shape[0]), int(p.shape[0]),
                             int(q.shape[1])))
                return _fn(q, p, *a, **kw)
            self._patch(ops, name, functools.wraps(fn)(counted))

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()
