"""Unified observability for the serving stack (DESIGN.md §11).

One process-wide metrics registry (counters / gauges / bounded-reservoir
histograms), spans over the query path that also annotate a
``jax.profiler`` capture (``lims.<span>``, on the device trace's clock),
per-batch ``QueryProfile`` records, and exporters (JSON, Prometheus
text).  Controlled by ``REPRO_OBS=off|on``; the disabled path costs one
string compare and allocates nothing.

    from repro import obs
    with obs.span("my.stage"):
        ...
    obs.count("my.counter")
    print(obs.json_snapshot())
"""
from .registry import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                       MetricsRegistry, configure, count, enabled,
                       obs_mode, observe, set_gauge)
from .trace import instant, span  # noqa: F401
from .profile import (QueryProfile, clear_profiles,  # noqa: F401
                      last_profile, profiles, record_profile)
from .export import (json_snapshot, prometheus_text,  # noqa: F401
                     write_json_snapshot, write_prometheus)
from .timeseries import Series, SeriesStore, sparkline  # noqa: F401
from .health import (Detector, HealthFinding,  # noqa: F401
                     HeatSkewDetector, PruningRegressionDetector,
                     RankDriftDetector, SloBurnDetector,
                     default_detectors)
from .monitor import (Monitor, active_monitors,  # noqa: F401
                      configure_monitor, maybe_monitor, monitor_enabled,
                      monitor_mode, shutdown_monitors)

__all__ = [
    "REGISTRY", "Counter", "Detector", "Gauge", "HealthFinding",
    "HeatSkewDetector", "Histogram", "MetricsRegistry", "Monitor",
    "PruningRegressionDetector", "QueryProfile", "RankDriftDetector",
    "Series", "SeriesStore", "SloBurnDetector", "active_monitors",
    "clear_profiles", "configure", "configure_monitor", "count",
    "default_detectors", "enabled", "instant", "json_snapshot",
    "last_profile", "maybe_monitor", "monitor_enabled", "monitor_mode",
    "obs_mode", "observe", "profiles", "prometheus_text",
    "record_profile", "set_gauge", "shutdown_monitors", "span",
    "sparkline", "write_json_snapshot", "write_prometheus",
]
