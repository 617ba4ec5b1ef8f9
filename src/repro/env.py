"""Central registry for ``REPRO_*`` environment knobs.

Every runtime knob the package reads from the environment is declared
here with its set of valid values.  Consumers call :func:`get` (or the
thin helper functions that wrap it next to their subsystem, e.g.
``storage.storage_mode``) instead of ``os.environ.get`` so that a typo
like ``REPRO_STORAGE=pages`` fails loudly with the list of accepted
values rather than silently selecting a default via a scattered string
comparison.

Conventions:

* The empty string is always accepted and means "use the default" —
  benchmark harnesses explicitly blank knobs between configs
  (``env["REPRO_STORAGE"] = ""``) and that must stay valid.
* Values are matched case-insensitively after stripping whitespace.
* Free-form knobs (paths) declare ``values=None`` and are returned raw.
"""
from __future__ import annotations

import os
from dataclasses import dataclass


@dataclass(frozen=True)
class Knob:
    name: str
    values: tuple[str, ...] | None  # None -> free-form (e.g. a path)
    default: str
    help: str


_KNOBS = (
    Knob("REPRO_INTERPRET",
         ("", "auto", "on", "off"), "auto",
         "Kernel execution lane: auto (interpret on CPU, compiled pallas "
         "on TPU/GPU), on (force pallas interpret), off (force the "
         "compiled lane: pallas on TPU/GPU, jitted-XLA on CPU)."),
    Knob("REPRO_PALLAS_INTERPRET",
         ("", "auto", "0", "1", "false", "true"), "",
         "Legacy alias for REPRO_INTERPRET (1/true -> on, 0/false -> "
         "off). Ignored when REPRO_INTERPRET is set."),
    Knob("REPRO_AUTOTUNE",
         ("", "off", "on", "force"), "on",
         "Kernel tile autotuning: off (static heuristics), on (consult "
         "the tuning table, heuristics on miss), force (tune misses via "
         "timed micro-runs and write the cache)."),
    Knob("REPRO_TUNE_CACHE", None, "",
         "Path of the user tuning-cache JSON (default "
         "~/.cache/repro-tune.json)."),
    Knob("REPRO_STORAGE",
         ("", "paged"), "",
         "Snapshot storage tier: resident (default) or paged."),
    Knob("REPRO_PREFETCH",
         ("", "off", "async"), "",
         "Paged-store prefetch: sync IO (default/off) or async overlap."),
    Knob("REPRO_CACHE_PIN",
         ("", "on", "off", "0", "1", "no", "yes"), "on",
         "Schedule-aware page-cache pinning (off/0/no disables)."),
    Knob("REPRO_COMPACT",
         ("", "on", "off"), "on",
         "Compacted candidate gather on the resident range path: gather "
         "the certified candidate rows once into a dense power-of-two "
         "bucket and filter only those (on, default), or stream the "
         "full padded slot array through the kernels (off)."),
    Knob("REPRO_ROWS_DTYPE",
         ("", "off", "f32", "bf16", "f16"), "off",
         "Reduced-precision filter plane: keep an extra bf16/f16 copy "
         "of the snapshot row plane for first-pass distance filtering, "
         "with a certified rounding-error margin widening the filter "
         "radius so no true result can be cut (exact f32/f64 refinement "
         "keeps final results bitwise identical). off/f32 (default) "
         "disables the extra plane."),
    Knob("REPRO_KNN_DRIVER",
         ("", "auto", "loop", "rounds"), "auto",
         "kNN driver: loop (device lax.while_loop), rounds (host-stepped "
         "vectorized rounds), auto (rounds on single-shard XLA-CPU, "
         "loop elsewhere)."),
    Knob("REPRO_REAL_IO",
         ("", "0", "1"), "",
         "Benchmarks: drop the OS page cache before cold paged passes."),
    Knob("REPRO_OBS",
         ("", "off", "on"), "on",
         "Observability (repro.obs; DESIGN.md §11): off (zero-cost "
         "disabled path), on (metrics registry + span latency "
         "histograms + QueryProfiles; spans also annotate a "
         "jax.profiler capture as lims.<span>)."),
    Knob("REPRO_OBS_RESERVOIR", None, "1024",
         "Histogram reservoir capacity (samples kept per histogram; "
         "percentiles are exact up to this many observations)."),
    Knob("REPRO_OBS_PROFILES", None, "256",
         "QueryProfile ring capacity: most recent per-batch serving "
         "profiles kept."),
    Knob("REPRO_MONITOR",
         ("", "off", "on"), "off",
         "Continuous health monitoring (repro.obs.monitor; DESIGN.md "
         "§12): off (zero-thread, zero-allocation path), on (background "
         "sampler thread snapshotting registry metrics into time "
         "series, health detectors, and the closed-loop serving "
         "daemon)."),
    Knob("REPRO_MONITOR_INTERVAL", None, "0.5",
         "Monitor sampler tick interval in seconds (float)."),
    Knob("REPRO_MONITOR_SERIES_CAP", None, "512",
         "Time-series ring capacity: most recent samples kept per "
         "monitored series."),
    Knob("REPRO_MONITOR_FINDINGS", None, "256",
         "Health-finding ring capacity: most recent detector findings "
         "kept by a monitor."),
    Knob("REPRO_MONITOR_RETRAIN",
         ("", "off", "recommend", "auto"), "off",
         "Closed-loop reaction to rank-model drift findings: off "
         "(ignore), recommend (surface retrain recommendations on the "
         "ServingEngine), auto (additionally trigger "
         "retrain_cluster)."),
)

KNOBS: dict[str, Knob] = {k.name: k for k in _KNOBS}


def get(name: str) -> str:
    """Validated value of knob ``name`` ("" and unset -> its default).

    Raises ``KeyError`` for an undeclared knob (a programming error) and
    ``ValueError`` for a set-but-invalid value (a user error).
    """
    knob = KNOBS[name]
    raw = os.environ.get(name)
    if raw is None:
        return knob.default
    if knob.values is None:
        return raw
    val = raw.strip().lower()
    if val not in knob.values:
        valid = ", ".join(repr(v) for v in knob.values if v) or "''"
        raise ValueError(
            f"{name}={raw!r} is not a valid setting ({knob.help} "
            f"Valid values: {valid}, or empty/unset for the default.)")
    return knob.default if val == "" else val


# fixed in-checkout cache path: JAX keys cache entries on it, so it must
# not move between runs (never a temp name, pid or time stamp)
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the
    examples) call this once at start-up; importing the package never
    does.  A set ``JAX_COMPILATION_CACHE_DIR`` is already read by JAX
    itself and wins — nothing is set in code then.  Otherwise the cache
    lives in ``.jax_cache`` at the root of the checkout.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


def describe() -> str:
    """Human-readable table of all knobs (used by ``python -m repro.env``)."""
    lines = []
    for k in _KNOBS:
        vals = "path" if k.values is None else "|".join(v for v in k.values if v)
        cur = os.environ.get(k.name)
        cur_s = f"  [set: {cur!r}]" if cur is not None else ""
        lines.append(f"{k.name} ({vals}; default {k.default!r}){cur_s}\n    {k.help}")
    return "\n".join(lines)


if __name__ == "__main__":  # pragma: no cover
    print(describe())
