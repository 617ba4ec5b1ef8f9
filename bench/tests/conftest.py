"""Tiny cells for the benchmark's CPU tests.

``tiny_root`` is a checkout of its own: a copy of ``bench/`` and of
``BENCHMARK.json`` to which two tiny cells are added as new files and
new entries (a closed mixed kNN/range loop and an open kNN loop over a
3,000-row GaussMix corpus), with the program's ``src`` beside it.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

TINY_CONFIG = {
    "name": "tiny", "generator": {"name": "gauss_mix",
                                  "args": {"components": 20, "std": 0.05}},
    "data_seed": 5, "n": 3000, "d": 8, "metric": "l2", "K": 8, "m": 3,
    "N": 20, "build": "host", "chips": 1}
TINY_TRAFFIC = {
    "tiny-closed": {"loop": "closed", "clients": 8,
                    "frontend": {"max_batch": 4},
                    "queries": [{"kind": "knn", "k": 10, "share": 0.5},
                                {"kind": "range", "selectivity": 0.01,
                                 "share": 0.5}],
                    "radius_sample": 4, "noise": 0.003, "pool": 64,
                    "check_sample": 16},
    "tiny-open": {"loop": "open", "rate": 40.0, "workers": 16,
                  "frontend": {"max_batch": 4},
                  "queries": [{"kind": "knn", "k": 5, "share": 1.0}],
                  "noise": 0.003, "check_sample": 16},
}


def make_root(dst: Path) -> Path:
    """A checkout in ``dst`` with the tiny cells added."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(ROOT / "src", dst / "src")
    (dst / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    for name, tr in TINY_TRAFFIC.items():
        (dst / f"bench/traffic/{name}.json").write_text(json.dumps(tr))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "CPU tests"})
    for name in TINY_TRAFFIC:
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": name, "chips": 1,
                                  "why": "CPU tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-closed")
    # the open loop's metrics, which the harness and bench/metrics/
    # carry for an open cell
    spec["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tiny-open"]}
        for n in ("p50_ms", "p95_ms")]
    spec["per_layer"] += [
        {"name": n, "unit": u, "better": "lower", "source": src,
         "layer": layer, "moves": "p95_ms", "workloads": ["tiny-open"]}
        for n, u, src, layer in (
            ("frontend.queue_wait_ms.open", "ms", "program_span", "frontend"),
            ("device.idle.open", "%", "device_trace", "device"),
            ("loadgen.late_ms.open", "ms", "host_clock", "load generator"))]
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def isolated():
    """A run changes the process: the environment's ``REPRO_*`` knobs,
    JAX's persistent-cache settings, the profile ring.  Put them back.
    A set ``JAX_COMPILATION_CACHE_DIR`` makes the run leave JAX's cache
    directory as it is, so a test run writes no persistent cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.obs import profile
    env = dict(os.environ)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    conf = {k: getattr(jax.config, k) for k in keys}
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          conf["jax_compilation_cache_dir"] or "unset")
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(env)
        for k, v in conf.items():
            jax.config.update(k, v)
        cc.reset_cache()
        profile.clear_profiles()
