"""Everything a run needs, found by name.

``BENCHMARK.json`` at the root of the checkout lists the cells and the
metrics.  A cell names a configuration and a traffic mix; each of those
is a data file of its own (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``), a configuration's corpus generator is
``bench/generators/<generator>.py`` and a per-layer metric's reader is
``bench/metrics/<metric>.py``.  Adding any of them is adding a file:
nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    root: str = ROOT


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics;
    ``KeyError`` for a name ``BENCHMARK.json`` does not list."""
    spec = benchmark(root)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    bench = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=_read_json(os.path.join(bench, "configs",
                                       w["config"] + ".json")),
        traffic=_read_json(os.path.join(bench, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=_for_cell(spec["end_to_end"], name),
        per_layer=_for_cell(spec["per_layer"], name), root=root)


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"limsbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
