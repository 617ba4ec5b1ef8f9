"""The per-layer metrics read from the program's own per-batch cost
record: a tiny traced run reports each of them, and each reads nothing
from a program whose profiles do not carry the record."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from limsbench import cell, spec

PROGRAM_METRICS = ("executor.d2h_mb", "executor.d2h_ms",
                   "executor.device_wait_ms", "executor.host_syncs",
                   "router.route_ms", "obs.profile_ms")


def test_traced_tiny_cell_reports_program_metrics(tiny_root, isolated):
    args = cell.parse(["--workload", "tiny-closed", "--seed", "3000000019",
                       "--seconds", "1.0", "--trace", "1"])
    r = cell.run(args, t_start=0.0, chip=False, root=str(tiny_root))
    assert r["correct"] is True
    m = r["metrics"]
    assert set(PROGRAM_METRICS) <= set(m)
    assert m["executor.d2h_mb"]["unit"] == "MB"
    # a routed batch copies at least its routing and its candidate mask
    assert m["executor.d2h_mb"]["value"] > 0
    assert m["executor.host_syncs"]["value"] >= 2
    for name in PROGRAM_METRICS:
        assert m[name]["value"] >= 0


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_program_metric_silent_without_the_record(name):
    """A program whose profiles carry only the older fields and stages
    (plan, execute, refine; a cumulative host_syncs) reads as nothing."""
    old = SimpleNamespace(stages={"plan": 0.01, "execute": 0.2,
                                  "refine": 0.05},
                          host_syncs=147, candidates_per_query=10.0,
                          batch=64)
    mod = spec.load_module("metrics", name)
    assert mod.read({"profiles": [old, old]}) is None
    assert mod.read({"profiles": []}) is None
