"""The four-replica cell and the router's per-layer metrics: the cell
loads by name, the three readers read hand-built dispatch records, read
nothing from a program whose profiles lack the record, and a tiny
traced four-replica run reports them."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from conftest import make_root
from limsbench import cell, spec

ROUTER_METRICS = ("router.dispatch_ms", "router.parallel",
                  "router.pad_share")


def test_replicated_cell_loads():
    """The four-replica cell: its configuration, traffic and metrics
    load by name, it reports qps and setup_s, and its closed loop keeps
    every batch at 256 (64 a replica)."""
    c = spec.load_cell("gm32-knn10-replicas4")
    assert c.chips == 4 and c.config["replicas"] == 4
    one = spec.load_cell("gm32-knn10-closed").config
    for key in ("generator", "data_seed", "n", "d", "metric", "K", "m",
                "N", "build", "guarantee"):
        assert c.config[key] == one[key]
    assert {m["name"] for m in c.end_to_end} == {"qps", "setup_s"}
    names = {m["name"] for m in c.per_layer}
    assert {"router.dispatch_ms", "router.parallel", "router.pad_share",
            "frontend.batch_mean", "router.route_ms", "device.idle.closed",
            "pdist_roofline"} <= names
    assert c.traffic["frontend"] == {"max_batch": 256, "max_queue": 1024}
    assert cell.batch_sizes(c.traffic) == [256]
    assert c.traffic["frontend"]["max_queue"] >= c.traffic["clients"]


@pytest.mark.parametrize("name", ROUTER_METRICS)
def test_router_metric_silent_without_the_record(name):
    """A program whose profiles carry no dispatch record (one replica,
    or a router that does not write it) reads as nothing."""
    old = SimpleNamespace(stages={"plan": 0.01, "execute": 0.2,
                                  "refine": 0.05},
                          host_syncs=3, candidates_per_query=10.0,
                          batch=64)
    mod = spec.load_module("metrics", name)
    assert mod.read({"profiles": [old, old]}) is None
    assert mod.read({"profiles": []}) is None


def _batch(dispatch_s, subbatch_s, rows, pad_rows, n_sub=4):
    """A routed batch's profiles: the first carries the dispatch record."""
    first = SimpleNamespace(dispatch_s=dispatch_s, subbatch_s=subbatch_s,
                            rows=rows, pad_rows=pad_rows)
    rest = SimpleNamespace(dispatch_s=None, subbatch_s=None, rows=None,
                           pad_rows=None)
    return [first] + [rest] * (n_sub - 1)


def _read(name, profiles):
    return spec.load_module("metrics", name).read({"profiles": profiles})


@pytest.mark.parametrize("case, want", [
    ("serial", {"router.dispatch_ms": 400.0, "router.parallel": 1.0,
                "router.pad_share": 0.0}),
    ("overlapped", {"router.dispatch_ms": 100.0, "router.parallel": 4.0,
                    "router.pad_share": 0.0}),
    ("padded", {"router.dispatch_ms": 150.0, "router.parallel": 2.5,
                "router.pad_share": 25.0}),
])
def test_router_metrics_on_hand_built_profiles(case, want):
    """Serial: four sub-batches of 0.1 s one after another in 0.4 s.
    Overlapped: all four at once in 0.1 s.  Padded: a full batch of 256
    rows (2 s of sub-batches in 0.1 s) and a tail of 64 real rows
    padded to 256 (0.5 s in 0.2 s): 192 of 768 rows are padding."""
    if case == "serial":
        ps = _batch(0.4, 0.4, 256, 0) * 2
    elif case == "overlapped":
        ps = _batch(0.1, 0.4, 256, 0) * 3
    else:
        ps = _batch(0.1, 0.2, 512, 0) + _batch(0.2, 0.6, 64, 192)
    for name, v in want.items():
        assert _read(name, ps) == pytest.approx(v)


def test_traced_tiny_replicated_cell_reports_router_metrics(tmp_path,
                                                             isolated):
    """A closed kNN loop through four replicas (cycled over the one CPU
    device): batches of 32 split into sub-batches of 8, the answers
    exact, the router's metrics on the result line."""
    root = make_root(tmp_path)
    tr = {"loop": "closed", "clients": 64,
          "frontend": {"max_batch": 32, "max_queue": 128},
          "queries": [{"kind": "knn", "k": 10, "share": 1.0}],
          "noise": 0.003, "pool": 128, "check_sample": 32}
    (root / "bench/traffic/tiny-x4.json").write_text(json.dumps(tr))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-x4", "config": "tiny",
                           "traffic": "tiny-x4", "chips": 4, "why": "t"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ROUTER_METRICS + ("qps", "frontend.batch_mean"):
            m["workloads"].append("tiny-x4")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    args = cell.parse(["--workload", "tiny-x4", "--seed", "3000000023",
                       "--seconds", "1.0", "--trace", "1"])
    r = cell.run(args, t_start=0.0, chip=False, root=str(root))
    assert r["correct"] is True
    m = r["metrics"]
    assert set(ROUTER_METRICS) <= set(m)
    assert m["router.dispatch_ms"]["value"] > 0
    # own seconds (thread CPU + device waits) leave out waits for the
    # interpreter, so four replicas on one CPU device can read under 1
    assert 0.0 < m["router.parallel"]["value"] <= 4.0 + 1e-9
    assert 0.0 <= m["router.pad_share"]["value"] < 100.0
    assert m["frontend.batch_mean"]["value"] > 8
