"""The host refinement's rows per query (``refine.rows_per_query``): the
reader's value on hand-built profiles, nothing from a program whose
profiles lack the field, and tiny traced runs, on one executor and
through four replicas, that report it below the certified candidates."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from conftest import make_root
from limsbench import cell, spec

NAME = "refine.rows_per_query"


def _read(profiles):
    return spec.load_module("metrics", NAME).read({"profiles": profiles})


def _profile(batch, refined):
    return SimpleNamespace(batch=batch, refined_per_query=refined,
                           candidates_per_query=3800.0,
                           stages={"refine": 0.03})


@pytest.mark.parametrize("profiles, want", [
    ([_profile(64, 12.0)], 12.0),
    # weighted by batch size: (64·10 + 16·30) / 80
    ([_profile(64, 10.0), _profile(16, 30.0)], 14.0),
    # a tail sub-batch of 32 real rows weighs half: 2176 / 160
    ([_profile(64, 11.0), _profile(64, 13.0), _profile(32, 20.0)], 13.6),
])
def test_refine_rows_on_hand_built_profiles(profiles, want):
    assert _read(profiles) == pytest.approx(want)


@pytest.mark.parametrize("profiles", [
    [SimpleNamespace(batch=64, candidates_per_query=3800.0,
                     stages={"refine": 0.03})] * 2,
    [SimpleNamespace(batch=64, refined_per_query=None,
                     candidates_per_query=3800.0, stages={})],
    [],
])
def test_refine_rows_silent_without_the_field(profiles):
    """Profiles that predate the field, or carry it empty, read as
    nothing."""
    assert _read(profiles) is None


def test_traced_tiny_cell_reports_refine_rows(tiny_root, isolated):
    """A mixed kNN (k = 10) and range closed loop: refinement scans a
    few rows a query, fewer than the certified candidates."""
    args = cell.parse(["--workload", "tiny-closed", "--seed", "3000000041",
                       "--seconds", "1.0", "--trace", "1"])
    r = cell.run(args, t_start=0.0, chip=False, root=str(tiny_root))
    assert r["correct"] is True
    m = r["metrics"]
    assert m[NAME]["unit"] == "rows"
    assert 0 < m[NAME]["value"] < m["planner.cands_per_query"]["value"]


def test_traced_tiny_replicated_cell_reports_refine_rows(tmp_path, isolated):
    """A closed kNN loop through four replicas (cycled over the one CPU
    device): every replica's sub-batch profile carries the rows it
    refined, and the result line reports them."""
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
        if m["name"] in (NAME, "qps", "planner.cands_per_query"):
            m["workloads"].append("tiny-x4")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    args = cell.parse(["--workload", "tiny-x4", "--seed", "3000000059",
                       "--seconds", "1.0", "--trace", "1"])
    r = cell.run(args, t_start=0.0, chip=False, root=str(root))
    assert r["correct"] is True
    m = r["metrics"]
    assert 0 < m[NAME]["value"] < m["planner.cands_per_query"]["value"]
