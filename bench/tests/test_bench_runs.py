"""Whole runs of tiny cells on the CPU, with the look for a chip
skipped: the result line, the traced run's per-layer metrics, and runs
whose served path is broken underneath, which must come out not
correct."""
from __future__ import annotations

import numpy as np
import pytest

from limsbench import cell


def _run(root, workload: str, trace: int = 0, seed: int = 3_000_000_007):
    args = cell.parse(["--workload", workload, "--seed", str(seed),
                       "--seconds", "1.0", "--trace", str(trace)])
    return cell.run(args, t_start=0.0, chip=False, root=str(root))


@pytest.mark.parametrize("workload,trace", [
    ("tiny-closed", 0), ("tiny-closed", 1), ("tiny-open", 0),
    ("tiny-open", 1)])
def test_tiny_cell_runs_correct(tiny_root, isolated, workload, trace):
    r = _run(tiny_root, workload, trace)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"wrong_ids", "max_dist_gap", "unanswered"}
    assert r["device"]["count"] == 1
    m = r["metrics"]
    if not trace:
        want = {"qps", "setup_s"} if workload == "tiny-closed" else \
            {"p50_ms", "p95_ms", "setup_s"}
        assert set(m) == want
        assert all(v["value"] > 0 for v in m.values())
    else:
        assert r["device"]["window_s"] > 0
        if workload == "tiny-closed":
            assert {"frontend.batch_mean", "planner.cands_per_query",
                    "executor.execute_ms", "refine.refine_ms",
                    "device.idle.closed"} <= set(m)
            assert 1 <= m["frontend.batch_mean"]["value"] <= 4
        else:
            assert {"frontend.queue_wait_ms.open", "loadgen.late_ms.open",
                    "device.idle.open"} <= set(m)


def _altered_knn(monkeypatch):
    """Every kNN answer's last distance moved up by one ulp where the
    executor produces it."""
    from repro.core.executor import QueryExecutor
    orig = QueryExecutor._refine_topk

    def refine(self, Q, final, k_eff):
        ids, d = orig(self, Q, final, k_eff)
        d[:, -1] = np.nextafter(d[:, -1], np.inf)
        return ids, d
    monkeypatch.setattr(QueryExecutor, "_refine_topk", refine)


def _dropped_range(monkeypatch):
    """Every range answer loses its last row where the executor
    produces it."""
    from repro.core.executor import QueryExecutor
    orig = QueryExecutor.execute_range

    def execute(self, Q, plan):
        return [(i[:-1], d[:-1]) for i, d in orig(self, Q, plan)]
    monkeypatch.setattr(QueryExecutor, "execute_range", execute)


def _failing_batches(monkeypatch):
    """From the window on, every batch the router runs raises: no
    request of the window is answered."""
    from repro.serving.router import PlanRouter
    warm = cell.Session.warm

    def boom(self, *a, **kw):
        raise RuntimeError("broken path")

    def warm_then_break(self, reqs):
        warm(self, reqs)
        monkeypatch.setattr(PlanRouter, "knn_query_batch", boom)
    monkeypatch.setattr(cell.Session, "warm", warm_then_break)


@pytest.mark.parametrize("fault,workload", [
    (_altered_knn, "tiny-closed"), (_altered_knn, "tiny-open"),
    (_dropped_range, "tiny-closed"), (_failing_batches, "tiny-open")])
def test_broken_path_is_not_correct(tiny_root, isolated, monkeypatch, fault,
                                    workload):
    fault(monkeypatch)
    r = _run(tiny_root, workload)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values()) or \
        r["attempted"] == r["failed"]
