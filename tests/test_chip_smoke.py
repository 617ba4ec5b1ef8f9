"""``chip_smoke.py`` on the CPU: its phases at a tiny size, and its
refusal to run anywhere but a TPU.

The script's ``main`` needs a TPU; these tests call the phase functions
it is built from (corpus, brute-force reference, host and device builds,
the frontend, updates, retrain, the multi-device paths) so the script
cannot rot between chip runs.
"""
from __future__ import annotations

import importlib
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


N = 4000


@pytest.fixture(scope="module")
def smoke():
    # the script lives at the repo root, outside the package
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(ROOT))
        yield importlib.import_module("chip_smoke")


@pytest.fixture(scope="module")
def tiny(smoke):
    X = smoke.make_corpus(N, 32, seed=0)
    Q = smoke.make_queries(X, smoke.BATCH, seed=0)
    radii, want = smoke.reference(X, 0, Q)
    return X, Q, radii, want


def test_main_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.main([])


@pytest.mark.parametrize("bare", [False, True])
def test_script_exits_nonzero_without_tpu(tmp_path, bare):
    """Under ``JAX_PLATFORMS=cpu`` — and copied alone into an empty
    directory — the script fails and prints no result line."""
    script = ROOT / "chip_smoke.py"
    if bare:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_reference_radii_hit_selectivity(smoke, tiny):
    X, Q, radii, want = tiny
    for s in smoke.SELECTIVITIES:
        k = max(1, round(s * len(X)))
        assert all(len(ids) == k for ids, _ in want[("range", s)])
    for k in smoke.KS:
        assert all(len(ids) == k for ids, _ in want[("knn", k)])


@pytest.mark.parametrize("updated", [False, True])
def test_reference_equals_linear_scan(smoke, tiny, updated):
    """The smoke run's one-scan reference gives ``LinearScan``'s answers,
    ids and f64 distances bit for bit, over the corpus and over the live
    set after the update batch."""
    from repro.baselines.linear_scan import LinearScan
    from repro.core import MetricSpace
    X, Q, radii, _ = tiny
    r, got = smoke.reference(X, 0, Q, None if not updated else radii,
                             updated=updated)
    rows, gids = smoke.live_set(X, 0, updated)
    scan = LinearScan(MetricSpace(rows, "l2"))
    want = {}
    for s in smoke.SELECTIVITIES:
        want[("range", s)] = [
            (gids[i], d) for i, d, _ in (scan.range_query(q, x)
                                         for q, x in zip(Q, r[s]))]
    for k in smoke.KS:
        want[("knn", k)] = [(gids[i], d) for i, d, _ in
                            (scan.knn_query(q, k) for q in Q)]
    smoke.compare(got, want, "LinearScan")
    for k in smoke.KS:                  # kNN in the scan's order, too
        for (gi, gd), (wi, wd) in zip(got[("knn", k)], want[("knn", k)]):
            assert np.array_equal(gi, wi) and np.array_equal(gd, wd)


def test_phases_tiny(smoke, tiny):
    X, Q, radii, want = tiny
    ix = smoke.build_index(X, 16)
    engine = smoke.make_engine(ix)
    stats = smoke.snapshot_stats(engine.snapshot)
    assert stats["slots"] >= len(X) and stats["device_bytes"] > 0
    with engine.frontend(max_batch=smoke.BATCH) as fe:
        assert smoke.compare(smoke.serve_frontend(fe, Q, radii), want,
                             "host") == 4 * smoke.BATCH
        assert fe.metrics()["batch_size_max"] == smoke.BATCH

        dix = smoke.build_index(X, 16, "device")
        assert smoke.structural_parity(ix, dix)["centers"]
        with smoke.make_engine(dix).frontend(max_batch=smoke.BATCH) as dfe:
            smoke.compare(smoke.serve_frontend(dfe, Q, radii), want,
                          "device build")

        gen = engine.generation
        smoke.apply_updates(engine, X, seed=0)
        assert engine.index.live_count() == N + 1000 - 100
        c, n_rows, backend = smoke.retrain_largest(engine)
        assert engine.generation == gen + 2 and n_rows > 0
        assert backend in ("host", "device")
        assert engine.index.clusters[c].buf_ids == []
        rows, gids = smoke.live_set(X, 0, updated=True)
        _, live = smoke.Reference(rows, gids).answers(Q, radii)
        smoke.compare(smoke.serve_frontend(fe, Q, radii), live, "updated")


def test_multi_device_paths_tiny(smoke, tiny):
    """The ``--chips 4`` phase on whatever devices this process has
    (one CPU device in plain tier-1, four under the fake-device leg)."""
    X, Q, radii, want = tiny
    got1, got4, gotr = smoke.four_chip_answers(
        smoke.build_index(X, 16), Q, radii, smoke.Phases())
    for got in (got1, got4, gotr):
        smoke.compare(got, want, "multi-device")
