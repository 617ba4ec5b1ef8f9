"""The k-th-distance band: kNN refinement scans only the rows that can
be answers.

The certified kNN set (``cand(r) ∧ ball(r)`` at the radius that
certified, or every valid slot on the exact fallback) is ANDed with
``_knn_band`` before it leaves the device.  Pinned here: the band's
radius is the smallest at which the loop's certification inequality
holds for the f32 k-th distance, never narrower; every refined set lies
inside the band and holds the whole closed f64 k-th ball; and answers
stay bit-identical to the golden driver and equal to a linear scan and
the host index — on a corpus whose rows come in threes, so several rows
tie exactly at the k-th distance — for k = 1, 10 and near ``live``, on
certified and fallback rows, through the compiled loop, the host rounds
driver and the sharded loop.
"""
import functools

import numpy as np
import pytest

import _golden_drivers as golden

import jax.numpy as jnp

from repro.baselines.linear_scan import LinearScan
from repro.core import LIMSIndex, MetricSpace
from repro.core import executor as executor_mod
from repro.core.executor import QueryExecutor, ShardedExecutor, _knn_band
from repro.core.metrics import dist_one_to_many
from repro.core.planner import _BALL_ABS, _R_REL
from repro.core.snapshot import LIMSSnapshot

D = 5


@pytest.mark.parametrize("eps", [0.0, 3e-4])
def test_band_radius_is_the_certification_radius(eps):
    """Over k-th distances from 0 to 300, the band admits every filter
    distance up to the f64 band edge ``r_t·(1 + _R_REL) + _BALL_ABS +
    eps`` with ``r_t = (kth0 + _BALL_ABS + eps) / (1 − _R_REL)``, less
    f32 rounding, and nothing a hair beyond it: the band is the
    certification's own radius, neither narrower nor left off."""
    rng = np.random.default_rng(7)
    kth0 = np.concatenate([
        [0.0, 1e-7, 1e-3], rng.random(200) * 1e-3, rng.random(200) * 0.5,
        rng.random(200) * 300.0]).astype(np.float32)
    k64 = kth0.astype(np.float64)
    r_t = (k64 + _BALL_ABS + eps) / (1.0 - _R_REL)
    edge = r_t * (1.0 + _R_REL) + _BALL_ABS + eps
    probe = np.stack([edge * (1 - 4e-7), edge * (1 + 1e-5)], axis=1)
    band = np.asarray(_knn_band(jnp.asarray(probe ** 2, jnp.float32),
                                jnp.asarray(kth0), jnp.float32(eps)))
    assert band[:, 0].all()
    assert not band[:, 1].any()


@functools.lru_cache(maxsize=1)
def _env():
    """A corpus whose rows all appear three times: every distance ties
    in threes, so the k-th distance is shared by 2 or 3 rows."""
    from repro.data.datasets import gauss_mix
    X0 = gauss_mix(250, D, seed=23)
    X = np.concatenate([X0, X0, X0])
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=5, m=3, n_rings=8)
    snap = LIMSSnapshot.build(ix)
    return X, ix, LinearScan(MetricSpace(X, "l2")), {
        "resident": QueryExecutor(snap), "sharded": ShardedExecutor(snap)}


def _queries(X):
    rng = np.random.default_rng(31)
    Q = X[rng.choice(250, 6)] + rng.normal(0, 0.004, (6, D))
    Q[0] = X[5]                              # three rows at distance 0
    Q[1] = X[7] + 1e-9
    return Q


def _ties_at_kth(X, q, k):
    d = np.sort(dist_one_to_many(q, X, "l2"))
    return int(np.sum(d == d[k - 1]))


# (executor, driver, k, max_rounds, seed): k None is near ``live``;
# max_rounds 0 runs no round, so every row takes the exact fallback;
# "small" shrinks the plan's seed radii and the skip-ahead below the
# k-th distance, so the one round fails on the host rounds driver (the
# only driver whose skip-ahead is not fixed when the loop compiles)
CASES = [
    ("resident", "loop", 1, 64, None), ("resident", "loop", 10, 64, None),
    ("resident", "loop", None, 64, None), ("resident", "loop", 10, 0, None),
    ("resident", "rounds", 1, 64, None),
    ("resident", "rounds", 10, 64, None),
    ("resident", "rounds", None, 64, None),
    ("resident", "rounds", 10, 0, None),
    ("resident", "rounds", 10, 1, "small"),
    ("sharded", "loop", 1, 64, None), ("sharded", "loop", 10, 64, None),
    ("sharded", "loop", None, 64, None), ("sharded", "loop", 10, 0, None),
]


@pytest.mark.parametrize("name,driver,k,max_rounds,seed", CASES)
def test_refined_sets_are_the_band_and_answers_unchanged(
        name, driver, k, max_rounds, seed, monkeypatch):
    """Every refined set lies inside the band (true distance within
    ~2·``_BALL_ABS`` of the k-th) and holds every valid slot whose f64
    distance is at most the k-th; the certified counts the profile
    reads are the LIMS set's (``live`` on a fallback row); and the
    answers equal the golden driver's bit for bit, and a linear scan's
    and the host index's distances, ids wherever no tie decides."""
    monkeypatch.setenv("REPRO_KNN_DRIVER", driver)
    X, ix, scan, exs = _env()
    ex = exs[name]
    s = ex.snap
    k = s.live - 3 if k is None else k
    Q = _queries(X)
    if seed == "small":
        monkeypatch.setattr(executor_mod, "_SEED_REL", -0.9)
        plan_knn = ex.planner.plan_knn

        def small_plan(*a):
            plan = plan_knn(*a)
            plan.radii = plan.radii * 1e-3
            return plan
        monkeypatch.setattr(ex.planner, "plan_knn", small_plan)
    got = {}
    knn_candidates, refine = ex.backend.knn_candidates, ex._refine_topk

    def keep_candidates(plan):
        got["final"], got["rounds"], got["certified"] = \
            out = knn_candidates(plan)
        return out

    def keep_refined(Q64, final, k_eff):
        got["refined"] = final
        return refine(Q64, final, k_eff)
    monkeypatch.setattr(ex.backend, "knn_candidates", keep_candidates)
    monkeypatch.setattr(ex, "_refine_topk", keep_refined)
    ids, ds = ex.knn_query_batch(Q, k, max_rounds=max_rounds)

    g_ids, g_ds = golden.knn_resident(exs["resident"], Q, k, max_rounds)
    assert np.array_equal(ids, g_ids) and np.array_equal(ds, g_ds)
    for b, q in enumerate(Q):
        tie = ds[b] == ds[b, -1]
        for ref_ids, ref_ds in (scan.knn_query(q, k)[:2],
                                ix.knn_query(q, k)[:2]):
            assert np.array_equal(ds[b], ref_ds)
            assert set(ids[b][~tie]) == set(np.asarray(ref_ids)[~tie])
    if k in (1, 10):
        assert _ties_at_kth(X, Q[0], k) >= 2     # the k-th ball's edge ties

    valid = np.flatnonzero(s.valid_np)
    cnt, clusters = got["certified"]
    refined = got["refined"]
    for b in range(len(Q)):
        d = dist_one_to_many(Q[b], s.rows_np[valid], "l2")
        d_k = np.sort(d)[k - 1]
        mine = refined[b]
        assert np.isin(valid[d <= d_k], mine).all()      # the closed ball
        d_mine = dist_one_to_many(Q[b], s.rows_np[mine], "l2")
        assert d_mine.max() <= d_k * (1 + 3e-5) + 2 * _BALL_ABS + 1e-5
        assert len(mine) <= cnt[b]
    if max_rounds == 0:
        assert got["rounds"] == 0 and (cnt == s.live).all()
    if seed == "small":
        assert got["rounds"] == 1 and (cnt == s.live).any()
    if k < 64:                                  # the band engaged
        assert refined.counts.sum() < cnt.sum()
    K = s.rids.shape[0]
    assert ((clusters >= 1) & (clusters <= K)).all()
