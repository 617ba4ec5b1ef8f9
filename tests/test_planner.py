"""Plan/execute query path (DESIGN.md §8).

Covers the acceptance properties of the CandidatePlan refactor: exactly
one plan construction per query batch shared by both execution backends;
the plan (radii, certified masks, cluster routing) is bit-identical
resident vs paged, single-device vs sharded (the 4-fake-device CI legs
run the real ``shard_map`` path), and unchanged across a store writeback
manifest swap; the unified path's range and kNN results are pinned
bit-identical against the pre-refactor drivers' golden outputs
(``tests/_golden_drivers.py``); and the compiled kNN loop's host-sync
counter is O(1) per batch regardless of workload.
"""
import functools
import tempfile

import numpy as np
import pytest

import _golden_drivers as golden
from _hypothesis_compat import given, settings, st

from repro.core import LIMSIndex, MetricSpace, ServingEngine
from repro.core.executor import QueryExecutor, ShardedExecutor
from repro.core.metrics import dist_one_to_many
from repro.core.snapshot import LIMSSnapshot

N, D = 1500, 6


@functools.lru_cache(maxsize=1)
def _env():
    """Shared corpus/snapshot/store + one executor per backend×sharding
    combination (module-level cache rather than a fixture so the
    hypothesis property test below stays fixture-free)."""
    from repro.data.datasets import gauss_mix
    X = gauss_mix(N, D, seed=11)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=6, m=3, n_rings=10)
    snap = LIMSSnapshot.build(ix)
    path = tempfile.mkdtemp(prefix="lims-plans-")
    snap.spill(path)
    executors = {
        "resident": QueryExecutor(snap),
        "paged": QueryExecutor(LIMSSnapshot.load(path, store=True)),
        "sharded": ShardedExecutor(snap),
        "sharded_paged": ShardedExecutor(LIMSSnapshot.load(path, store=True)),
    }
    return X, ix, snap, path, executors


@pytest.fixture(scope="module")
def setup():
    X, ix, snap, path, _ = _env()
    return X, ix, snap, path


@pytest.fixture(scope="module")
def executors():
    return _env()[4]


def _queries(X, n_q, seed=2, scale=0.004):
    rng = np.random.default_rng(seed)
    return X[rng.choice(len(X), n_q)] + rng.normal(0, scale, (n_q, D))


def _radii(X, Q, sel=0.02):
    return np.array([float(np.quantile(dist_one_to_many(q, X, "l2"), sel))
                     for q in Q])


def _assert_plans_equal(a, b, P_ref: int, K_ref: int):
    """Plan equality modulo shard padding (padded slots must be inert)."""
    assert (a.kind, a.k, a.max_rounds, a.growth) == \
        (b.kind, b.k, b.max_rounds, b.growth)
    assert np.array_equal(a.radii, b.radii)
    assert np.array_equal(a.radius_at(3), b.radius_at(3))
    am, bm = a.mask[:, :P_ref], b.mask[:, :P_ref]
    assert np.array_equal(am, bm)
    assert not a.mask[:, P_ref:].any() and not b.mask[:, P_ref:].any()
    ar, br = a.routing[:, :K_ref], b.routing[:, :K_ref]
    assert np.array_equal(ar, br)
    assert not a.routing[:, K_ref:].any() and not b.routing[:, K_ref:].any()


# ----------------------------------------------------- plan construction
def test_one_plan_construction_per_batch(executors, setup):
    """Acceptance criterion: exactly one CandidatePlan per query batch,
    whichever backend executes it."""
    X = setup[0]
    Q = _queries(X, 5, seed=3)
    rs = _radii(X, Q)
    for name in ("resident", "paged"):
        ex = executors[name]
        before = ex.planner.built
        ex.range_query_batch(Q, rs)
        assert ex.planner.built == before + 1, name
        ex.knn_query_batch(Q, 5)
        assert ex.planner.built == before + 2, name


# ------------------------------------------------------- plan identity
@settings(max_examples=6, deadline=None, derandomize=True)
@given(qseed=st.integers(0, 1000), sel=st.sampled_from([0.005, 0.02, 0.06]),
       k=st.sampled_from([1, 4, 9]))
def test_plan_identical_across_backends_and_shards(qseed, sel, k):
    """The hypothesis property: a batch's CandidatePlan — radii, mask,
    routing, schedule — is identical resident vs paged and single-device
    vs sharded (shard padding contributes only inert slots).  The plan
    is metadata-only, so moving rows to disk or across devices cannot
    change it."""
    X, ix, snap, path, executors = _env()
    Q = _queries(X, 4, seed=qseed)
    rs = _radii(X, Q, sel=sel)
    ref = executors["resident"]
    P_ref, K_ref = snap.n_slots, snap.K
    plans_r = {n: e.planner.plan_range(Q, rs)
               for n, e in executors.items()}
    plans_k = {n: e.planner.plan_knn(Q, k, 64)
               for n, e in executors.items()}
    for n in executors:
        _assert_plans_equal(plans_r["resident"], plans_r[n], P_ref, K_ref)
        _assert_plans_equal(plans_k["resident"], plans_k[n], P_ref, K_ref)
    assert ref.planner.built >= 2


def test_plan_unchanged_across_writeback_swap(tmp_path):
    """A store writeback (retrain → new extents, atomic manifest swap)
    must not change the plans of an executor bound to the previous
    generation: its snapshot metadata and StoreView are frozen."""
    from repro.data.datasets import gauss_mix
    X = gauss_mix(900, D, seed=4)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=4, m=3, n_rings=8)
    path = str(tmp_path / "store")
    se = ServingEngine(ix, refresh_every=0, storage="paged",
                       storage_path=path)
    old_ex = se.executor
    Q = _queries(X, 4, seed=9)
    rs = _radii(X, Q)
    pr0 = old_ex.planner.plan_range(Q, rs)
    pk0 = old_ex.planner.plan_knn(Q, 5, 64)
    m0, r0, km0 = pr0.mask.copy(), pr0.routing.copy(), pk0.mask.copy()
    for c in range(ix.K):
        se.retrain_cluster(c)
    se.refresh()                       # new generation, appended extents
    assert se.executor is not old_ex
    pr1 = old_ex.planner.plan_range(Q, rs)
    pk1 = old_ex.planner.plan_knn(Q, 5, 64)
    assert np.array_equal(pr0.radii, pr1.radii)
    assert np.array_equal(pk0.radii, pk1.radii)
    assert np.array_equal(m0, pr1.mask)
    assert np.array_equal(r0, pr1.routing)
    assert np.array_equal(km0, pk1.mask)


# ---------------------------------------------------------- golden pins
def test_unified_path_matches_golden_drivers(executors, setup):
    """The refactor's bit-identity pin: range and kNN through the
    unified plan/execute path equal the four pre-refactor drivers'
    outputs exactly, resident AND paged (both CI device legs run this —
    the executors fixture shards where devices allow)."""
    X = setup[0]
    Q = _queries(X, 6, seed=5)
    rs = _radii(X, Q)
    rs[0] = 1e-12                       # provably empty query
    mem, pag = executors["resident"], executors["paged"]
    new_r = mem.range_query_batch(Q, rs)
    assert len(new_r[0][0]) == 0
    for ref in (golden.range_resident(mem, Q, rs),
                golden.range_store(pag, Q, rs),
                pag.range_query_batch(Q, rs)):
        for (ai, ad), (bi, bd) in zip(new_r, ref):
            assert np.array_equal(ai, bi)
            assert np.array_equal(ad, bd)
    for k in (6, N + 99):               # incl. k > live clamp
        ids_n, ds_n = mem.knn_query_batch(Q, k)
        for ref in (golden.knn_resident(mem, Q, k),
                    golden.knn_store(pag, Q, k),
                    pag.knn_query_batch(Q, k),
                    executors["sharded"].knn_query_batch(Q, k),
                    executors["sharded_paged"].knn_query_batch(Q, k)):
            assert np.array_equal(ids_n, ref[0])
            assert np.array_equal(ds_n, ref[1])


# --------------------------------------------------- host-sync counter
def test_knn_host_syncs_constant_in_compiled_path(executors, setup,
                                                  monkeypatch):
    """Acceptance criterion: the device-resident kNN *loop* costs O(1)
    host syncs per batch — one for the plan's seed radii, one for the
    loop's certified masks — independent of workload (k, batch size,
    rounds).  The sharded executor must hold the same bound: its loop
    keeps every per-round reduction a collective.  Pinned to the
    compiled driver: ``REPRO_KNN_DRIVER=auto`` picks the host-driven
    vectorized-round driver on single-device XLA-CPU interpret (per
    round, eager dispatch beats the jitted loop's slow lowerings —
    see the driver test below), which syncs per round by design."""
    monkeypatch.setenv("REPRO_KNN_DRIVER", "loop")
    X = setup[0]
    for name in ("resident", "sharded"):
        ex = executors[name]
        syncs = []
        for k, nq in ((3, 4), (11, 8), (64, 2)):
            ex.knn_query_batch(_queries(X, nq, seed=k), k)
            assert ex.last_knn["backend"] == "resident"
            assert ex.last_knn["driver"] == "loop"
            assert ex.last_knn["rounds"] >= 1
            syncs.append(ex.last_knn["host_syncs"])
        assert len(set(syncs)) == 1, (name, syncs)
        assert syncs[0] <= 3, (name, syncs)
    # the paged backend is host-driven by design; it reports its rounds
    pag = executors["paged"]
    pag.knn_query_batch(_queries(X, 4, seed=1), 6)
    assert pag.last_knn["backend"] == "paged"
    assert pag.last_knn["rounds"] >= 1


def test_knn_rounds_driver_matches_loop_driver(executors, setup,
                                               monkeypatch):
    """The interpret-mode vectorized-round driver (the PR-5 q/s
    regression fix) executes the same certified schedule as the
    compiled ``lax.while_loop`` — results bit-identical, the banded
    sets refinement scans identical too, driver surfaced in
    ``last_knn``."""
    X = setup[0]
    ex = executors["resident"]
    Q = _queries(X, 5, seed=17)
    refine, sets = ex._refine_topk, []

    def keep(Q64, final, k_eff):
        sets.append(final)
        return refine(Q64, final, k_eff)
    monkeypatch.setattr(ex, "_refine_topk", keep)
    for k in (4, 23):
        monkeypatch.setenv("REPRO_KNN_DRIVER", "loop")
        ids_l, ds_l = ex.knn_query_batch(Q, k)
        assert ex.last_knn["driver"] == "loop"
        monkeypatch.setenv("REPRO_KNN_DRIVER", "rounds")
        ids_r, ds_r = ex.knn_query_batch(Q, k)
        assert ex.last_knn["driver"] == "rounds"
        assert ex.last_knn["host_syncs"] >= ex.last_knn["rounds"]
        assert np.array_equal(ids_l, ids_r)
        assert np.array_equal(ds_l, ds_r)
        loop_set, rounds_set = sets[-2:]
        assert np.array_equal(loop_set.offsets, rounds_set.offsets)
        assert np.array_equal(loop_set.slots, rounds_set.slots)
