"""Serving-stack layers above the engine (DESIGN.md §9).

Covers the refactor's acceptance properties: queries submitted
concurrently through the dynamic-batching frontend return bit-identical
results to direct ``QueryExecutor`` calls (resident AND paged — the CI
legs run this file on 1 and 4 fake devices); the batcher demonstrably
coalesces ≥2 submitters into one kernel batch; admission control sheds
with ``FrontendOverload`` when the bounded queue is full; the router
builds exactly one CandidatePlan per batch and dispatches sub-batches
to replicas whose results reassemble bit-identically; replica placement
shares the snapshot's aux state; ownership rebalance follows the heat
signal; and the ``repro.core.serving`` shim keeps old imports working.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import LIMSIndex, MetricSpace
from repro.core.executor import QueryExecutor
from repro.core.metrics import dist_one_to_many
from repro.core.snapshot import LIMSSnapshot
from repro.serving import (FrontendOverload, PlanRouter, ReplicaSet,
                           ServingEngine, ServingFrontend)

N, D = 1200, 5


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    from repro.data.datasets import gauss_mix
    X = gauss_mix(N, D, seed=13)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=5, m=3, n_rings=8)
    snap = LIMSSnapshot.build(ix)
    path = str(tmp_path_factory.mktemp("frontend-store"))
    snap.spill(path)
    return X, ix, snap, path


def _queries(X, n_q, seed=2, scale=0.004):
    rng = np.random.default_rng(seed)
    return X[rng.choice(len(X), n_q)] + rng.normal(0, scale, (n_q, D))


def _radii(X, Q, sel=0.02):
    return np.array([float(np.quantile(dist_one_to_many(q, X, "l2"), sel))
                     for q in Q])


def _pending(f: ServingFrontend) -> int:
    with f._cv:
        return len(f._pending)


def _wait_pending(f: ServingFrontend, n: int, timeout: float = 10.0):
    deadline = time.monotonic() + timeout
    while _pending(f) < n:
        assert time.monotonic() < deadline, \
            f"only {_pending(f)}/{n} requests queued"
        time.sleep(0.005)


# -------------------------------------------------------------------- shim
def test_core_serving_shim_still_works():
    """The refactor keeps every old import path alive."""
    from repro.core.serving import ServingEngine as shim_engine
    from repro.core import ServingEngine as core_engine
    assert shim_engine is ServingEngine
    assert core_engine is ServingEngine


# ---------------------------------------------------------------- replicas
def test_replica_set_shares_aux_state(setup):
    """Placement is a pytree map: device leaves move, aux data (ids,
    validity, store view) is shared by reference across replicas."""
    X, ix, snap, path = setup
    rs = ReplicaSet(snap, n_replicas=3)
    assert len(rs) == 3
    for rep in rs.members:
        s = rep.ex.snap
        assert s.gids_np is snap.gids_np
        assert s.valid_np is snap.valid_np
        assert s.store is snap.store
    own = rs.ownership()
    assert own.shape == (3, snap.K)
    assert (own.sum(axis=0) == 1).all()      # every cluster owned once
    # every replica answers bit-identically on its own
    Q = _queries(X, 4, seed=3)
    ref_ids, ref_ds = QueryExecutor(snap).knn_query_batch(Q, 5)
    for rep in rs.members:
        ids, ds = rep.ex.knn_query_batch(Q, 5)
        assert np.array_equal(ids, ref_ids)
        assert np.array_equal(ds, ref_ds)


def test_rebalance_follows_heat(setup):
    """Greedy makespan: the hottest cluster lands alone on one replica
    when it outweighs the rest combined; total heat stays balanced."""
    snap = setup[2]
    rs = ReplicaSet(snap, n_replicas=2)
    heat = np.ones(snap.K)
    heat[3] = 100.0
    owner = rs.rebalance(heat)
    hot = owner[3]
    assert (owner == hot).sum() == 1         # hot cluster isolated
    assert set(owner.tolist()) == {0, 1}
    assert np.array_equal(rs.owner, owner)
    stats = rs.load_stats()
    assert sum(s["owned_clusters"] for s in stats) == snap.K
    with pytest.raises(ValueError):
        rs.rebalance(np.ones(snap.K + 1))


# ------------------------------------------------------------------ router
def test_router_bit_identical_and_one_plan(setup):
    """Sub-batched execution across replicas reassembles to exactly the
    direct executor's results, from exactly one plan construction per
    batch (subsetting never re-plans)."""
    X, ix, snap, path = setup
    direct = QueryExecutor(snap)
    Q = _queries(X, 12, seed=5)
    router = PlanRouter(ReplicaSet(snap, n_replicas=3), max_batch=len(Q))
    rs = _radii(X, Q)
    rs[0] = 1e-12                            # unrouted → least loaded
    before = router.routing_ex.planner.built
    got = router.range_query_batch(Q, rs)
    assert router.routing_ex.planner.built == before + 1
    for (gi, gd), (ri, rd) in zip(got, direct.range_query_batch(Q, rs)):
        assert np.array_equal(gi, ri)
        assert np.array_equal(gd, rd)
    assert len(got[0][0]) == 0
    for k in (1, 7, N + 50):                 # incl. k > live clamp
        ids_r, ds_r = router.knn_query_batch(Q, k)
        ids_d, ds_d = direct.knn_query_batch(Q, k)
        assert np.array_equal(ids_r, ids_d)
        assert np.array_equal(ds_r, ds_d)
    # replica planners never built a plan; dispatch covered every query
    assert all(m.ex.planner.built == 0
               for m in router.replicas.members[1:])
    assert sum(m.queries for m in router.replicas.members) == 4 * len(Q)
    assert router.routed_heat.sum() > 0
    assert router.load_stats()["routed_heat"][0] >= 0


def test_router_paged_bit_identical(setup):
    """Routing composes with the paged tier: replicas share one
    StoreView/cache, results stay bit-identical, pins drain."""
    X, ix, snap, path = setup
    direct = QueryExecutor(snap)
    paged = LIMSSnapshot.load(path, store=True, cache_pages=8)
    Q = _queries(X, 8, seed=7)
    router = PlanRouter(ReplicaSet(paged, n_replicas=2), max_batch=len(Q))
    ids_r, ds_r = router.knn_query_batch(Q, 6)
    ids_d, ds_d = direct.knn_query_batch(Q, 6)
    assert np.array_equal(ids_r, ids_d)
    assert np.array_equal(ds_r, ds_d)
    assert paged.store.cache.pinned == 0
    rs = _radii(X, Q)
    for (gi, gd), (ri, rd) in zip(router.range_query_batch(Q, rs),
                                  direct.range_query_batch(Q, rs)):
        assert np.array_equal(gi, ri)
        assert np.array_equal(gd, rd)
    assert paged.store.cache.pinned == 0
    heat = router.replicas.cluster_heat()
    assert heat is not None and heat.shape == (paged.K,)
    assert heat.sum() > 0                    # cache counters fed back
    router.rebalance()                       # folds heat into ownership


def test_router_replica_error_reaches_caller(setup):
    """An executor failure inside a routed sub-batch re-raises on the
    calling thread, never silently drops queries."""
    X, ix, snap, path = setup
    router = PlanRouter(ReplicaSet(snap, n_replicas=1), max_batch=3)
    def boom(Q, plan):
        raise RuntimeError("replica died")
    router.replicas.members[0].ex.execute_knn = boom
    with pytest.raises(RuntimeError, match="replica died"):
        router.knn_query_batch(_queries(X, 3, seed=9), 4)


# ------------------------------------- capacity-bounded, padded sub-batches
MB, R4 = 16, 4                  # cap = ceil(16 / 4) = 4 → 8 rows (x8)


def _padded_router(snap, n_replicas=R4, max_batch=MB):
    """A router whose replicas record each sub-plan they execute."""
    router = PlanRouter(ReplicaSet(snap, n_replicas=n_replicas),
                        max_batch=max_batch)
    seen = []
    for rep in router.replicas.members:
        for name in ("execute_knn", "execute_range"):
            fn = getattr(rep.ex, name)

            def rec(Q, plan, _fn=fn, _rid=rep.rid):
                seen.append((_rid, plan.B, plan.pad, len(Q)))
                return _fn(Q, plan)
            setattr(rep.ex, name, rec)
    return router, seen


def _scan_answers(X, Q, kind, arg):
    from repro.baselines.linear_scan import LinearScan
    scan = LinearScan(MetricSpace(X, "l2"))
    if kind == "knn":
        return [scan.knn_query(q, arg)[:2] for q in Q]
    return [scan.range_query(q, r)[:2] for q, r in zip(Q, arg)]


def _by_dist(ids, ds):
    o = np.lexsort((ids, ds))
    return ids[o], ds[o]


@pytest.mark.parametrize("kind", ["knn1", "knn10", "range"])
@pytest.mark.parametrize("B", [1, 3, 4, 9, 16])
def test_padded_subbatches_bit_identical(setup, B, kind):
    """Four replicas, batches up to 16: whatever B, every sub-batch runs
    at the cap of 8 rows, and the answers equal the direct executor's
    and a linear scan's, ids and f64 distances bit for bit."""
    X, ix, snap, path = setup
    router, seen = _padded_router(snap)
    Q = _queries(X, B, seed=100 + B)
    direct = QueryExecutor(snap)
    if kind == "range":
        rs = _radii(X, Q)
        got = router.range_query_batch(Q, rs)
        ref = direct.range_query_batch(Q, rs)
        scan = _scan_answers(X, Q, "range", rs)
        for (gi, gd), (ri, rd), (si, sd) in zip(got, ref, scan):
            assert np.array_equal(gi, ri) and np.array_equal(gd, rd)
            gi, gd = _by_dist(gi, gd)
            si, sd = _by_dist(si, sd)
            assert np.array_equal(gi, si) and np.array_equal(gd, sd)
    else:
        k = int(kind[3:])
        ids, ds = router.knn_query_batch(Q, k)
        ids_d, ds_d = direct.knn_query_batch(Q, k)
        assert np.array_equal(ids, ids_d) and np.array_equal(ds, ds_d)
        for b, (si, sd) in enumerate(_scan_answers(X, Q, "knn", k)):
            assert np.array_equal(ids[b], si) and np.array_equal(ds[b], sd)
    assert sum(n for *_, n in seen) == B
    assert {pb for _, pb, _, _ in seen} == {8}
    assert all(pad == 8 - n for _, _, pad, n in seen)


@pytest.mark.parametrize("driver", ["rounds", "loop"])
def test_padding_never_adds_knn_rounds(setup, monkeypatch, driver):
    """A padding row copies a real query of its sub-batch, so it
    certifies when that query does: each sub-batch takes as many rounds
    as its slowest real query alone."""
    monkeypatch.setenv("REPRO_KNN_DRIVER", driver)
    X, ix, snap, path = setup
    router, seen = _padded_router(snap)
    Q = _queries(X, 11, seed=41, scale=0.05)
    direct = QueryExecutor(snap)
    solo = []
    for q in Q:
        direct.knn_query_batch(q[None], 10)
        solo.append(direct.last_knn["rounds"])
    pick = router._assign(router.routing_ex.planner.plan_knn(
        _pad_rows(Q, MB), 10, 64), len(Q), 8)
    router.knn_query_batch(Q, 10)
    assert any(pad for _, _, pad, _ in seen)
    for rep in router.replicas.members:
        idx = np.nonzero(pick == rep.rid)[0]
        if len(idx):
            assert rep.ex.last_knn["driver"] == driver
            assert rep.ex.last_knn["rounds"] == max(solo[i] for i in idx)


def _pad_rows(Q, n):
    return np.concatenate([Q, np.repeat(Q[:1], n - len(Q), axis=0)])


def test_varied_batch_sizes_compile_nothing_after_warm(setup):
    """After one kNN batch at max_batch (32: four replicas, each filled
    to its cap of 8), batches of any size compile no program: the batch
    is planned at 32 rows and every sub-batch runs at 8."""
    from repro.obs import registry as obs
    X, ix, snap, path = setup
    obs.configure("on")
    router, seen = _padded_router(snap, max_batch=32)
    router.knn_query_batch(_queries(X, 32, seed=60), 10)
    assert len({rid for rid, *_ in seen}) == R4
    direct = QueryExecutor(snap)
    Qs = [_queries(X, B, seed=60 + B) for B in (1, 3, 4, 9, 16, 23, 32)]
    refs = [direct.knn_query_batch(Q, 10) for Q in Qs]
    c0 = obs.REGISTRY.counter("jax.backend_compiles").value
    got = [router.knn_query_batch(Q, 10) for Q in Qs]
    assert obs.REGISTRY.counter("jax.backend_compiles").value == c0
    for (ids, ds), (ids_d, ds_d) in zip(got, refs):
        assert np.array_equal(ids, ids_d) and np.array_equal(ds, ds_d)
    assert {pb for _, pb, _, _ in seen} == {8}


@pytest.mark.parametrize("B", [1, 5, 16])
def test_one_replica_dispatches_unpadded(setup, B):
    """One replica: the batch is planned and executed at its own size,
    with no padding, exactly as it came."""
    X, ix, snap, path = setup
    router, seen = _padded_router(snap, n_replicas=1)
    assert router.shapes(B) == (B, 0)
    Q = _queries(X, B, seed=70 + B)
    ids, ds = router.knn_query_batch(Q, 5)
    rs = _radii(X, Q)
    router.range_query_batch(Q, rs)
    assert seen == [(0, B, 0, B), (0, B, 0, B)]
    ids_d, ds_d = QueryExecutor(snap).knn_query_batch(Q, 5)
    assert np.array_equal(ids, ids_d) and np.array_equal(ds, ds_d)


def test_paged_replicas_route_by_ownership_unpadded(setup):
    """Paged replicas take neither the cap nor padding: with every
    cluster owned by replica 0, the whole batch goes there, planned and
    executed at its own size, and stays bit-identical."""
    X, ix, snap, path = setup
    paged = LIMSSnapshot.load(path, store=True, cache_pages=8)
    router, seen = _padded_router(paged, max_batch=8)
    router.replicas.set_ownership(np.zeros(paged.K, np.int64))
    Q = _queries(X, 8, seed=75)
    assert router.shapes(len(Q)) == (len(Q), 0)
    ids, ds = router.knn_query_batch(Q, 5)
    assert seen == [(0, len(Q), 0, len(Q))]
    ids_d, ds_d = QueryExecutor(snap).knn_query_batch(Q, 5)
    assert np.array_equal(ids, ids_d) and np.array_equal(ds, ds_d)
    assert paged.store.cache.pinned == 0


@pytest.mark.parametrize("seed", range(6))
def test_capacity_assign_bounds_and_preference(seed):
    """No replica takes more than cap; every query is placed; a query
    off its first preference (most votes, then least load) finds that
    replica full; and a full replica kept the queries that vote for it
    most."""
    from repro.serving.router import capacity_assign
    rng = np.random.default_rng(seed)
    R = int(rng.integers(2, 6))
    cap = int(rng.integers(1, 9))
    B = int(rng.integers(1, R * cap + 1))
    votes = rng.integers(0, 4, (B, R)) * rng.integers(0, 2, (B, 1))
    load = rng.integers(0, 50, R)
    pick = capacity_assign(votes, load, cap)
    assert pick.shape == (B,) and pick.min() >= 0 and pick.max() < R
    counts = np.bincount(pick, minlength=R)
    assert counts.max() <= cap
    rank = np.argsort(np.argsort(load, kind="stable"), kind="stable")
    first = np.argmax(votes * R - rank[None, :], axis=1)
    for q in np.nonzero(pick != first)[0]:
        r = first[q]
        assert counts[r] == cap
        assert votes[pick == r, r].min() >= votes[q, r]


def test_router_records_dispatch_on_first_subbatch(setup):
    """A routed batch's dispatch record lands on its first sub-batch's
    profile: dispatch wall time, the sub-batches' own seconds, real and
    padding rows; the router's row counters follow."""
    from repro.obs import profile as prof
    from repro.obs import registry as obs
    X, ix, snap, path = setup
    obs.configure("on")
    router = PlanRouter(ReplicaSet(snap, n_replicas=R4), max_batch=MB)
    rows0 = obs.REGISTRY.counter("router.rows").value
    pad0 = obs.REGISTRY.counter("router.pad_rows").value
    n0 = len(prof.profiles())
    router.knn_query_batch(_queries(X, 9, seed=80), 5)
    ps = prof.profiles()[n0:]
    first = [p for p in ps if p.dispatch_s is not None]
    assert len(first) == 1 and len(ps) >= 2
    p = first[0]
    assert p.rows == 9 and p.pad_rows == 8 * len(ps) - 9
    # own seconds exclude waits for the interpreter: at most every
    # sub-batch working for the whole dispatch
    assert p.dispatch_s > 0 and 0 < p.subbatch_s <= len(ps) * p.dispatch_s
    assert sum(q.batch for q in ps) == 9
    assert p.as_dict()["pad_rows"] == p.pad_rows
    assert obs.REGISTRY.counter("router.rows").value - rows0 == 9
    assert obs.REGISTRY.counter("router.pad_rows").value - pad0 == \
        p.pad_rows


# ---------------------------------------------------------------- frontend
def test_frontend_coalesces_concurrent_submitters(setup):
    """Acceptance criterion: single-query submitters are coalesced into
    one kernel batch (≥2 demonstrably), with results bit-identical to a
    direct batch call."""
    X, ix, snap, path = setup
    Q = _queries(X, 6, seed=11)
    ref_ids, ref_ds = QueryExecutor(snap).knn_query_batch(Q, 5)
    with ServingFrontend(QueryExecutor(snap), max_batch=8,
                         slo_ms=50.0) as f:
        f.pause()
        results = [None] * len(Q)

        def submit(j):
            results[j] = f.knn_query(Q[j], 5)

        threads = [threading.Thread(target=submit, args=(j,))
                   for j in range(len(Q))]
        for t in threads:
            t.start()
        _wait_pending(f, len(Q))
        f.resume()
        for t in threads:
            t.join()
        for j, (ids, ds) in enumerate(results):
            assert np.array_equal(ids, ref_ids[j])
            assert np.array_equal(ds, ref_ds[j])
        m = f.metrics()
    assert m["submitted"] == len(Q)
    assert m["batches"] == 1                 # all six in one dispatch
    assert m["batch_size_max"] == len(Q)
    assert m["coalesced_batches"] >= 1
    assert m["shed"] == 0
    assert m["queue_wait_ms_p99"] >= m["queue_wait_ms_p50"] >= 0.0
    # the whole batch was routed (replica count is device-dependent)
    assert sum(r["queries"] for r in m["routing"]["replicas"]) == len(Q)


def test_frontend_batches_by_key(setup):
    """Range queries coalesce regardless of radius; kNN batches never
    mix k (k shapes the plan and the outputs)."""
    X, ix, snap, path = setup
    Q = _queries(X, 4, seed=15)
    rs = _radii(X, Q)
    direct = QueryExecutor(snap)
    ref_range = direct.range_query_batch(Q, rs)
    ref3 = direct.knn_query_batch(Q[:2], 3)
    ref9 = direct.knn_query_batch(Q[2:], 9)
    with ServingFrontend(QueryExecutor(snap), max_batch=8,
                         slo_ms=50.0) as f:
        f.pause()
        out = {}

        def submit(tag, fn, *a):
            out[tag] = fn(*a)

        threads = [threading.Thread(target=submit,
                                    args=(("r", j), f.range_query,
                                          Q[j], rs[j]))
                   for j in range(4)]
        threads += [threading.Thread(target=submit,
                                     args=(("k3", j), f.knn_query, Q[j], 3))
                    for j in range(2)]
        threads += [threading.Thread(target=submit,
                                     args=(("k9", j), f.knn_query, Q[j], 9))
                    for j in range(2, 4)]
        for t in threads:
            t.start()
        _wait_pending(f, 8)
        f.resume()
        for t in threads:
            t.join()
        m = f.metrics()
    for j in range(4):
        ids, ds = out[("r", j)]
        assert np.array_equal(ids, ref_range[j][0])
        assert np.array_equal(ds, ref_range[j][1])
    for j in range(2):
        assert np.array_equal(out[("k3", j)][0], ref3[0][j])
        assert np.array_equal(out[("k9", j + 2)][0], ref9[0][j])
    assert m["batches"] == 3                 # range, k=3, k=9 — never mixed
    assert m["coalesced_batches"] == 3
    assert m["batch_size_mean"] > 2.0


def test_frontend_sheds_on_overload(setup):
    """Admission control: a submit that finds the bounded queue full
    fails immediately with FrontendOverload; queued requests still
    complete exactly."""
    X, ix, snap, path = setup
    Q = _queries(X, 3, seed=17)
    ref_ids, _ = QueryExecutor(snap).knn_query_batch(Q[:2], 4)
    with ServingFrontend(QueryExecutor(snap), max_batch=4, slo_ms=20.0,
                         max_queue=2) as f:
        f.pause()
        results = {}
        threads = [threading.Thread(
            target=lambda j=j: results.update({j: f.knn_query(Q[j], 4)}))
            for j in range(2)]
        for t in threads:
            t.start()
        _wait_pending(f, 2)
        with pytest.raises(FrontendOverload):
            f.knn_query(Q[2], 4)             # queue full → shed, no queueing
        f.resume()
        for t in threads:
            t.join()
        m = f.metrics()
    assert m["shed"] == 1 and m["submitted"] == 2
    assert m["shed_rate"] == pytest.approx(1 / 3, abs=1e-4)
    for j in range(2):
        assert np.array_equal(results[j][0], ref_ids[j])


def test_frontend_tracks_engine_generation(setup):
    """The frontend rebuilds its replica set when the engine publishes a
    new snapshot generation — batches never mix generations, and queries
    after a refresh see the refreshed index."""
    X, ix0, snap, path = setup
    from repro.data.datasets import gauss_mix
    Xe = gauss_mix(800, D, seed=21)
    ixe = LIMSIndex(MetricSpace(Xe, "l2"), n_clusters=4, m=3, n_rings=8)
    se = ServingEngine(ixe, refresh_every=0)
    with se.frontend(max_batch=4, slo_ms=5.0) as f:
        q = Xe[5]
        ids0, _ = f.knn_query(q, 3)
        r0 = f._router_obj
        assert f._gen == se.generation
        p_new = Xe[5] + 1e-7                 # near-duplicate insert
        gid = se.insert(p_new)
        se.refresh()
        assert se.generation == f._gen + 1
        ids1, _ = f.knn_query(q, 3)
        assert f._gen == se.generation
        assert f._router_obj is not r0       # replica set rebuilt
        assert gid in ids1                   # new generation is served
        ref_ids, _ = se.executor.knn_query_batch(q[None], 3)
        assert np.array_equal(ids1, ref_ids[0])
    assert ids0 is not None


def test_frontend_paged_backend(setup):
    """Frontend → router → replicas over the paged tier: bit-identical
    to the resident direct path, pins fully drained after every batch."""
    X, ix, snap, path = setup
    Q = _queries(X, 5, seed=19)
    ref_ids, ref_ds = QueryExecutor(snap).knn_query_batch(Q, 6)
    paged = LIMSSnapshot.load(path, store=True, cache_pages=8)
    with ServingFrontend(QueryExecutor(paged), max_batch=8,
                         slo_ms=50.0) as f:
        f.pause()
        results = [None] * len(Q)
        threads = [threading.Thread(
            target=lambda j=j: results.__setitem__(j, f.knn_query(Q[j], 6)))
            for j in range(len(Q))]
        for t in threads:
            t.start()
        _wait_pending(f, len(Q))
        f.resume()
        for t in threads:
            t.join()
        m = f.metrics()
    for j, (ids, ds) in enumerate(results):
        assert np.array_equal(ids, ref_ids[j])
        assert np.array_equal(ds, ref_ds[j])
    assert m["coalesced_batches"] >= 1
    assert paged.store.cache.pinned == 0


def test_frontend_lifecycle(setup):
    """close() drains and rejects later submits; errors inside a batch
    reach every submitter of that batch."""
    X, ix, snap, path = setup
    f = ServingFrontend(QueryExecutor(snap), max_batch=4, slo_ms=5.0)
    ids, ds = f.knn_query(X[0], 2)
    assert len(ids) == 2
    f.close()
    with pytest.raises(RuntimeError, match="closed"):
        f.knn_query(X[0], 2)
    with pytest.raises(ValueError):
        ServingFrontend(QueryExecutor(snap), max_batch=0)
