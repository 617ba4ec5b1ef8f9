"""Paged storage tier correctness (DESIGN.md §7).

Covers the acceptance properties: snapshot spill/load round trip is
bit-identical (structures AND range/kNN results, including after a
retrain's incremental manifest swap), the store-backed executor returns
results bit-identical to the in-memory path on both CI legs (the
``ShardedExecutor`` degrades or shards exactly as usual — only the row
payloads move to disk), the IO-batch scheduler dedupes and coalesces
page fetches, the LRU cache stays exact under eviction pressure, and
``ServingEngine`` serves cold-start from a spilled directory and writes
retrained clusters back as new page extents.
"""
import os

import numpy as np
import pytest

import jax

from repro.core import LIMSIndex, MetricSpace, ServingEngine
from repro.core.executor import QueryExecutor, ShardedExecutor
from repro.core.metrics import dist_one_to_many
from repro.core.snapshot import LIMSSnapshot
from repro.data.datasets import gauss_mix
from repro.storage import (Manifest, PageLayout, PagedStore, page_runs,
                           plan_batch, rows_per_page)

N, D = 1600, 6


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    X = gauss_mix(N, D, seed=7)
    sp = MetricSpace(X, "l2")
    ix = LIMSIndex(sp, n_clusters=6, m=3, n_rings=10)
    snap = LIMSSnapshot.build(ix)
    path = str(tmp_path_factory.mktemp("store"))
    snap.spill(path)
    return X, ix, snap, path


def _queries(X, n_q, seed=2, scale=0.004):
    rng = np.random.default_rng(seed)
    return X[rng.choice(len(X), n_q)] + rng.normal(0, scale, (n_q, D))


def _radii(X, Q, sel=0.02):
    return np.array([float(np.quantile(dist_one_to_many(q, X, "l2"), sel))
                     for q in Q])


def _assert_snapshots_equal(a: LIMSSnapshot, b: LIMSSnapshot):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    assert (a.K, a.m, a.n_rings, a.n_max, a.live) == \
        (b.K, b.m, b.n_rings, b.n_max, b.live)
    assert np.array_equal(a.gids_np, b.gids_np)
    assert np.array_equal(a.rows_np, b.rows_np)
    assert np.array_equal(a.valid_np, b.valid_np)


# ----------------------------------------------------------- layout/plan
def test_layout_math_and_alignment():
    rpp = rows_per_page(4096, 8)            # 64 f64 records of d=8
    assert rpp == 64
    assert rows_per_page(65536, 8) == 1024  # > 128 rows → 128-aligned
    assert rows_per_page(65536, 7) % 128 == 0
    lay = PageLayout(page_bytes=512, rows_per_page=8, d=8, n_max=20,
                     extents=(0, 3, 10))
    assert lay.pages_per_cluster == 3       # ceil(20/8)
    # slot 0 of cluster 1 starts at its extent; slot 19 is in its 3rd page
    pages, offs = lay.slot_locations(np.array([20, 39, 45]))
    assert pages.tolist() == [3, 5, 10] and offs.tolist() == [0, 3, 5]


def test_scheduler_dedupes_and_coalesces():
    lay = PageLayout(page_bytes=512, rows_per_page=8, d=8, n_max=16,
                     extents=(0, 2))
    cand = np.zeros((2, 32), bool)
    cand[0, [0, 1, 9]] = True          # cluster 0, pages 0 and 1
    cand[1, [1, 16, 31]] = True        # shares page 0; cluster 1 pages 2+3
    plan = plan_batch(cand, lay)
    assert plan.pages.tolist() == [0, 1, 2, 3]      # deduped across queries
    assert plan.runs == ((0, 4),)                   # coalesced to one run
    assert plan.pages_per_query.tolist() == [2, 3]
    assert plan.cand_per_query.tolist() == [3, 3]
    assert page_runs(np.array([0, 1, 5, 7, 8])) == ((0, 2), (5, 6), (7, 9))


# ------------------------------------------------------------- round trip
def test_spill_load_resident_roundtrip(setup):
    X, ix, snap, path = setup
    loaded = LIMSSnapshot.load(path)
    assert loaded.store is None
    _assert_snapshots_equal(snap, loaded)


def test_spill_is_atomic_no_temp_litter(setup):
    _, _, _, path = setup
    assert Manifest.exists(path)
    assert not [f for f in os.listdir(path) if ".tmp" in f]


def test_store_backed_results_bit_identical(setup):
    """The acceptance criterion: range and kNN through the paged store
    equal the in-memory executor bit for bit.  Runs the sharded wrapper
    so the 4-fake-device CI legs exercise the sharded candidate mask
    over a store-backed snapshot."""
    X, ix, snap, path = setup
    mem = QueryExecutor(snap)
    st = ShardedExecutor(LIMSSnapshot.load(path, store=True))
    assert st.snap.store is not None
    Q = _queries(X, 8, seed=3)
    rs = _radii(X, Q)
    rs[0] = 1e-12                               # provably empty query
    a = mem.range_query_batch(Q, rs)
    b = st.range_query_batch(Q, rs)
    assert len(b[0][0]) == 0
    for (ai, ad), (bi, bd) in zip(a, b):
        assert np.array_equal(ai, bi)
        assert np.array_equal(ad, bd)
    ids_a, ds_a = mem.knn_query_batch(Q, 6)
    ids_b, ds_b = st.knn_query_batch(Q, 6)
    assert np.array_equal(ids_a, ids_b) and np.array_equal(ds_a, ds_b)
    # k > live clamps identically (the store driver must terminate too)
    ids_a, ds_a = mem.knn_query_batch(Q[:2], N + 99)
    ids_b, ds_b = st.knn_query_batch(Q[:2], N + 99)
    assert ids_b.shape == (2, N)
    assert np.array_equal(ids_a, ids_b) and np.array_equal(ds_a, ds_b)


def test_store_reports_page_and_candidate_counts(setup):
    X, ix, snap, path = setup
    ex = QueryExecutor(LIMSSnapshot.load(path, store=True))
    Q = _queries(X, 5, seed=9)
    ex.range_query_batch(Q, _radii(X, Q))
    stats = ex.snap.store.stats.snapshot()
    assert stats["queries"] == 5
    assert stats["pages_per_query"] > 0
    assert stats["candidates_per_query"] > 0
    assert stats["requests"] == stats["hits"] + stats["misses"]
    # a single batch on a cold cache is all misses: the gather behind a
    # planned fetch must not re-count resident pages as hits
    assert stats["hits"] == 0 and stats["misses"] == stats["requests"]
    io = ex.last_io
    assert io["pages"] <= ex.snap.store.manifest.total_pages
    assert len(io["pages_per_query"]) == 5
    # candidate pages are a fraction of the corpus: the learned positions
    # narrow IO (the paper's point) — batch union strictly under a scan
    assert io["pages"] < ex.snap.store.manifest.total_pages


def test_lru_eviction_stays_exact(setup):
    """A 4-page cache thrashes constantly; results must not change and
    the counters must stay consistent."""
    X, ix, snap, path = setup
    tiny = QueryExecutor(LIMSSnapshot.load(path, store=True, cache_pages=4))
    mem = QueryExecutor(snap)
    Q = _queries(X, 6, seed=11)
    rs = _radii(X, Q)
    a = mem.range_query_batch(Q, rs)
    b = tiny.range_query_batch(Q, rs)
    for (ai, ad), (bi, bd) in zip(a, b):
        assert np.array_equal(ai, bi) and np.array_equal(ad, bd)
    st = tiny.snap.store
    assert len(st.cache) <= 4
    assert st.stats.evictions > 0
    assert st.stats.requests == st.stats.hits + st.stats.misses


# ----------------------------------------------------- serving + writeback
def test_serving_paged_writeback_and_extent_reuse(tmp_path):
    """A refresh after updates publishes a new generation atomically;
    clusters whose row bytes are unchanged keep their extents, dirty
    ones append new pages (append-only file — the reader's cache and any
    concurrent reader's mmap stay valid)."""
    X = gauss_mix(1200, D, seed=5)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=5, m=3, n_rings=10)
    path = str(tmp_path / "store")
    se = ServingEngine(ix, refresh_every=0, storage="paged",
                       storage_path=path)
    man0 = Manifest.load(path)
    assert se.executor.snap.store is not None
    # a delete only flips validity (metadata): row bytes unchanged
    # everywhere → every extent reused.  Target the smallest cluster so
    # the later retrain can't shrink the global n_max (full rewrite).
    victim = int(np.argmin([ci.n for ci in ix.clusters]))
    dead = int(ix.clusters[victim].store_ids[0])
    assert se.delete(X[dead]) == 1
    se.refresh()
    man1 = Manifest.load(path)
    assert man1.generation == man0.generation + 1
    assert man1.extents == man0.extents
    assert man1.total_pages == man0.total_pages
    # retrain the dirtied cluster: it drops the tombstone, so its rows
    # change — exactly its extent is rewritten (appended)
    se.retrain_cluster(victim)          # refresh_every=0 gates auto-refresh
    se.refresh()                        # → trigger manually
    man2 = Manifest.load(path)
    assert man2.generation > man1.generation
    assert man2.n_max == man1.n_max     # smallest cluster can't set n_max
    changed = [k for k in range(man2.K)
               if man2.extents[k] != man1.extents[k]]
    assert changed == [victim]
    assert man2.total_pages > man1.total_pages
    # post-writeback results still match the host exactly
    Q = _queries(X, 6, seed=13)
    rs = _radii(X, Q)
    for (ids, ds), q, r in zip(se.range_query_batch(Q, rs), Q, rs):
        h_ids, h_ds, _ = ix.range_query(q, r)
        assert set(map(int, ids)) == set(map(int, h_ids))
        np.testing.assert_allclose(np.sort(ds), np.sort(h_ds), atol=0)
    # and a fresh resident load of the swapped store round-trips the
    # current snapshot bit-for-bit (post-retrain manifest swap)
    _assert_snapshots_equal(LIMSSnapshot.build(ix), LIMSSnapshot.load(path))


def test_serving_paged_update_consistency():
    """Insert/delete/retrain through a paged engine: store-backed batch
    results stay bit-identical to the host after the refresh folds the
    updates in (buffer rows included, tombstones excluded)."""
    rng = np.random.default_rng(0)
    X = gauss_mix(1100, D, seed=9)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=4, m=3, n_rings=8)
    se = ServingEngine(ix, refresh_every=0, storage="paged")
    new_rows = X[rng.choice(1100, 12)] + rng.normal(0, 0.02, (12, D))
    gids = [se.insert(r) for r in new_rows]
    assert se.delete(X[3]) == 1
    assert se.delete(new_rows[0]) == 1
    se.retrain_cluster(0)
    se.refresh()
    Q = np.concatenate([new_rows[:3], X[rng.choice(1100, 3)]]) \
        + rng.normal(0, 0.003, (6, D))
    rs = _radii(X, Q)
    for (ids, ds), q, r in zip(se.range_query_batch(Q, rs), Q, rs):
        h_ids, h_ds, _ = ix.range_query(q, r)
        assert set(map(int, ids)) == set(map(int, h_ids))
        np.testing.assert_allclose(np.sort(ds), np.sort(h_ds), atol=0)
    ids, ds = se.knn_query_batch(Q, 5)
    for b, q in enumerate(Q):
        h_ids, h_ds, _ = ix.knn_query(q, 5)
        np.testing.assert_allclose(np.sort(ds[b]), np.sort(h_ds), atol=0)
    hit_ids, _ = se.range_query(new_rows[1], 1e-9)
    assert gids[1] in set(map(int, hit_ids))
    dead_ids, _ = se.range_query(new_rows[0], 1e-9)
    assert gids[0] not in set(map(int, dead_ids))


def test_inflight_executor_survives_writeback(tmp_path):
    """An executor serving generation g must keep returning generation-g
    results after refreshes publish later generations into the same
    store: its ``StoreView`` froze g's extents, and append-only page ids
    keep them byte-valid — the engine's contract that an in-flight batch
    finishes on its consistent snapshot extends to the storage tier."""
    X = gauss_mix(1000, D, seed=3)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=4, m=3, n_rings=8)
    se = ServingEngine(ix, refresh_every=0, storage="paged",
                       storage_path=str(tmp_path / "s"))
    old_ex = se.executor
    Q = _queries(X, 5, seed=23)
    rs = _radii(X, Q)
    before_r = old_ex.range_query_batch(Q, rs)
    before_k = old_ex.knn_query_batch(Q, 5)
    rng = np.random.default_rng(1)
    for row in X[rng.choice(1000, 8)] + rng.normal(0, 0.02, (8, D)):
        se.insert(row)
    for c in range(ix.K):            # rewrite every cluster's extent
        se.retrain_cluster(c)
    se.refresh()
    assert se.executor is not old_ex
    after_r = old_ex.range_query_batch(Q, rs)
    for (ai, ad), (bi, bd) in zip(before_r, after_r):
        assert np.array_equal(ai, bi) and np.array_equal(ad, bd)
    after_k = old_ex.knn_query_batch(Q, 5)
    assert np.array_equal(before_k[0], after_k[0])
    assert np.array_equal(before_k[1], after_k[1])


def test_cold_start_from_spill(setup):
    """A replica cold-starts from the spilled directory: serves exact
    results immediately, is read-only until an index is attached, and
    keeps its warm page cache across the first refresh."""
    X, ix, snap, path = setup
    cold = ServingEngine.from_spill(path)
    warm = QueryExecutor(snap)
    Q = _queries(X, 5, seed=17)
    rs = _radii(X, Q)
    a = warm.range_query_batch(Q, rs)
    b = cold.range_query_batch(Q, rs)
    for (ai, ad), (bi, bd) in zip(a, b):
        assert np.array_equal(ai, bi) and np.array_equal(ad, bd)
    ids_a, ds_a = warm.knn_query_batch(Q, 4)
    ids_b, ds_b = cold.knn_query_batch(Q, 4)
    assert np.array_equal(ids_a, ids_b) and np.array_equal(ds_a, ds_b)
    assert cold.store.stats.misses > 0          # pages faulted in on demand
    with pytest.raises(RuntimeError, match="read-only"):
        cold.insert(X[0])
    with pytest.raises(RuntimeError, match="read-only"):
        cold.refresh()
    cold.attach_index(ix)
    store_before = cold.store
    cold.refresh()
    assert cold.store is store_before           # warm reader carried over
    b2 = cold.range_query_batch(Q, rs)
    for (ai, ad), (bi, bd) in zip(a, b2):
        assert np.array_equal(ai, bi) and np.array_equal(ad, bd)


def test_geometry_mismatch_rejected(setup):
    """Mixing record formats in one store file must be refused."""
    X, ix, snap, path = setup
    with pytest.raises(ValueError, match="geometry"):
        snap.spill(path, page_bytes=64)         # different rows_per_page


# -------------------------------------------------------------- compaction
def test_compact_reclaims_garbage_extents(tmp_path):
    """Repeated retrain writebacks append new extents and orphan the old
    ones; ``compact()`` rewrites the live extents into a fresh pages
    file (atomic manifest swap) and the garbage is reclaimed — while an
    executor bound to the pre-compaction generation keeps serving
    bit-identically through its ``StoreView`` (old file unlinked, bytes
    pinned by its mmap)."""
    X = gauss_mix(1000, D, seed=21)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=4, m=3, n_rings=8)
    path = str(tmp_path / "store")
    se = ServingEngine(ix, refresh_every=0, storage="paged",
                       storage_path=path)
    Q = _queries(X, 5, seed=31)
    rs = _radii(X, Q)
    old_ex = se.executor
    before_r = old_ex.range_query_batch(Q, rs)
    before_k = old_ex.knn_query_batch(Q, 5)
    rng = np.random.default_rng(7)
    for _ in range(2):                  # two dirty writeback generations
        for row in X[rng.choice(1000, 6)] + rng.normal(0, 0.02, (6, D)):
            se.insert(row)
        se.retrain_cluster(0)
        se.refresh()
    man_dirty = Manifest.load(path)
    live_pages = man_dirty.K * man_dirty.layout().pages_per_cluster
    assert man_dirty.total_pages > live_pages       # garbage accumulated
    size_dirty = se.store.nbytes_file()
    man_c = se.compact()
    assert man_c.generation == man_dirty.generation + 1
    assert man_c.total_pages == live_pages          # dense again
    assert man_c.pages_file != man_dirty.pages_file
    assert se.store.nbytes_file() < size_dirty      # bytes reclaimed
    assert not os.path.exists(os.path.join(path, man_dirty.pages_file))
    # compaction moved rows, not results: current, pre-compaction and
    # freshly loaded readers all still serve exactly
    for (ids, ds), q, r in zip(se.range_query_batch(Q, rs), Q, rs):
        h_ids, h_ds, _ = ix.range_query(q, r)
        assert set(map(int, ids)) == set(map(int, h_ids))
    after_r = old_ex.range_query_batch(Q, rs)
    for (ai, ad), (bi, bd) in zip(before_r, after_r):
        assert np.array_equal(ai, bi) and np.array_equal(ad, bd)
    after_k = old_ex.knn_query_batch(Q, 5)
    assert np.array_equal(before_k[0], after_k[0])
    assert np.array_equal(before_k[1], after_k[1])
    _assert_snapshots_equal(LIMSSnapshot.build(ix), LIMSSnapshot.load(path))
    # and the next dirty writeback appends into the compacted file
    se.insert(X[0] + 0.01)
    se.refresh()
    man_next = Manifest.load(path)
    assert man_next.pages_file == man_c.pages_file
    assert man_next.total_pages > man_c.total_pages


def test_compact_through_stale_reader_is_safe(tmp_path):
    """Regression: compact() must copy through the *latest published*
    manifest's file size, not the calling reader's possibly older mmap
    — a writeback since the reader's last refresh() appends extents
    past that mmap, and a stale-sized read would silently truncate the
    compacted file."""
    X = gauss_mix(700, D, seed=13)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=3, m=2, n_rings=6)
    path = str(tmp_path / "s")
    LIMSSnapshot.build(ix).spill(path)
    stale = PagedStore(path)            # mmap sized to generation 0
    # a writeback this reader never refresh()ed into: dirty every
    # cluster so new extents land beyond the stale reader's mmap
    for c in range(ix.K):
        ix.retrain_cluster(c)
    ix.insert(X[0] + 0.01)
    snap1 = LIMSSnapshot.build(ix)
    snap1.spill(path)
    assert Manifest.load(path).total_pages > stale.manifest.total_pages
    man_c = stale.compact()             # must read the NEW extents fully
    assert man_c.generation == Manifest.load(path).generation
    _assert_snapshots_equal(snap1, LIMSSnapshot.load(path))


def test_repeated_compaction_converges(tmp_path):
    """compact() after compact() is stable: no garbage → same size."""
    X = gauss_mix(600, D, seed=2)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=3, m=2, n_rings=6)
    path = str(tmp_path / "s")
    LIMSSnapshot.build(ix).spill(path)
    store = PagedStore(path)
    m1 = store.compact()
    size1 = store.nbytes_file()
    m2 = store.compact()
    assert m2.generation == m1.generation + 1
    assert store.nbytes_file() == size1
    assert m2.extents == m1.extents


def test_compaction_releases_retired_mmaps(tmp_path):
    """An unlinked pages file stays mapped only while a live StoreView
    pins it; once the last view dies, the next compaction/refresh drops
    the mmap (releasing the unlinked file's disk blocks).  Without this
    a long-lived reader would pin every retired generation forever."""
    X = gauss_mix(600, D, seed=8)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=3, m=2, n_rings=6)
    path = str(tmp_path / "s")
    snap = LIMSSnapshot.build(ix)
    snap.spill(path)
    store = PagedStore(path)
    v0 = store.view()                   # pins generation 0's file
    f0 = v0.file
    store.compact()
    assert f0 in store._maps            # v0 alive → old mmap retained
    rows_pinned = v0.gather(np.arange(4))
    assert rows_pinned.shape == (4, D)  # still readable post-unlink
    del v0, rows_pinned
    store.compact()                     # next adoption prunes it
    assert f0 not in store._maps
    assert len(store._maps) == 1        # only the current file mapped


# ---------------------------------------------------------- async prefetch
def test_prefetch_async_bit_identical_and_overlaps(setup):
    """``REPRO_PREFETCH=async`` is an IO-scheduling change only: kNN
    results stay bit-identical to the synchronous paged path, and the
    prefetcher demonstrably overlaps rounds — the speculative fetch for
    at least one round completes before that round's demand fetch
    arrives (the acceptance criterion's overlap proof)."""
    X, ix, snap, path = setup
    sync_ex = QueryExecutor(LIMSSnapshot.load(path, store=True),
                            prefetch="off")     # pinned past REPRO_PREFETCH
    pre_ex = QueryExecutor(LIMSSnapshot.load(path, store=True),
                           prefetch="async")
    assert sync_ex.prefetcher is None
    pf = pre_ex.prefetcher
    assert pf is not None
    # de-flake the overlap assertion: on a starved runner the daemon
    # worker might not get scheduled between submit and the next
    # round's demand, so let each demand wait for its pending ticket —
    # production keeps the racy best-effort behavior, this pins that
    # the machinery (submit → background fetch → demand hit) works
    orig_note = pf.note_demand

    def patient_note(pages, ticket=None):
        if ticket is not None:
            assert ticket.wait(timeout=60)
        orig_note(pages, ticket)

    pf.note_demand = patient_note
    # querying AT pivot rows collapses the seed radii to the guard band:
    # round-0 masks are tiny and each doubling adds slots (and pages)
    # incrementally — the regime prefetch exists for.  (Random-query
    # batches over a corpus this small saturate the batch-deduped page
    # union in round 0, leaving later rounds no IO to overlap.)
    Q = np.asarray(snap.pivots, np.float64).reshape(-1, D)[:8]
    ids_a, ds_a = sync_ex.knn_query_batch(Q, 8)
    ids_b, ds_b = pre_ex.knn_query_batch(Q, 8)
    assert np.array_equal(ids_a, ids_b) and np.array_equal(ds_a, ds_b)
    assert pre_ex.last_knn["rounds"] >= 2       # tiny seed → multi-round
    pf.drain()          # settle in-flight tickets before reading stats
    stats = pf.snapshot()
    assert stats["pages_submitted"] > 0
    assert stats["pages_fetched"] == stats["pages_submitted"]
    assert stats["overlapped_rounds"] >= 1
    assert 0.0 <= stats["hit_rate"] <= 1.0
    # range results are single-round (nothing to prefetch) but must be
    # unaffected by the prefetcher's presence
    rs = _radii(X, Q)
    a = sync_ex.range_query_batch(Q, rs)
    b = pre_ex.range_query_batch(Q, rs)
    for (ai, ad), (bi, bd) in zip(a, b):
        assert np.array_equal(ai, bi) and np.array_equal(ad, bd)


def test_prefetch_engine_wiring(tmp_path):
    """ServingEngine(prefetch="async") threads the mode through refresh
    generations; results stay exact."""
    X = gauss_mix(900, D, seed=17)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=4, m=3, n_rings=8)
    se = ServingEngine(ix, refresh_every=0, storage="paged",
                       storage_path=str(tmp_path / "s"), prefetch="async")
    assert se.executor.prefetcher is not None
    Q = _queries(X, 4, seed=3)
    ids, ds = se.knn_query_batch(Q, 5)
    for b, q in enumerate(Q):
        h_ids, h_ds, _ = ix.knn_query(q, 5)
        np.testing.assert_allclose(np.sort(ds[b]), np.sort(h_ds), atol=0)
    se.refresh()
    assert se.executor.prefetcher is not None   # survives the swap


# -------------------------------------------------------- schedule pinning
def test_pinned_pages_survive_cache_squeeze():
    """Unit pin/evict semantics: capacity eviction takes the coldest
    *unpinned* page; an all-pinned cache overflows instead of breaking
    a hold; releasing the pins shrinks back under capacity."""
    from repro.storage import LRUPageCache
    c = LRUPageCache(capacity_pages=2)
    blk = np.zeros((1, 1))
    c.put("a", blk), c.put("b", blk)
    c.pin(["a"])
    assert c.put("c", blk) == 1                 # "b" (coldest unpinned)
    assert c.peek("a") is not None and c.peek("b") is None
    c.pin(["c"])
    # "a"/"c" pinned → the only evictable page is "d" itself
    assert c.put("d", blk) == 1
    assert c.peek("a") is not None and c.peek("c") is not None
    c.pin(["d", "e"])                           # pin non-resident pages
    c.put("d", blk)
    assert len(c) == 3 and c.pinned == 4        # all pinned: overflowed
    assert c.put("e", blk) == 0                 # nothing evictable
    assert len(c) == 4
    assert c.unpin(["a", "c", "d", "e"]) == 2   # shrink back to capacity
    assert len(c) == 2 and c.pinned == 0


def test_unpin_restores_lru_order():
    """A pinned page earns recency like any other; after unpin it is
    evicted exactly when plain LRU would evict it — no residual
    privilege, no penalty."""
    from repro.storage import LRUPageCache
    c = LRUPageCache(capacity_pages=3)
    blk = np.zeros((1, 1))
    for k in ("a", "b", "c"):
        c.put(k, blk)
    c.pin(["a"])
    c.touch("a")                                # "a" now hottest
    c.unpin(["a"])
    c.put("d", blk)                             # plain LRU: "b" goes
    assert c.peek("b") is None
    assert all(c.peek(k) is not None for k in ("a", "c", "d"))


def test_plan_pins_released_after_batch(setup, monkeypatch):
    """A batch pins its planned pages for its whole execution (fetch →
    gather → exact refinement) and releases them all afterwards — on
    success AND when the executor errors mid-batch."""
    X, ix, snap, path = setup
    ex = QueryExecutor(LIMSSnapshot.load(path, store=True, cache_pages=4))
    store = ex.snap.store
    Q = _queries(X, 5, seed=23)
    rs = _radii(X, Q)
    mem = QueryExecutor(snap)
    a = mem.range_query_batch(Q, rs)
    b = ex.range_query_batch(Q, rs)
    for (ai, ad), (bi, bd) in zip(a, b):
        assert np.array_equal(ai, bi) and np.array_equal(ad, bd)
    assert ex.last_io["pinned_pages"] > 0
    assert store.cache.pinned == 0              # fully released
    assert len(store.cache) <= 4                # overflow cleared too
    ids_m, _ = mem.knn_query_batch(Q, 6)
    ids_p, _ = ex.knn_query_batch(Q, 6)
    assert np.array_equal(ids_m, ids_p)
    assert ex.last_io["pinned_pages"] > 0
    assert store.cache.pinned == 0
    # executor error mid-refinement: the finally still drains the plan
    def boom(idx):
        raise RuntimeError("refinement died")
    monkeypatch.setattr(ex, "_refine_rows", boom)
    with pytest.raises(RuntimeError, match="refinement died"):
        ex.range_query_batch(Q, rs)
    assert store.cache.pinned == 0
    with pytest.raises(RuntimeError, match="refinement died"):
        ex.knn_query_batch(Q, 6)
    assert store.cache.pinned == 0


def test_pin_mode_off_is_blind_lru(setup, monkeypatch):
    """``REPRO_CACHE_PIN=off`` (blind LRU) takes no holds at
    all — and results are unchanged either way."""
    X, ix, snap, path = setup
    monkeypatch.setenv("REPRO_CACHE_PIN", "off")
    ex = QueryExecutor(LIMSSnapshot.load(path, store=True, cache_pages=4))
    Q = _queries(X, 4, seed=29)
    ids_p, ds_p = ex.knn_query_batch(Q, 5)
    assert ex.last_io["pinned_pages"] == 0
    assert ex.snap.store.cache.pinned == 0
    ids_m, ds_m = QueryExecutor(snap).knn_query_batch(Q, 5)
    assert np.array_equal(ids_p, ids_m) and np.array_equal(ds_p, ds_m)


# -------------------------------------------------------- prefetch shutdown
def test_prefetch_shutdown_drops_and_counts(setup):
    """Satellite requirement: the prefetch daemon stops deliberately —
    queued/in-flight plans are dropped (not drained), the drop is
    visible in the prefetcher's stats, and a post-shutdown submit
    degrades to an immediate counted drop instead of leaking work."""
    import repro.storage.prefetch as pfm
    from repro.storage import PagePrefetcher, shutdown_prefetch
    X, ix, snap, path = setup
    store = PagedStore(path)
    pf = PagePrefetcher(store)
    try:
        t = pf.submit(np.arange(3, dtype=np.int64))
        assert t.wait(5.0)
        assert pf.pages_fetched == 3
        assert shutdown_prefetch(timeout=5.0)   # joined within timeout
        t2 = pf.submit(np.arange(4, dtype=np.int64))
        assert t2.done()                        # completes at once...
        snap_d = pf.snapshot()
        assert snap_d["dropped_plans"] == 1     # ...but dropped, counted
        assert snap_d["pages_dropped"] == 4
        assert pf.pages_fetched == 3            # nothing fetched for it
        pf.drain()                              # no-op, must not hang
        assert shutdown_prefetch()              # idempotent
    finally:
        pfm._restart_for_tests()                # rest of the suite
    t3 = pf.submit(np.arange(2, dtype=np.int64))
    assert t3.wait(5.0)
    assert pf.pages_fetched == 5


# ----------------------------------------------------------------- real IO
def test_drop_os_cache_best_effort(setup):
    """``--real-io`` support: dropping the OS page cache is advisory and
    must never change results (it only makes the next cold read honest)."""
    X, ix, snap, path = setup
    ex = QueryExecutor(LIMSSnapshot.load(path, store=True))
    Q = _queries(X, 4, seed=41)
    rs = _radii(X, Q)
    a = ex.range_query_batch(Q, rs)
    supported = ex.snap.store.drop_os_cache()
    assert supported == hasattr(os, "posix_fadvise")
    ex.snap.store.cache.clear()
    b = ex.range_query_batch(Q, rs)
    for (ai, ad), (bi, bd) in zip(a, b):
        assert np.array_equal(ai, bi) and np.array_equal(ad, bd)
