"""Candidate sets on their way to the host.

A (B, P) bool candidate mask computed on the device leaves it packed
into (B, W) uint32 words (``_pack_mask``) and becomes per-query slot
lists on the host (``CandidateSets``), which refinement and profiling
read.  Pinned here: the pack and its decoder round-trip to exactly
``np.nonzero`` of every row, slots ascending; served results stay
bit-identical to the golden drivers and agree with the host index, ties
included (corpus with duplicated rows); and a batch's profile reads what
the dense mask would give.  The copies, syncs and packed-batch count of
a served batch are pinned in ``test_obs.py``.
"""
import functools

import numpy as np
import pytest

import _golden_drivers as golden
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro import obs
from repro.core import LIMSIndex, MetricSpace
from repro.core import executor as executor_mod
from repro.core.executor import (CandidateSets, QueryExecutor,
                                 ShardedExecutor, _pack_mask, _pack_width)
from repro.core.metrics import dist_one_to_many
from repro.core.snapshot import LIMSSnapshot

N, D = 700, 5


@pytest.fixture(autouse=True)
def _restore_mode():
    """Tests turn observability on; put the mode back afterwards."""
    before = obs.obs_mode()
    yield
    obs.configure(before)


def _check_round_trip(mask: np.ndarray) -> None:
    B, P = mask.shape
    words = np.asarray(_pack_mask(jnp.asarray(mask)))
    W = _pack_width(P)
    assert words.shape == (B, W) and words.dtype == np.uint32
    assert W % 128 == 0 and 32 * W >= P
    for sets in (CandidateSets.from_packed(words),
                 CandidateSets.from_mask(mask)):
        assert len(sets) == B
        assert sets.offsets[0] == 0 and sets.offsets[-1] == mask.sum()
        for b in range(B):
            assert np.array_equal(sets[b], np.nonzero(mask[b])[0]), b
            assert np.all(np.diff(sets[b]) > 0)
        assert np.array_equal(sets.counts, mask.sum(axis=1))


def _mask(B: int, P: int, pattern: str, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if pattern == "random":
        return rng.random((B, P)) < 0.07
    mask = np.zeros((B, P), bool)
    if pattern == "rows":                 # all-False, all-True, mixed
        mask[B // 2:] = True
        mask[-1, ::3] = False
    elif pattern == "last":               # one bit, at slot P − 1
        mask[:, P - 1] = True
    return mask


@pytest.mark.parametrize("B,P,pattern", [
    (1, 1, "last"), (1, 4096, "random"), (1, 900, "rows"),
    (3, 31, "random"), (4, 900, "rows"), (2, 4097, "last"),
    (5, 8197, "random"), (6, 4096 * 3 + 32 * 5 + 1, "rows"),
    (64, 5000, "random"), (2, 100, "none")])
def test_pack_round_trip(B, P, pattern):
    """Pack on the device, decode on the host: every query's slots are
    ``np.nonzero`` of its row, ascending — P a multiple of neither 32
    nor 4096, all-False and all-True rows, a lone bit at P − 1, B = 1."""
    _check_round_trip(_mask(B, P, pattern))


@settings(max_examples=25, deadline=None, derandomize=True)
@given(B=st.integers(1, 6), P=st.integers(1, 9000),
       density=st.sampled_from([0.0, 0.002, 0.05, 0.5, 1.0]),
       seed=st.integers(0, 10_000))
def test_pack_round_trip_property(B, P, density, seed):
    """The round trip holds for any batch, plane width and density."""
    mask = np.random.default_rng(seed).random((B, P)) < density
    _check_round_trip(mask)


def test_from_mask_maps_gathered_columns():
    """A mask over a gathered subset of slots names each column's slot:
    the same lists as the full-width mask."""
    full = _mask(4, 300, "random", seed=3)
    cols = np.nonzero(full.any(axis=0))[0]
    a, b = CandidateSets.from_mask(full[:, cols], cols), \
        CandidateSets.from_mask(full)
    assert np.array_equal(a.offsets, b.offsets)
    assert np.array_equal(a.slots, b.slots)


def test_clusters_and_union_match_dense():
    """Distinct clusters per query and the union of the lists are what
    the dense mask gives."""
    B, K, n_max = 5, 7, 40
    mask = _mask(B, K * n_max, "random", seed=8)
    mask[1] = False
    sets = CandidateSets.from_mask(mask)
    want = mask.reshape(B, K, n_max).any(axis=-1).sum(axis=-1)
    assert np.array_equal(sets.clusters(n_max), want)
    assert np.array_equal(sets.union(K * n_max), mask.any(axis=0))


# ------------------------------------------------------- served batches
@functools.lru_cache(maxsize=1)
def _env():
    """A corpus whose last 200 rows repeat earlier ones, so equal
    distances tie in refinement's stable sort."""
    from repro.data.datasets import gauss_mix
    X = gauss_mix(N - 200, D, seed=21)
    X = np.concatenate([X, X[:200]])
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=5, m=3, n_rings=8)
    snap = LIMSSnapshot.build(ix)
    return X, ix, snap, {"resident": QueryExecutor(snap),
                         "sharded": ShardedExecutor(snap)}


def _queries(X, n_q, seed):
    rng = np.random.default_rng(seed)
    Q = X[rng.choice(len(X), n_q)] + rng.normal(0, 0.004, (n_q, D))
    Q[0] = X[5]                          # a query on a duplicated row
    return Q


@pytest.mark.parametrize("name", ["resident", "sharded"])
@pytest.mark.parametrize("k", [1, 6, 25])
def test_knn_matches_golden_and_host_with_ties(name, k):
    """kNN through the executor equals the golden driver bit for bit —
    ids in tie order too — and the host index in distances and ids."""
    X, ix, snap, exs = _env()
    ex = exs[name]
    Q = _queries(X, 6, seed=4)
    ids, ds = ex.knn_query_batch(Q, k)
    g_ids, g_ds = golden.knn_resident(exs["resident"], Q, k)
    assert np.array_equal(ids, g_ids) and np.array_equal(ds, g_ds)
    for b, q in enumerate(Q):
        h_ids, h_ds, _ = ix.knn_query(q, k)
        assert np.array_equal(ds[b], h_ds)
        tie = ds[b] == ds[b, -1]             # the k-th ball's edge may tie
        assert set(ids[b][~tie]) == set(h_ids[~tie])
    d0 = dist_one_to_many(Q[0], X, "l2")
    assert np.sum(d0 == 0.0) == 2            # the tie is there


@pytest.mark.parametrize("name", ["resident", "sharded"])
@pytest.mark.parametrize("compact", ["off", "on"])
def test_range_matches_golden_and_host_with_ties(name, compact,
                                                 monkeypatch):
    """Range through the executor equals the golden driver bit for bit,
    ids in slot order, and the host index as sets."""
    monkeypatch.setenv("REPRO_COMPACT", compact)
    X, ix, snap, exs = _env()
    ex = exs[name]
    Q = _queries(X, 6, seed=6)
    rs = np.array([float(np.quantile(dist_one_to_many(q, X, "l2"), 0.03))
                   for q in Q])
    got = ex.range_query_batch(Q, rs)
    for (ids, ds), (g_ids, g_ds), q, r in zip(
            got, golden.range_resident(exs["resident"], Q, rs), Q, rs):
        assert np.array_equal(ids, g_ids) and np.array_equal(ds, g_ds)
        h_ids, h_ds, _ = ix.range_query(q, r)
        assert set(map(int, ids)) == set(map(int, h_ids))
        assert np.array_equal(np.sort(ds), np.sort(h_ds))


@pytest.mark.parametrize("kind", ["knn", "range"])
def test_profile_reads_what_the_dense_mask_gives(kind, monkeypatch):
    """A batch's ``candidates_per_query`` and ``clusters_per_query``
    equal what its LIMS-certified dense mask gives (kNN: the loop's
    ``cand ∧ ball`` set, recomputed here by the same loop with the
    k-th-distance band taken out; range: the hits), recomputed with
    ``np.nonzero``; ``refined_per_query`` equals what the packed mask
    refinement read gives (kNN: the certified set cut to the band); and
    ``rank_err_ratio`` is computed over the same sampled slots the
    packed mask's union gives."""
    monkeypatch.setenv("REPRO_COMPACT", "off")
    X, ix, snap, exs = _env()
    obs.configure("on")
    ex = QueryExecutor(snap)
    masks = []
    pack = executor_mod._pack_mask

    def keep(mask):
        masks.append(np.asarray(mask))
        return pack(mask)
    monkeypatch.setattr(executor_mod, "_pack_mask", keep)
    certified = []
    loop, band = executor_mod._knn_loop_single, executor_mod._knn_band

    def loop_keeping_certified(*a, **kw):
        # the same loop, run eagerly with an all-True band
        monkeypatch.setattr(executor_mod, "_knn_band", lambda *_: True)
        try:
            certified.append(np.asarray(loop.__wrapped__(*a, **kw)[0]))
        finally:
            monkeypatch.setattr(executor_mod, "_knn_band", band)
        return loop(*a, **kw)
    monkeypatch.setattr(executor_mod, "_knn_loop_single",
                        loop_keeping_certified)
    gathered = []                         # slot arrays refinement rows
    rows = ex._refine_rows                # are read for, in call order

    def refine_rows(idx):
        gathered.append(np.array(idx))
        return rows(idx)
    monkeypatch.setattr(ex, "_refine_rows", refine_rows)
    Q = _queries(X, 5, seed=13)
    if kind == "knn":
        ex.knn_query_batch(Q, 7)
        (cert,) = certified
    else:
        ex.range_query_batch(Q, np.array([float(np.quantile(
            dist_one_to_many(q, X, "l2"), 0.05)) for q in Q]))
    (mask,) = masks
    if kind == "range":
        cert = mask
    assert not np.any(mask & ~cert)       # the band only takes rows away
    p = ex.last_profile
    B = len(Q)
    K, n_max, _ = snap.rids.shape
    nz = [np.nonzero(cert[b])[0] for b in range(B)]
    assert p.candidates_per_query == float(
        np.mean([len(i) for i in nz]))
    assert p.clusters_per_query == float(
        np.mean([len(np.unique(i // n_max)) for i in nz]))
    assert p.refined_per_query == float(mask.sum(axis=1).mean())
    if kind == "knn":                     # the band engaged
        assert p.refined_per_query < p.candidates_per_query
    # the rank-health sample: the deterministic stride over the in-ring
    # slots of the packed mask's union, the last rows the batch read
    sample = np.nonzero(mask.any(axis=0) &
                        np.asarray(snap.in_ring).reshape(-1))[0]
    n = ex._HEALTH_SAMPLE
    if sample.size > n:
        sample = sample[::sample.size // n][:n]
    assert sample.size and np.array_equal(gathered[-1], sample)
    assert p.rank_err_ratio is not None
