"""Memory-layout acceptance (DESIGN.md §13): the PR-10 roofline push.

Three independent byte-movers changed and every one must be invisible
in results:

  * **query-blocked kernels** — the xla lane's ``qb`` sub-blocking and
    the pallas point-major grid reorder move *bytes*, never math: every
    (bq, bp, qb) tiling of ``pdist`` / ``range_filter`` /
    ``pdist_rankeval`` is bit-identical;
  * **compacted candidate gather** — the resident range path's dense
    union-gather (``REPRO_COMPACT``) returns exactly the padded-slot
    path's hits, and executor results match bit-for-bit both ways;
  * **certified reduced-precision filter plane** — with
    ``REPRO_ROWS_DTYPE=bf16|f16`` the ε-widened filters keep every true
    result (property-tested) and final query results stay bitwise
    identical to the f32 baseline through the compiled kNN loop and the
    sharded executor (the 4-fake-device CI leg runs the real
    ``shard_map`` path through this file).
"""
import functools

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import LIMSIndex, MetricSpace
from repro.core.executor import QueryExecutor, ShardedExecutor, _bucket_size
from repro.core.metrics import dist_one_to_many
from repro.core.planner import _BALL_ABS, _R_REL
from repro.core import planner as planner_mod
from repro.core.snapshot import LIMSSnapshot, lp_quant_eps
from repro.kernels import ops

N, D = 1200, 6


@functools.lru_cache(maxsize=1)
def _env():
    # a single Gaussian blob k-center-clusters unevenly, so the padded
    # slot array carries real slack over the live rows — the layout the
    # compacted gather exists for (the union candidate set sits well
    # under the n_max-padded slot count)
    rng = np.random.default_rng(23)
    X = rng.normal(size=(N, D))
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=8, m=3, n_rings=10)
    return X, ix


def _queries(X, n_q, seed=2, scale=0.004):
    rng = np.random.default_rng(seed)
    return X[rng.choice(len(X), n_q)] + rng.normal(0, scale, (n_q, D))


def _radii(X, Q, sel=0.02):
    return np.array([float(np.quantile(dist_one_to_many(q, X, "l2"), sel))
                     for q in Q])


def _run_queries(ex, X):
    """One range + one kNN batch; returns comparable result tuples."""
    Q = _queries(X, 5, seed=7)
    rr = ex.range_query_batch(Q, _radii(X, Q))
    kk = ex.knn_query_batch(Q, 9)
    return rr, kk


def _assert_same(a, b):
    for (ai, ad), (bi, bd) in zip(a[0], b[0]):
        assert np.array_equal(ai, bi)
        assert np.array_equal(ad, bd)
    assert np.array_equal(a[1][0], b[1][0])
    assert np.array_equal(a[1][1], b[1][1])


# ------------------------------------------------- compacted gather
def test_compact_range_bitwise_identical(monkeypatch):
    """REPRO_COMPACT=on (the default) gathers the union candidate rows
    into a pow2 bucket and must return exactly the padded-slot path's
    results; ``last_compact`` records the gather it ran."""
    X, ix = _env()
    snap = LIMSSnapshot.build(ix)
    monkeypatch.setenv("REPRO_COMPACT", "off")
    ex_full = QueryExecutor(snap)
    base = _run_queries(ex_full, X)
    assert ex_full.last_compact is None
    monkeypatch.setenv("REPRO_COMPACT", "on")
    ex_c = QueryExecutor(snap)
    got = _run_queries(ex_c, X)
    _assert_same(got, base)
    lc = ex_c.last_compact
    assert lc is not None
    assert 0 < lc["slots"] <= lc["bucket"] <= lc["n_slots"]
    assert lc["bucket"] == _bucket_size(lc["slots"])
    assert lc["bucket"] & (lc["bucket"] - 1) == 0        # power of two


def test_compact_falls_back_when_union_large(monkeypatch):
    """A union past the payoff bound streams the full padded array —
    same results, ``last_compact`` None, plan reports no gather."""
    X, ix = _env()
    snap = LIMSSnapshot.build(ix)
    monkeypatch.setenv("REPRO_COMPACT", "on")
    ex = QueryExecutor(snap)
    base = _run_queries(ex, X)
    monkeypatch.setattr(planner_mod, "_COMPACT_MAX_FRAC", 0.0)
    got = _run_queries(ex, X)
    _assert_same(got, base)
    assert ex.last_compact is None
    Q = _queries(X, 3, seed=5)
    plan = ex.planner.plan_range(Q, _radii(X, Q))
    assert plan.compact_slots() is None
    assert plan.compact_slots() is None                  # cached decision


def test_compact_slots_plan_contract():
    """The plan's gather is the sorted union of its certified mask and
    is cached with the mask it derives from."""
    X, ix = _env()
    ex = QueryExecutor(LIMSSnapshot.build(ix))
    Q = _queries(X, 4, seed=9)
    plan = ex.planner.plan_range(Q, _radii(X, Q))
    slots = plan.compact_slots()
    assert slots is not None and slots.size
    assert np.array_equal(slots, np.nonzero(plan.mask.any(axis=0))[0])
    assert plan.compact_slots() is slots                 # cached


# ------------------------------------------- query-blocked tilings
@pytest.mark.parametrize("metric", ["sql2", "l1", "linf"])
def test_xla_query_blocked_pdist_tilings_bit_identical(metric):
    """Every (bq, bp, qb) tiling of the xla-lane kernels reorders byte
    movement only — outputs are bit-identical (tiles never change
    per-pair math)."""
    if jax.default_backend() != "cpu":
        pytest.skip("xla lane is the CPU compiled path")
    from repro.kernels.xla import pdist_xla, range_filter_xla
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    p = jnp.asarray(rng.normal(size=(384, 8)), jnp.float32)
    r = jnp.asarray(rng.uniform(1.0, 3.0, 32), jnp.float32)
    base = pdist_xla(q, p, metric, bq=32, bp=384, qb=0)
    mbase = range_filter_xla(q, p, r, bq=32, bp=384, qb=0)
    for bq in (16, 32):
        for bp in (128, 384):
            for qb in (0, 8, 16):
                d = pdist_xla(q, p, metric, bq=bq, bp=bp, qb=qb)
                assert np.array_equal(np.asarray(d), np.asarray(base)), \
                    (metric, bq, bp, qb)
                m = range_filter_xla(q, p, r, bq=bq, bp=bp, qb=qb)
                assert np.array_equal(np.asarray(m), np.asarray(mbase))


def test_xla_fused_bb_blocking_bit_identical():
    if jax.default_backend() != "cpu":
        pytest.skip("xla lane is the CPU compiled path")
    from repro.kernels.xla import pdist_rankeval_xla
    rng = np.random.default_rng(4)
    G, B, d, C = 16, 32, 8, 9
    q = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    piv = jnp.asarray(rng.normal(size=(G, d)), jnp.float32)
    coef = jnp.asarray(rng.normal(size=(G, C)), jnp.float32)
    lo = jnp.zeros(G, jnp.float32)
    hi = jnp.full(G, 4.0, jnp.float32)
    n = jnp.full(G, 64.0, jnp.float32)
    rg = jnp.asarray(rng.uniform(0.5, 1.5, B), jnp.float32)
    base = pdist_rankeval_xla(q, piv, coef, lo, hi, n, rg,
                              n_rings=10, bg=G, bb=B)
    for bg in (8, 16):
        for bb in (8, 16, 32):
            out = pdist_rankeval_xla(q, piv, coef, lo, hi, n, rg,
                                     n_rings=10, bg=bg, bb=bb)
            for a, b in zip(out, base):
                assert np.array_equal(np.asarray(a), np.asarray(b)), (bg, bb)


def test_pallas_point_major_grid_matches_reference():
    """The point-major grid reorder in the pallas kernels (point tile
    resident across query tiles) leaves per-cell outputs untouched."""
    from repro.kernels.pdist import pdist_pallas
    from repro.kernels.range_filter import range_filter_pallas
    from repro.kernels.ref import pdist_ref
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    p = jnp.asarray(rng.normal(size=(256, 8)), jnp.float32)
    r = jnp.asarray(rng.uniform(1.0, 3.0, 16), jnp.float32)
    ref = np.asarray(pdist_ref(q, p))
    base = np.asarray(pdist_pallas(q, p, bq=16, bp=256, interpret=True))
    mbase = range_filter_pallas(q, p, r, bq=16, bp=256, interpret=True)
    mbase = np.asarray(mbase, bool)
    np.testing.assert_allclose(base, ref, rtol=1e-5, atol=1e-5)
    for bq, bp in ((8, 128), (16, 128), (8, 64), (8, 256)):
        d = pdist_pallas(q, p, bq=bq, bp=bp, interpret=True)
        np.testing.assert_array_equal(np.asarray(d), base)
        m = range_filter_pallas(q, p, r, bq=bq, bp=bp, interpret=True)
        assert np.array_equal(np.asarray(m, bool), mbase)


# -------------------------------------- reduced-precision filter plane
@pytest.mark.parametrize("dtype", ["bf16", "f16"])
def test_lp_plane_results_bitwise_identical(monkeypatch, dtype):
    """The ε-certified lp filter plane changes first-pass byte traffic
    only: range and kNN results are bitwise identical to the f32
    baseline through the compiled kNN loop."""
    X, ix = _env()
    monkeypatch.delenv("REPRO_ROWS_DTYPE", raising=False)
    base_snap = LIMSSnapshot.build(ix)
    base = _run_queries(QueryExecutor(base_snap), X)
    monkeypatch.setenv("REPRO_ROWS_DTYPE", dtype)
    snap = LIMSSnapshot.build(ix)
    assert snap.rows_lp is not None and snap.lp_eps > 0.0
    ex = QueryExecutor(snap)
    got = _run_queries(ex, X)
    _assert_same(got, base)
    assert ex.last_knn["driver"] == "loop"


def test_lp_plane_sharded_and_compact_identical(monkeypatch):
    """bf16 plane + compaction on the sharded executor (real shard_map
    on the 4-fake-device CI leg; single-device degradation otherwise)
    still returns the f32 baseline bit-for-bit — the sharded filter
    keeps the exact f32 plane, resident compaction composes with the
    lp plane."""
    X, ix = _env()
    monkeypatch.delenv("REPRO_ROWS_DTYPE", raising=False)
    base = _run_queries(QueryExecutor(LIMSSnapshot.build(ix)), X)
    monkeypatch.setenv("REPRO_ROWS_DTYPE", "bf16")
    monkeypatch.setenv("REPRO_COMPACT", "on")
    snap = LIMSSnapshot.build(ix)
    _assert_same(_run_queries(ShardedExecutor(snap), X), base)


def test_lp_plane_off_is_default_and_plane_absent(monkeypatch):
    monkeypatch.delenv("REPRO_ROWS_DTYPE", raising=False)
    X, ix = _env()
    snap = LIMSSnapshot.build(ix)
    assert snap.rows_lp is None and snap.lp_eps == 0.0
    rows, eps = snap.filter_rows()
    assert rows is snap.rows and eps == 0.0


def _lp_never_drops(seed: int) -> None:
    """Core ε-certification property: for rows quantized to bf16,
    d(q, x_lp) ≤ d(q, x) + eps, so the ε-widened ball keeps every true
    result of the exact ball (the device filter additionally carries
    the f32 guard bands on top of eps)."""
    rng = np.random.default_rng(seed)
    n, d = int(rng.integers(4, 200)), int(rng.integers(1, 12))
    scale = 10.0 ** rng.integers(-3, 4)
    rows = rng.normal(scale=scale, size=(n, d))
    rows32 = jnp.asarray(rows, jnp.float32)
    lp = rows32.astype(jnp.bfloat16)
    eps = lp_quant_eps(rows32, lp, "l2")
    q = rng.normal(scale=scale, size=d)
    d_true = dist_one_to_many(q, rows, "l2")
    d_lp = np.sqrt(((q - np.asarray(lp, np.float64)) ** 2).sum(axis=1))
    r = float(np.quantile(d_true, rng.uniform(0.05, 0.95)))
    true_ball = d_true <= r
    widened = d_lp <= r + eps
    assert not (true_ball & ~widened).any(), seed


def test_lp_eps_widened_filter_never_drops_sweep():
    for seed in range(200):
        _lp_never_drops(seed)


@settings(max_examples=200, deadline=None)
@given(st.integers(min_value=0, max_value=10_000_000))
def test_lp_eps_widened_filter_never_drops_property(seed):
    _lp_never_drops(seed)


def test_lp_eps_guard_band_end_to_end(monkeypatch):
    """Device-path version of the property: the executor's ε-widened
    ball filter mask is a superset of the exact in-ball set for every
    query in a batch."""
    X, ix = _env()
    monkeypatch.setenv("REPRO_ROWS_DTYPE", "bf16")
    snap = LIMSSnapshot.build(ix)
    ex = QueryExecutor(snap)
    Q = _queries(X, 6, seed=13)
    rs = _radii(X, Q, sel=0.05)
    ball = np.asarray(ex._ball_filter(
        jnp.asarray(Q, jnp.float32), jnp.asarray(rs, jnp.float32)))
    rows = snap.rows_np.reshape(-1, D)
    valid = snap.valid_np
    for b, q in enumerate(Q):
        d_true = np.sqrt(((q - rows) ** 2).sum(axis=1))
        inside = (d_true <= rs[b]) & valid
        assert not (inside & ~ball[b]).any()
