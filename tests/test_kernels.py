"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


# Lanes: "on" = pallas interpret (the default CPU validation lane),
# "off" = the compiled lane (jitted-XLA on CPU, pallas_call on TPU/GPU).
# Running the oracle comparisons under both pins the compiled hot path
# against the references directly, not just against the interpret lane.
LANES = ["on", "off"]


@pytest.fixture(params=LANES)
def lane(request, monkeypatch):
    monkeypatch.setenv("REPRO_INTERPRET", request.param)
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    return request.param


# ---------------------------------------------------------------- pdist
@pytest.mark.parametrize("metric", ["sql2", "l1", "linf"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nq,npts,d", [(64, 128, 8), (137, 301, 33),
                                       (1, 257, 128), (128, 128, 4)])
def test_pdist_matches_ref(lane, metric, dtype, nq, npts, d):
    q = _rand((nq, d), dtype, 1)
    p = _rand((npts, d), dtype, 2)
    out = ops.pdist(q, p, metric)
    expect = ref.pdist_ref(q, p, metric)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol * d)


@settings(max_examples=25, deadline=None)
@given(nq=st.integers(1, 200), npts=st.integers(1, 300),
       d=st.integers(1, 64),
       metric=st.sampled_from(["sql2", "l1", "linf"]))
def test_pdist_property(nq, npts, d, metric):
    q = _rand((nq, d), jnp.float32, nq)
    p = _rand((npts, d), jnp.float32, npts + 1)
    out = np.asarray(ops.pdist(q, p, metric))
    expect = np.asarray(ref.pdist_ref(q, p, metric))
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-3)
    assert (out >= -1e-6).all()            # non-negativity


# -------------------------------------------------------------- rankeval
@pytest.mark.parametrize("g,b,c", [(8, 128, 5), (13, 200, 9), (1, 1, 21),
                                   (32, 512, 2)])
def test_rankeval_matches_ref(lane, g, b, c):
    coef = _rand((g, c), jnp.float32, 3) * 10
    x = jax.random.uniform(KEY, (g, b), minval=0.0, maxval=2.0)
    lo = jnp.zeros(g)
    hi = jnp.full(g, 2.0)
    n = jnp.full(g, 500.0)
    rk, rid = ops.rankeval(x, coef, lo, hi, n, n_rings=20)
    rk2, rid2 = ref.rankeval_ref(x, coef, lo, hi, n, n_rings=20)
    # rint on fp32 can differ by 1 ulp at .5 boundaries
    assert int(jnp.abs(rk - rk2).max()) <= 1
    assert int(jnp.abs(rid - rid2).max()) <= 1


def test_rankeval_block_overrides_match_defaults():
    """Explicit bg/bb tile overrides (the autotune hook) change only the
    launch grid, never the values."""
    g, b, c = 13, 200, 9
    coef = _rand((g, c), jnp.float32, 3) * 10
    x = jax.random.uniform(KEY, (g, b), minval=0.0, maxval=2.0)
    lo = jnp.zeros(g)
    hi = jnp.full(g, 2.0)
    n = jnp.full(g, 500.0)
    rk, rid = ops.rankeval(x, coef, lo, hi, n, n_rings=20)
    rk2, rid2 = ops.rankeval(x, coef, lo, hi, n, n_rings=20, bg=8, bb=64)
    assert np.array_equal(np.asarray(rk), np.asarray(rk2))
    assert np.array_equal(np.asarray(rid), np.asarray(rid2))


def test_rankeval_matches_host_model():
    """Kernel model inference == the host PolyRankModel used by LIMS."""
    from repro.core.rankmodel import PolyRankModel
    rng = np.random.default_rng(0)
    col = np.sort(rng.gamma(2.0, 1.0, size=1000))
    model = PolyRankModel.fit(col, degree=8)
    xs = rng.uniform(col[0], col[-1], size=128)
    want = np.array([model.predict_scalar(float(v)) for v in xs])
    coef = np.zeros((1, len(model.coef)), np.float32)
    coef[0, :] = model.coef
    rk, _ = ops.rankeval(xs[None, :].astype(np.float32), coef,
                         np.array([model.lo], np.float32),
                         np.array([model.hi], np.float32),
                         np.array([model.n], np.float32))
    got = np.asarray(rk)[0]
    assert np.abs(got - want).max() <= 1   # fp32 vs fp64 rounding


# ----------------------------------------------------------- range_filter
@pytest.mark.parametrize("nq,npts,d", [(64, 256, 16), (137, 301, 33)])
def test_range_filter_matches_ref(lane, nq, npts, d):
    q = _rand((nq, d), jnp.float32, 5)
    p = _rand((npts, d), jnp.float32, 6)
    r = jax.random.uniform(KEY, (nq,), minval=1.0, maxval=8.0)
    mask = ops.range_filter(q, p, r)
    d2 = np.asarray(ref.pdist_ref(q, p, "sql2"))
    r2 = np.asarray(r) ** 2
    inner = d2 <= r2[:, None] - 1e-3
    outer = d2 <= r2[:, None] + 1e-3
    m = np.asarray(mask).astype(bool)
    assert m.shape == (nq, npts)
    assert (inner <= m).all() and (m <= outer).all()


# -------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hk,sq,sk,d,causal", [
    (2, 8, 2, 256, 256, 64, True),
    (1, 4, 4, 100, 100, 32, True),
    (2, 8, 4, 128, 384, 64, False),
    (1, 2, 1, 64, 300, 16, False),
])
def test_flash_attention_matches_ref(dtype, b, hq, hk, sq, sk, d, causal):
    q = _rand((b, hq, sq, d), dtype, 7)
    k = _rand((b, hk, sk, d), dtype, 8)
    v = _rand((b, hk, sk, d), dtype, 9)
    out = ops.flash_attention(q, k, v, causal=causal)
    expect = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_rows_sum_preserved():
    """softmax rows sum to 1 ⇒ attention of constant V returns constant."""
    b, hq, hk, s, d = 1, 4, 2, 128, 32
    q = _rand((b, hq, s, d), jnp.float32, 1)
    k = _rand((b, hk, s, d), jnp.float32, 2)
    v = jnp.ones((b, hk, s, d), jnp.float32) * 3.5
    out = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), 3.5, rtol=1e-5)
