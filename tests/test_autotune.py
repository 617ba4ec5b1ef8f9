"""Compiled hot path: lane dispatch, tile autotuner, fused plan kernel.

Pins the PR's correctness claims:

* the compiled XLA lane (``REPRO_INTERPRET=off`` on CPU) computes the
  same results as the interpret validation lane for every kernel stage;
* the fused ``pdist_rankeval`` launch is *bit-identical* to the staged
  pdist→rankeval pair, at the ops level and through
  ``planner.plan_arrays``, in both lanes;
* tile policy always yields aligned tiles that divide the padded
  operands (property-tested), whatever the tuning table says;
* a corrupted tuning-cache entry is rejected on load, served as a miss,
  and replaced by a valid entry under ``REPRO_AUTOTUNE=force``
  (round-trip through the JSON file);
* the env-knob registry rejects unknown knobs and invalid values with
  actionable errors, and declares exactly the knobs the package reads.
"""
import functools
import json

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro import env
from repro.kernels import autotune, ops

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

_TINY = {"q": 16, "p": 64, "d": 8}       # fast enough to tune in-test


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """Point the tuner at a private cache file and drop the in-memory
    table around the test, so nothing leaks to ~/.cache or across
    tests."""
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    autotune._reset()
    yield path
    autotune._reset()


def _operands(seed=0, nq=37, npts=201, d=9):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((nq, d)).astype(np.float32)
    p = rng.standard_normal((npts, d)).astype(np.float32)
    r = np.abs(rng.standard_normal(nq)).astype(np.float32) + 0.5
    return q, p, r


def _rank_operands(seed=1, g=13, b=200, c=9):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.0, 2.0, (g, b)).astype(np.float32)
    coef = (rng.standard_normal((g, c)) * 10).astype(np.float32)
    lo = np.zeros(g, np.float32)
    hi = np.full(g, 2.0, np.float32)
    n = np.full(g, 500.0, np.float32)
    return x, coef, lo, hi, n


# ------------------------------------------------------------ env registry
def test_env_unknown_knob_raises():
    with pytest.raises(KeyError):
        env.get("REPRO_NO_SUCH_KNOB")


def test_env_invalid_value_lists_valid_ones(monkeypatch):
    monkeypatch.setenv("REPRO_STORAGE", "bogus")
    with pytest.raises(ValueError, match="paged"):
        env.get("REPRO_STORAGE")
    monkeypatch.setenv("REPRO_AUTOTUNE", "sometimes")
    with pytest.raises(ValueError, match="force"):
        env.get("REPRO_AUTOTUNE")


def test_env_empty_and_unset_mean_default(monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    assert env.get("REPRO_AUTOTUNE") == "on"
    monkeypatch.setenv("REPRO_AUTOTUNE", "")
    assert env.get("REPRO_AUTOTUNE") == "on"
    monkeypatch.setenv("REPRO_PREFETCH", "ASYNC")      # case-insensitive
    assert env.get("REPRO_PREFETCH") == "async"


def test_env_describe_covers_all_knobs():
    text = env.describe()
    for name in ("REPRO_INTERPRET", "REPRO_AUTOTUNE", "REPRO_TUNE_CACHE",
                 "REPRO_PREFETCH", "REPRO_STORAGE"):
        assert name in text


def test_env_every_knob_declared_is_read_and_every_read_declared():
    """Each knob in ``env._KNOBS`` is named by some module of the
    package besides the registry, and every ``REPRO_*`` name a module
    there spells out is declared: a knob nothing reads, or a read of an
    undeclared one, fails here and not at serve time."""
    import ast
    import pathlib
    import re

    registry = pathlib.Path(env.__file__)
    named = set()
    for path in registry.parent.rglob("*.py"):
        if path == registry:
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                    and re.fullmatch(r"REPRO_[A-Z0-9_]+", node.value)):
                named.add(node.value)
    declared = {k.name for k in env._KNOBS}
    assert not declared - named, f"never read: {sorted(declared - named)}"
    assert not named - declared, f"undeclared: {sorted(named - declared)}"


# ------------------------------------------------------- tile properties
@settings(max_examples=30, deadline=None)
@given(nq=st.integers(1, 300), npts=st.integers(1, 3000),
       bq=st.sampled_from([None, 8, 32, 128, 256]),
       bp=st.sampled_from([None, 128, 512, 4096]),
       metric=st.sampled_from(["sql2", "l1", "linf"]))
def test_local_blocks_divide_padded_operands(nq, npts, bq, bp, metric):
    """Whatever the policy (heuristics or tuning table) picks, the tiles
    are sublane-aligned and divide the padded operand exactly — the
    invariant every lane's launch grid depends on."""
    tbq, tbp = ops.local_blocks(nq, npts, bq=bq, bp=bp, metric=metric)
    assert tbq > 0 and tbp > 0
    assert tbq % 8 == 0 and tbp % 8 == 0
    padded_q = -(-nq // tbq) * tbq
    padded_p = -(-npts // tbp) * tbp
    assert padded_q % tbq == 0 and padded_p % tbp == 0
    # tiles never exceed the padded operand (no degenerate over-tiling)
    assert tbq <= max(-(-nq // 8) * 8, tbq)


def test_tiles_for_returns_validated_tiles(tune_cache, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    t = autotune.tiles_for("pdist", "sql2", _TINY)
    assert set(t) == {"bq", "bp", "qb"}
    assert all(isinstance(v, int) and v > 0 and v % 8 == 0
               for v in t.values())
    # the entry landed in the JSON file too
    data = json.loads(tune_cache.read_text())
    assert data["version"] == autotune.SCHEMA_VERSION
    [(key, ent)] = list(data["entries"].items())
    assert key.startswith("xla-cpu/pdist/sql2/") or "/pdist/" in key
    assert ent["tiles"] == t


def test_autotune_off_is_a_miss(tune_cache, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert autotune.tiles_for("pdist", "sql2", _TINY) is None


def test_autotune_on_never_tunes_implicitly(tune_cache, monkeypatch):
    """Default mode is lookup-only: a miss stays a miss (no surprise
    multi-second tuning runs inside a serving path)."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    assert autotune.tiles_for("pdist", "sql2", _TINY) is None
    assert not tune_cache.exists()


def test_corrupted_cache_rejected_then_retuned(tune_cache, monkeypatch):
    """Corrupted entries must not crash loading or leak into launches:
    they are dropped on load (miss), and ``force`` replaces them with a
    freshly tuned valid entry — full round-trip through the file."""
    backend = "xla-cpu"
    bd = {k: autotune.bucket(v) for k, v in _TINY.items()}
    key = autotune._key(backend, "pdist", "sql2", bd)
    v = autotune.SCHEMA_VERSION
    corrupt = {
        key: {"tiles": {"bq": 12, "bp": 64, "qb": 8}, "us": 1.0, "v": v},
        key + "x": {"tiles": {"bq": 8}, "us": 1.0, "v": v},       # names
        autotune._key(backend, "rankeval", None, {"g": 8, "b": 8, "c": 8}):
            {"tiles": {"bg": 8, "bb": "all"}, "us": 1.0, "v": v},  # type
        autotune._key(backend, "range_filter", "sql2", bd):
            {"tiles": {"bq": 8, "bp": 8, "qb": 8}, "us": 1.0,
             "v": 1},   # stale schema version (pre-qb)
    }
    tune_cache.write_text(json.dumps(
        {"version": autotune.SCHEMA_VERSION, "entries": corrupt}))
    autotune._reset()
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    assert autotune.tiles_for("pdist", "sql2", _TINY) is None
    assert autotune.tiles_for("range_filter", "sql2", _TINY) is None
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    t = autotune.tiles_for("pdist", "sql2", _TINY)
    assert t["bq"] % 8 == 0 and t["bp"] % 8 == 0
    # the rewritten file now carries the valid entry under the same key
    data = json.loads(tune_cache.read_text())
    assert data["entries"][key]["tiles"] == t
    assert autotune._valid_entry(backend, "pdist", data["entries"][key])
    # and a fresh process (cache drop) sees it as a plain hit
    autotune._reset()
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    assert autotune.tiles_for("pdist", "sql2", _TINY) == t


def test_truncated_cache_file_is_a_miss(tune_cache, monkeypatch):
    tune_cache.write_text('{"version": 1, "entr')      # torn write
    autotune._reset()
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    assert autotune.tiles_for("pdist", "sql2", _TINY) is None


def test_bucket_is_pow2_floor8():
    assert [autotune.bucket(v) for v in (1, 8, 9, 64, 65, 4096)] == \
        [8, 8, 16, 64, 128, 4096]
    # bucketing is why nearby shapes share one entry
    a = autotune._key("xla-cpu", "pdist", "sql2",
                      {k: autotune.bucket(v)
                       for k, v in {"q": 60, "p": 4000, "d": 8}.items()})
    b = autotune._key("xla-cpu", "pdist", "sql2",
                      {k: autotune.bucket(v)
                       for k, v in {"q": 64, "p": 4096, "d": 8}.items()})
    assert a == b


# ----------------------------------------------------- lane equivalence
def _lane(monkeypatch, value):
    monkeypatch.setenv("REPRO_INTERPRET", value)
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")


@pytest.mark.parametrize("metric", ["sql2", "l1", "linf"])
def test_xla_lane_matches_interpret_pdist(monkeypatch, metric):
    q, p, _ = _operands()
    _lane(monkeypatch, "on")
    a = np.asarray(ops.pdist(q, p, metric))
    _lane(monkeypatch, "off")
    b = np.asarray(ops.pdist(q, p, metric))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_xla_lane_matches_interpret_rankeval(monkeypatch):
    x, coef, lo, hi, n = _rank_operands()
    _lane(monkeypatch, "on")
    rk_a, rid_a = ops.rankeval(x, coef, lo, hi, n)
    _lane(monkeypatch, "off")
    rk_b, rid_b = ops.rankeval(x, coef, lo, hi, n)
    assert np.array_equal(np.asarray(rk_a), np.asarray(rk_b))
    assert np.array_equal(np.asarray(rid_a), np.asarray(rid_b))


def test_xla_lane_matches_interpret_range_filter(monkeypatch):
    q, p, r = _operands(seed=3)
    _lane(monkeypatch, "on")
    m_a = ops.range_filter(q, p, r)
    _lane(monkeypatch, "off")
    m_b = ops.range_filter(q, p, r)
    assert np.array_equal(np.asarray(m_a), np.asarray(m_b))


# -------------------------------------------------- fused vs staged
def _fused_inputs(seed=5, B=21, G=13, d=9, c=9):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, d)).astype(np.float32)
    piv = rng.standard_normal((G, d)).astype(np.float32)
    coef = (rng.standard_normal((G, c)) * 10).astype(np.float32)
    lo = np.zeros(G, np.float32)
    hi = np.full(G, 8.0, np.float32)
    n = np.full(G, 500.0, np.float32)
    rg = np.abs(rng.standard_normal(B)).astype(np.float32)
    return q, piv, coef, lo, hi, n, rg


@pytest.mark.parametrize("lane", ["on", "off"])
def test_fused_bitwise_matches_staged_ops(monkeypatch, lane):
    """The fused launch is bit-identical to the staged pair *within a
    lane* — same jnp ops in the same order on the same blocks — so
    enabling fusion can never change a plan."""
    _lane(monkeypatch, lane)
    q, piv, coef, lo, hi, n, rg = _fused_inputs()
    B = q.shape[0]
    dq_f, lo_f, hi_f = ops.pdist_rankeval(q, piv, coef, lo, hi, n, rg)
    dq_s = jnp.sqrt(jnp.maximum(ops.pdist(q, piv), 0.0))
    xb = jnp.concatenate([(dq_s - rg[:, None]).T,
                          (dq_s + rg[:, None]).T], axis=1)
    rank, _ = ops.rankeval(xb, coef, lo, hi, n)
    assert np.array_equal(np.asarray(dq_f), np.asarray(dq_s))
    assert np.array_equal(np.asarray(lo_f), np.asarray(rank)[:, :B])
    assert np.array_equal(np.asarray(hi_f), np.asarray(rank)[:, B:])


@functools.lru_cache(maxsize=1)
def _snapshot_env():
    from repro.core import LIMSIndex, MetricSpace
    from repro.core.snapshot import LIMSSnapshot
    from repro.data.datasets import gauss_mix
    X = gauss_mix(900, 6, seed=7)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=4, m=3, n_rings=8)
    return X, LIMSSnapshot.build(ix)


@pytest.mark.parametrize("lane", ["on", "off"])
def test_plan_arrays_fused_bit_identity(monkeypatch, lane):
    """plan_arrays(fused=True) == plan_arrays(fused=False) bitwise —
    candidate mask and TriPrune routing — in both lanes.  This is the
    pin that lets dispatch turn fusion on by default on compiled
    lanes."""
    from repro.core.planner import plan_arrays
    _lane(monkeypatch, lane)
    X, snap = _snapshot_env()
    rng = np.random.default_rng(8)
    qf = jnp.asarray(X[rng.choice(len(X), 6)]
                     + rng.normal(0, 0.004, (6, X.shape[1])), jnp.float32)
    rf = jnp.asarray(rng.uniform(0.05, 0.5, 6), jnp.float32)
    cand_s, alive_s = plan_arrays(qf, rf, snap, snap.n_rings, fused=False)
    cand_f, alive_f = plan_arrays(qf, rf, snap, snap.n_rings, fused=True)
    assert np.array_equal(np.asarray(cand_s), np.asarray(cand_f))
    assert np.array_equal(np.asarray(alive_s), np.asarray(alive_f))


def test_tuned_tiles_change_grid_not_values(tune_cache, monkeypatch):
    """End-to-end: tune a bucket, then run the kernel with the table on
    vs off in the compiled lane — identical results, only the launch
    shape differs."""
    monkeypatch.setenv("REPRO_INTERPRET", "off")
    q, p, r = _operands(seed=9, nq=16, npts=64, d=8)
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    assert autotune.tiles_for("pdist", "sql2", _TINY) is not None
    a = np.asarray(ops.pdist(q, p))
    m_a = ops.range_filter(q, p, r)
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    b = np.asarray(ops.pdist(q, p))
    m_b = ops.range_filter(q, p, r)
    assert np.array_equal(a, b)
    assert np.array_equal(np.asarray(m_a), np.asarray(m_b))
