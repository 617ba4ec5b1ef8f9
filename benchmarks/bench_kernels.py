"""Kernel-stage lane comparison: interpret vs compiled-XLA, static vs
autotuned tiles, staged vs fused.

For each kernel stage (pdist, rankeval, range_filter) this bench times:

* ``interpret``     — Pallas interpret mode with today's heuristics
                      (the validation lane every prior BENCH number
                      used);
* ``xla-static``    — the compiled XLA-CPU lane (``REPRO_INTERPRET=off``)
                      with the static heuristic tiles
                      (``REPRO_AUTOTUNE=off``);
* ``xla-autotuned`` — the compiled lane with tiles from the tuning table,
                      tuned in-process for these exact shape buckets.

Acceptance (asserted here, recorded in ``BENCH_kernels.json``): the
autotuned tiles beat the static-heuristic tiles on >= 2 of the 3 stages.
The static tile is itself a candidate in the tuner's grid, so a loss can
only come from measurement noise — the assertion uses fresh *paired*
interleaved timings, not the tuner's own numbers.

Also measured: the fused ``pdist_rankeval`` plan stage against its
staged two-launch equivalent (same lane, both ways), the per-stage
roofline report (``repro.roofline.pipeline``) over a real snapshot,
and the filter-plane bytes-per-query ledger (DESIGN.md §13): the
padded-f32 baseline against the compacted candidate gather and the
certified bf16 plane, with the ≥ 2x traffic-reduction acceptance and
the results of all three layouts asserted identical inline.

Writes ``BENCH_kernels.json`` itself (structured payload; ``run.py``
passes slug ``None`` for this section), and still prints the historical
ref-path rows for continuity with earlier BENCH files.
"""
from __future__ import annotations

import os
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune, ref

from .common import QUICK, emit, write_json

# operand shapes per stage, full vs QUICK (keyed by QUICK flag)
_SHAPES = {
    False: {"q": 256, "p": 65_536, "d": 32, "g": 64, "b": 4_096, "c": 9},
    True: {"q": 128, "p": 4_096, "d": 16, "g": 64, "b": 512, "c": 9},
}


@contextmanager
def _env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _time(fn, reps=3):
    jax.block_until_ready(fn())           # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _paired(fn_a, fn_b, reps=3):
    """Interleaved best-of pair — the same discipline bench_batch uses
    for the golden bars, so a one-off scheduler hiccup hits both sides
    equally instead of deciding the comparison."""
    jax.block_until_ready(fn_a())
    jax.block_until_ready(fn_b())
    best_a = best_b = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def _stage_thunks(sh):
    """(name, thunk) per stage; ops resolves the lane and tiles from the
    env at every call, so the same thunk times any lane."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    q = rng.standard_normal((sh["q"], sh["d"])).astype(np.float32)
    p = rng.standard_normal((sh["p"], sh["d"])).astype(np.float32)
    r = np.full((sh["q"],), 1.0, np.float32)
    x = (rng.standard_normal((sh["g"], sh["b"])) * 2).astype(np.float32)
    coef = (rng.standard_normal((sh["g"], sh["c"])) * 5).astype(np.float32)
    lo = np.zeros(sh["g"], np.float32)
    hi = np.ones(sh["g"], np.float32) * 4
    n = np.full(sh["g"], 1e5, np.float32)
    return [
        ("pdist", lambda: ops.pdist(q, p)),
        ("rankeval", lambda: ops.rankeval(x, coef, lo, hi, n)[0]),
        ("range_filter", lambda: ops.range_filter(q, p, r)),
    ]


def _fused_thunks(sh):
    """(staged, fused) thunks computing the same plan quantities."""
    from repro.kernels import ops
    rng = np.random.default_rng(1)
    B, G = min(sh["q"], 256), sh["g"]
    q = rng.standard_normal((B, sh["d"])).astype(np.float32)
    piv = rng.standard_normal((G, sh["d"])).astype(np.float32)
    coef = (rng.standard_normal((G, sh["c"])) * 5).astype(np.float32)
    lo = np.zeros(G, np.float32)
    hi = np.ones(G, np.float32) * 4
    n = np.full(G, 1e5, np.float32)
    rg = np.abs(rng.standard_normal(B)).astype(np.float32)

    def staged():
        dq = jnp.sqrt(jnp.maximum(ops.pdist(q, piv), 0.0))
        xb = jnp.concatenate([(dq - rg[:, None]).T,
                              (dq + rg[:, None]).T], axis=1)
        rank, _ = ops.rankeval(xb, coef, lo, hi, n)
        return dq, rank

    def fused():
        return ops.pdist_rankeval(q, piv, coef, lo, hi, n, rg)

    return staged, fused


def _filter_plane(reps: int) -> dict:
    """Bytes the ball-filter stage streams per query under the three
    layouts DESIGN.md §13 ships: padded f32 (baseline), compacted f32
    gather, compacted bf16.  Bytes are the filter-plane rows the kernel
    actually reads (slots x d x itemsize — the exact quantity the
    compaction/precision work targets); wall time rides along, and all
    three layouts must return bitwise-identical results."""
    from repro.core import LIMSIndex, MetricSpace
    from repro.core.executor import QueryExecutor
    from repro.core.metrics import dist_one_to_many
    from repro.core.snapshot import LIMSSnapshot

    n, d, B = (3_000, 8, 32) if QUICK else (12_000, 8, 64)
    rng = np.random.default_rng(5)
    # a single blob k-center-clusters unevenly — the padded-slot slack
    # the compacted gather exists for (cf. tests/test_layout.py)
    X = rng.normal(size=(n, d))
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=32, m=3, n_rings=16)
    Q = X[rng.choice(n, B)] + rng.normal(0, 0.003, (B, d))
    radii = np.array([float(np.quantile(dist_one_to_many(q, X, "l2"),
                                        2e-3)) for q in Q])

    def run(compact: str, dtype: str) -> tuple:
        with _env(REPRO_COMPACT=compact, REPRO_ROWS_DTYPE=dtype):
            snap = LIMSSnapshot.build(ix)
            ex = QueryExecutor(snap)
            res = ex.range_query_batch(Q, radii)
            t = _time(lambda: ex.range_query_batch(Q, radii), reps)
        itemsize = 2 if dtype in ("bf16", "f16") else 4
        rows = (snap.n_slots if ex.last_compact is None
                else ex.last_compact["bucket"])
        return res, t, rows * d * itemsize / B, ex.last_compact, snap

    base, t0, bpq0, _, snap = run("off", "off")
    comp, t1, bpq1, lc1, _ = run("on", "off")
    lowp, t2, bpq2, lc2, _ = run("on", "bf16")
    for got in (comp, lowp):
        for (ai, ad), (bi, bd) in zip(base, got):
            assert np.array_equal(ai, bi) and np.array_equal(ad, bd), \
                "filter-plane layouts must be bitwise-identical"

    out = {
        "n": n, "d": d, "batch": B, "n_slots": snap.n_slots,
        "padded_f32": {"bytes_per_query": round(bpq0), "us": round(t0 * 1e6, 1)},
        "compact_f32": {"bytes_per_query": round(bpq1), "us": round(t1 * 1e6, 1),
                        "gather": lc1},
        "compact_bf16": {"bytes_per_query": round(bpq2), "us": round(t2 * 1e6, 1),
                         "gather": lc2},
        "bytes_reduction": round(bpq0 / bpq2, 2),
    }
    emit("kernels/filter_plane_bytes", bpq2,
         f"padded_f32={bpq0:.0f}B/q compact_bf16={bpq2:.0f}B/q "
         f"reduction={out['bytes_reduction']}x")
    # acceptance: the compacted bf16 plane moves >= 2x fewer bytes per
    # query than the padded f32 baseline.  This is layout arithmetic,
    # not a timing — bf16 alone halves traffic, the gather stacks on
    # top whenever the union clears the payoff bound — so it holds on
    # every backend and at the QUICK shapes too.
    assert out["bytes_reduction"] >= 2.0, (
        f"filter plane bytes/query reduced only "
        f"{out['bytes_reduction']}x: {out}")
    return out


def main() -> None:
    sh = _SHAPES[QUICK]
    reps = 2 if QUICK else 5
    payload: dict = {"bench": "kernels", "quick": QUICK, "shapes": sh,
                     "backend": jax.default_backend()}

    # ---- lane timings per stage ---------------------------------------
    lanes: dict[str, dict] = {}
    with _env(REPRO_INTERPRET="on"):
        for name, thunk in _stage_thunks(sh):
            lanes.setdefault(name, {})["interpret_us"] = round(
                _time(thunk, reps) * 1e6, 1)

    # tune the table for these exact shape buckets (tune() searches the
    # grid and persists the winner; explicit-tile thunks inside never
    # consult the table, so there is no recursion)
    with _env(REPRO_INTERPRET="off"):
        tuned = {
            "pdist": autotune.tune(
                "pdist", "sql2",
                {"q": sh["q"], "p": sh["p"], "d": sh["d"]}),
            "rankeval": autotune.tune(
                "rankeval", None,
                {"g": sh["g"], "b": sh["b"], "c": sh["c"]}),
            "range_filter": autotune.tune(
                "range_filter", "sql2",
                {"q": sh["q"], "p": sh["p"], "d": sh["d"]}),
        }
    payload["autotune"] = {k: dict(v["tiles"], tune_us=v["us"])
                           for k, v in tuned.items()}
    payload["tuning_cache"] = str(autotune.cache_path())

    wins = 0
    for name, thunk in _stage_thunks(sh):
        def run_static(t=thunk):
            with _env(REPRO_INTERPRET="off", REPRO_AUTOTUNE="off"):
                return t()

        def run_tuned(t=thunk):
            with _env(REPRO_INTERPRET="off", REPRO_AUTOTUNE="on"):
                return t()

        t_s, t_t = _paired(run_static, run_tuned, reps)
        lanes[name]["xla_static_us"] = round(t_s * 1e6, 1)
        lanes[name]["xla_autotuned_us"] = round(t_t * 1e6, 1)
        lanes[name]["tuned_beats_static"] = bool(t_t < t_s)
        wins += int(t_t < t_s)
        emit(f"kernels/{name}_lane", lanes[name]["xla_autotuned_us"],
             f"interp={lanes[name]['interpret_us']} "
             f"static={lanes[name]['xla_static_us']} "
             f"tuned_wins={t_t < t_s}")
    payload["lanes"] = lanes
    payload["autotuned_wins"] = wins
    # acceptance: tuned tiles beat the static heuristics on >= 2 of 3
    # stages.  Gated to the CPU xla lane — that is the lane the shipped
    # tuning table targets; on TPU/GPU the heuristics are MXU-aligned
    # already and the table starts empty.  Full shapes only: at the
    # QUICK sizes every stage is ~1-2ms and the comparison is noise.
    if jax.default_backend() == "cpu" and not QUICK:
        assert wins >= 2, (
            f"autotuned tiles beat static heuristics on only {wins}/3 "
            f"kernel stages: {lanes}")

    # ---- fused vs staged plan stage -----------------------------------
    fused_cmp = {}
    for lane, lane_env in (("interpret", "on"), ("xla", "off")):
        with _env(REPRO_INTERPRET=lane_env):
            staged, fused = _fused_thunks(sh)
            t_staged, t_fused = _paired(staged, fused, reps)
        fused_cmp[lane] = {
            "staged_us": round(t_staged * 1e6, 1),
            "fused_us": round(t_fused * 1e6, 1),
            "speedup": round(t_staged / t_fused, 2),
        }
    payload["fused_pdist_rankeval"] = fused_cmp
    emit("kernels/fused_plan_xla", fused_cmp["xla"]["fused_us"],
         f"staged={fused_cmp['xla']['staged_us']} "
         f"speedup={fused_cmp['xla']['speedup']}x")

    # ---- filter-plane bytes per query (compaction + bf16) -------------
    payload["filter_plane"] = _filter_plane(reps)

    # ---- roofline over the real query pipeline ------------------------
    from repro.roofline.pipeline import pipeline_report, render
    payload["roofline"] = pipeline_report(quick=QUICK)
    print(render(payload["roofline"]))
    # acceptance: the query-blocked pdist tiling holds the stage at
    # >= 55% of its memory roof at the pipeline shapes (up from ~39%
    # with the point-major-only tiles).  CPU xla lane, full shapes only
    # — the same gate as the autotuner assertion above.
    if jax.default_backend() == "cpu" and not QUICK:
        pd_util = next(s["roofline_utilization"]
                       for s in payload["roofline"]["stages"]
                       if s["stage"] == "pdist")
        assert pd_util >= 0.55, (
            f"query-blocked pdist at {pd_util:.0%} of the memory roof "
            f"(want >= 55%)")

    # ---- historical ref-path rows (trajectory continuity) -------------
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (sh["q"], sh["d"]), jnp.float32)
    p = jax.random.normal(jax.random.PRNGKey(1), (sh["p"], sh["d"]),
                          jnp.float32)
    pd = jax.jit(lambda a, b: ref.pdist_ref(a, b, "sql2"))
    dt = _time(lambda: pd(q, p), reps)
    emit(f"kernels/pdist_ref_{sh['q']}x{sh['p'] // 1024}k", dt * 1e6,
         f"gflops={2 * sh['q'] * sh['p'] * sh['d'] / dt / 1e9:.1f}")
    qa = jax.random.normal(key, (1, 8, 1024, 64), jnp.float32)
    ka = jax.random.normal(key, (1, 2, 1024, 64), jnp.float32)
    at = jax.jit(lambda a, b, c: ref.attention_ref(a, b, c, causal=True))
    dt = _time(lambda: at(qa, ka, ka), reps)
    emit("kernels/attention_1x8x1024", dt * 1e6, "")

    if not QUICK:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        write_json(os.path.join(root, "BENCH_kernels.json"), payload)


if __name__ == "__main__":
    main()
