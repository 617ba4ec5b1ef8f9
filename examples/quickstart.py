"""Quickstart: build LIMS on a GaussMix dataset, run exact range / kNN /
point queries, insert + delete, and compare against brute force.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.baselines import LinearScan
from repro.core import LIMSIndex, MetricSpace
from repro.core.metrics import dist_one_to_many
from repro.data.datasets import gauss_mix

def main() -> None:
    print("== LIMS quickstart ==")
    X = gauss_mix(50_000, 8, seed=0)
    sp = MetricSpace(X, "l2")

    ix = LIMSIndex(sp, n_clusters=50, m=3, n_rings=20)
    print(f"built LIMS over {sp.n:,} points in {ix.build_time_s:.2f}s "
          f"(index {ix.index_nbytes()/2**20:.1f} MiB, "
          f"K={ix.K}, m={ix.m}, N={ix.n_rings})")
    scan = LinearScan(sp)

    rng = np.random.default_rng(1)
    q = X[rng.integers(sp.n)] + rng.normal(0, 0.003, 8)

    # ---- range query -------------------------------------------------
    d = dist_one_to_many(q, X, "l2")
    r = float(np.quantile(d, 1e-4))     # 0.01% selectivity, paper default
    ids, ds, st = ix.range_query(q, r)
    truth = set(np.where(d <= r)[0].tolist())
    assert set(map(int, ids)) == truth, "range query must be EXACT"
    _, _, st_scan = scan.range_query(q, r)
    print(f"range(q, {r:.3f}): {len(ids)} results | LIMS pages={st.pages} "
          f"vs scan pages={st_scan.pages} "
          f"({st_scan.pages/max(st.pages,1):.0f}x fewer reads)")

    # ---- kNN query -----------------------------------------------------
    ids, ds, st = ix.knn_query(q, 10)
    assert abs(np.sort(ds)[-1] - np.sort(d)[9]) < 1e-9, "kNN must be EXACT"
    print(f"knn(q, 10): kth distance {np.sort(ds)[-1]:.4f} | "
          f"pages={st.pages} dist_comps={st.dist_comps}")

    # ---- point query ---------------------------------------------------
    ids, st = ix.point_query(X[123])
    assert 123 in set(map(int, ids))
    print(f"point(X[123]): found with {st.pages} page reads")

    # ---- updates --------------------------------------------------------
    gid = ix.insert(q)
    ids, _, _ = ix.range_query(q, 1e-6)
    assert gid in set(map(int, ids)), "inserted object must be findable"
    ix.delete(q)
    ids, _, _ = ix.range_query(q, 1e-6)
    assert gid not in set(map(int, ids)), "deleted object must disappear"
    print("insert/delete: exact through the per-cluster buffer + tombstones")
    print("OK")


if __name__ == "__main__":
    from repro.env import use_compile_cache
    use_compile_cache()
    main()
