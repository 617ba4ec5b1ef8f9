#!/usr/bin/env python3
"""Smoke run of the exact served query path on a TPU.

One process drives the main path end to end through the entry points a
user calls, on a GaussMix corpus of 1,000,000 x 32 L2 rows:

    corpus -> LIMSIndex -> ServingEngine snapshot -> ServingFrontend ->
    PlanRouter -> QueryExecutor (Pallas kernels on the chip) ->
    f64 host refinement

and holds every answer to the brute-force ``LinearScan`` on the same
data: range queries at selectivity 1e-4 and 1e-3 and kNN at k=10 and
k=100, each for 64 queries (a corpus row plus N(0, 0.003) noise).  A
``backend="device"`` build of the same corpus must answer identically.
Then 1,000 inserts, 100 deletes, a snapshot refresh and a
``retrain_cluster(backend="auto")`` that runs the device builder, and
the same queries again against brute force over the live set.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # four chips: the cluster-sharded
                                        # executor and a 4-replica frontend,
                                        # against 1-chip and brute force

The wall times it prints are those of a smoke run, not measurements.
The last line of stdout is ``{"ok": true, "device": {...}}``; a missing
TPU, a kernel lane other than ``pallas`` or any wrong answer exits
non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_ROWS = 1_000_000
DIM = 32
CLUSTERS = 256
SELECTIVITIES = (1e-4, 1e-3)
KS = (10, 100)
BATCH = 64
NOISE = 0.003


class SmokeFailure(RuntimeError):
    """An answer differs from brute force, or the run is on the wrong
    device or lane."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int) -> dict:
    """Refuse to run anywhere but ``chips`` TPU chips on the pallas
    lane; returns the device record for the last line."""
    import jax
    from repro.kernels.dispatch import kernel_mode
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) != chips:
        raise SmokeFailure(f"expected {chips} TPU chip(s), JAX sees "
                           f"{len(devs)}")
    mode = kernel_mode()
    if mode != "pallas":
        raise SmokeFailure(f"kernel lane is {mode!r}, not 'pallas' "
                           "(is REPRO_INTERPRET set?)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Phases:
    """Wall time per phase (a smoke run's, not a measurement) and the
    backend compile seconds JAX reports inside each."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += secs

    def run(self, name: str, fn, *args, **kw):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args, **kw)
        log(f"phase {name}: {time.perf_counter() - t0:.2f} s wall, "
            f"{self.compile_s - c0:.2f} s compile (smoke run, not a "
            f"measurement)")
        return out


# ------------------------------------------------------------------ data
def make_corpus(n: int, d: int, seed: int) -> np.ndarray:
    from repro.data.datasets import gauss_mix
    return gauss_mix(n, d, seed=seed)


def make_queries(X: np.ndarray, n_queries: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    return X[rng.choice(len(X), n_queries, replace=False)] + \
        rng.normal(0.0, NOISE, (n_queries, X.shape[1]))


def update_rows(X: np.ndarray, seed: int, n_insert: int = 1000,
                n_delete: int = 100) -> tuple[np.ndarray, np.ndarray]:
    """The smoke run's update batch, from the seed: rows to insert (noisy
    corpus rows) and ascending corpus ids to delete."""
    rng = np.random.default_rng(seed + 2)
    new = X[rng.choice(len(X), n_insert)] + \
        rng.normal(0.0, NOISE, (n_insert, X.shape[1]))
    dead = np.sort(rng.choice(len(X), n_delete, replace=False))
    return new, dead


def live_set(X: np.ndarray, seed: int,
             updated: bool) -> tuple[np.ndarray, np.ndarray]:
    """(rows, global ids) of the live set, ids ascending: the corpus, or
    the corpus after the update batch (inserts take ids n, n+1, ...)."""
    if not updated:
        return X, np.arange(len(X))
    new, dead = update_rows(X, seed)
    keep = np.ones(len(X), bool)
    keep[dead] = False
    return (np.concatenate([X[keep], new]),
            np.concatenate([np.nonzero(keep)[0],
                            len(X) + np.arange(len(new))]))


class Reference:
    """Brute force over the live rows, with ids mapped back to the
    index's global ids: ``baselines.LinearScan``'s scan (the f64
    ``dist_one_to_many`` over every row) and its answers — kNN is the
    k-prefix of the stable argsort of the distances, so ties break by
    position, which is id order because ``gids`` ascend; range is the
    rows with d <= r.  ``tests/test_chip_smoke.py`` holds it equal to
    ``LinearScan`` itself."""

    def __init__(self, rows: np.ndarray, gids: np.ndarray):
        if np.any(np.diff(gids) <= 0):
            raise ValueError("reference gids must ascend")
        self.rows = rows
        self.gids = np.asarray(gids, np.int64)

    def answers(self, Q: np.ndarray, radii: dict | None = None,
                sels=SELECTIVITIES, ks=KS) -> tuple[dict, dict]:
        """``(radii, answers)``: range answers per selectivity and kNN
        answers per k, from one scan per query.

        Without ``radii``, each query's radius for selectivity ``s`` is
        its distance to the ``round(s * n)``-th nearest live row.  Every
        answer lies within the larger of the largest k's distance and
        the largest radius, so a partition at that reach and a stable
        sort of the rows inside it stand in for ``LinearScan``'s full
        stable argsort (a 1M-row scan per query, not a 1M-row sort)."""
        from repro.core.metrics import dist_one_to_many
        n = len(self.gids)
        k_sel = [max(1, int(round(s * n))) for s in sels]
        k_all = min(n, max(ks) if radii is not None
                    else max(k_sel + list(ks)))
        near = []
        for b, q in enumerate(Q):
            d = dist_one_to_many(q, self.rows, "l2")
            reach = np.partition(d, k_all - 1)[k_all - 1]
            if radii is not None:
                reach = max(reach, max(r[b] for r in radii.values()))
            i = np.nonzero(d <= reach)[0]
            i = i[np.argsort(d[i], kind="stable")]
            near.append((i, d[i]))
        if radii is None:
            radii = {s: np.array([d[k - 1] for _, d in near])
                     for s, k in zip(sels, k_sel)}
        out = {}
        for s, r in radii.items():
            out[("range", s)] = [(self.gids[i[d <= x]], d[d <= x])
                                 for (i, d), x in zip(near, r)]
        for k in ks:
            out[("knn", k)] = [(self.gids[i[:k]], d[:k]) for i, d in near]
        return radii, out


def reference(X: np.ndarray, seed: int, Q: np.ndarray,
              radii: dict | None = None,
              updated: bool = False) -> tuple[dict, dict]:
    """Brute-force ``(radii, answers)`` for the queries ``Q`` over the
    corpus, or over the live set after the update batch when
    ``updated``."""
    rows, gids = live_set(X, seed, updated)
    return Reference(rows, gids).answers(Q, radii)


# -------------------------------------------------------------- serving
def build_index(X: np.ndarray, n_clusters: int, backend: str = "host"):
    from repro.core import LIMSIndex, MetricSpace
    return LIMSIndex(MetricSpace(X, "l2"), n_clusters=n_clusters, m=3,
                     n_rings=20, backend=backend)


def make_engine(index, sharded: bool | None = None):
    """A serving engine that refreshes only when asked (the smoke run
    refreshes once after its update batch)."""
    from repro.core import ServingEngine
    return ServingEngine(index, refresh_every=0, sharded=sharded)


def snapshot_stats(snap) -> dict:
    import jax
    return {"K": snap.K, "n_max": snap.n_max, "slots": snap.n_slots,
            "device_bytes": int(sum(a.nbytes for a in
                                    jax.tree_util.tree_leaves(snap)))}


def _frontend_batch(fe, submit, args: list) -> list:
    """Submit one query per thread and let the frontend coalesce them
    into one batch: the batcher is held until all are queued."""
    fe.pause()
    before = fe.metrics()["submitted"]
    with ThreadPoolExecutor(len(args)) as pool:
        futs = [pool.submit(submit, *a) for a in args]
        while fe.metrics()["submitted"] < before + len(args):
            time.sleep(0.001)
        fe.resume()
        return [f.result() for f in futs]


def serve_frontend(fe, Q: np.ndarray, radii: dict, ks=KS) -> dict:
    """Every query of the smoke mix through ``ServingFrontend``."""
    out = {}
    for s, r in radii.items():
        out[("range", s)] = _frontend_batch(
            fe, fe.range_query, [(q, float(x)) for q, x in zip(Q, r)])
    for k in ks:
        out[("knn", k)] = _frontend_batch(fe, fe.knn_query,
                                          [(q, k) for q in Q])
    return out


def serve_engine(engine, Q: np.ndarray, radii: dict, ks=KS) -> dict:
    """The same mix through the engine's batch calls (the sharded
    executor's entry point)."""
    out = {}
    for s, r in radii.items():
        out[("range", s)] = engine.range_query_batch(Q, r)
    for k in ks:
        ids, ds = engine.knn_query_batch(Q, k)
        out[("knn", k)] = list(zip(ids, ds))
    return out


def _canonical(ids, d):
    ids = np.asarray(ids, np.int64)
    d = np.asarray(d, np.float64)
    order = np.lexsort((ids, d))
    return ids[order], d[order]


def compare(got: dict, want: dict, label: str) -> int:
    """Raise unless every answer equals the reference: the same ids with
    bit-identical f64 distances, ordered by (distance, id).  Returns the
    number of answers compared."""
    n = 0
    for key, ref in want.items():
        res = got[key]
        if len(res) != len(ref):
            raise SmokeFailure(f"{label} {key}: {len(res)} answers, "
                               f"expected {len(ref)}")
        for b, ((gi, gd), (ri, rd)) in enumerate(zip(res, ref)):
            gi, gd = _canonical(gi, gd)
            ri, rd = _canonical(ri, rd)
            if not (np.array_equal(gi, ri) and np.array_equal(gd, rd)):
                raise SmokeFailure(
                    f"{label} {key} query {b}: {len(gi)} results, "
                    f"expected {len(ri)}; first ids {gi[:5]} vs {ri[:5]}")
            n += 1
    log(f"{label}: {n} answers equal brute force")
    return n


# -------------------------------------------------------------- updates
def apply_updates(engine, X: np.ndarray, seed: int) -> None:
    """Apply the seeded update batch (:func:`update_rows`) through the
    engine and refresh its snapshot."""
    new, dead = update_rows(X, seed)
    gids = [engine.insert(p) for p in new]
    if gids != list(range(len(X), len(X) + len(new))):
        raise SmokeFailure("inserts did not take ids n, n+1, ...")
    removed = sum(engine.delete(X[g]) for g in dead)
    if removed != len(dead):
        raise SmokeFailure(f"deleted {removed} rows, expected {len(dead)}")
    engine.refresh()


def retrain_largest(engine) -> tuple[int, int, str]:
    """``retrain_cluster`` (backend "auto") on the cluster with the most
    live rows, then refresh.  Returns (cluster, rows, backend used)."""
    index = engine.index
    dead = index.tombstones
    sizes = [sum(int(g) not in dead for g in ci.store_ids)
             + sum(int(g) not in dead for g in ci.buf_ids)
             for ci in index.clusters]
    c = int(np.argmax(sizes))
    engine.retrain_cluster(c)
    engine.refresh()
    return c, sizes[c], index.last_retrain_backend


def structural_parity(host_ix, dev_ix) -> dict:
    """Whether the device build picked the host build's centers and
    pivots (its f64 sweeps aim for this; exactness does not need it)."""
    centers = np.array_equal(host_ix.clustering.center_idx,
                             dev_ix.clustering.center_idx)
    pivots = centers and all(
        np.array_equal(a.pivot_rows, b.pivot_rows)
        for a, b in zip(host_ix.clusters, dev_ix.clusters))
    return {"centers": centers, "pivots": pivots}


# ----------------------------------------------------------------- runs
def run_one_chip(args, ph: Phases) -> None:
    import jax
    X = ph.run("corpus", make_corpus, N_ROWS, DIM, args.seed)
    Q = make_queries(X, BATCH, args.seed)
    radii, want = ph.run("reference", reference, X, args.seed, Q)

    ix = ph.run("host build", build_index, X, CLUSTERS)
    engine = ph.run("snapshot", make_engine, ix)
    log(f"snapshot: {snapshot_stats(engine.snapshot)}")
    with engine.frontend(max_batch=BATCH) as fe:
        got = ph.run("serve (cold)", serve_frontend, fe, Q, radii)
        compare(got, want, "host build, cold")
        got = ph.run("serve (warm)", serve_frontend, fe, Q, radii)
        compare(got, want, "host build, warm")

        dix = ph.run("device build", build_index, X, CLUSTERS,
                     "device")
        log(f"device build stages: {dix.device_build_timings}")
        log(f"device build parity with host: {structural_parity(ix, dix)}")
        dengine = make_engine(dix)
        with dengine.frontend(max_batch=BATCH) as dfe:
            got = ph.run("serve device build", serve_frontend, dfe, Q,
                         radii)
        compare(got, want, "device build")
        del dengine, dix

        ph.run("updates", apply_updates, engine, X, args.seed)
        c, n_rows, backend = ph.run("retrain", retrain_largest, engine)
        log(f"retrain: cluster {c}, {n_rows} rows, backend {backend}")
        if backend != "device":
            raise SmokeFailure(f"retrain ran on {backend!r}, not the "
                               "device builder")
        _, want = ph.run("reference (live)", reference, X, args.seed, Q,
                         radii, updated=True)
        got = ph.run("serve after updates", serve_frontend, fe, Q, radii)
        compare(got, want, "after updates")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


def run_four_chips(args, ph: Phases) -> None:
    import jax
    X = ph.run("corpus", make_corpus, N_ROWS, DIM, args.seed)
    Q = make_queries(X, BATCH, args.seed)
    radii, want = ph.run("reference", reference, X, args.seed, Q)
    ix = ph.run("host build", build_index, X, CLUSTERS)
    got1, got4, gotr = four_chip_answers(ix, Q, radii, ph)
    compare(got1, want, "1 chip")
    compare(got4, want, "sharded over 4 chips")
    compare(got4, {k: got1[k] for k in want}, "sharded vs 1 chip")
    compare(gotr, want, "4 replicas")
    compare(gotr, {k: got1[k] for k in want}, "4 replicas vs 1 chip")
    for i, dev in enumerate(jax.devices()):
        stats = dev.memory_stats() or {}
        log(f"device {i} peak_bytes_in_use: "
            f"{stats.get('peak_bytes_in_use')}")


def four_chip_answers(ix, Q, radii, ph: Phases):
    """The 1-chip frontend, the cluster-sharded engine over every
    device, and a frontend with one replica per device — each printing
    where its arrays live."""
    import jax
    n_dev = len(jax.devices())
    one = make_engine(ix, sharded=False)
    with one.frontend(max_batch=BATCH, n_replicas=1) as fe:
        got1 = ph.run("serve 1 chip", serve_frontend, fe, Q, radii)
    log(f"1-chip snapshot rows on {one.snapshot.rows.devices()}")

    sharded = make_engine(ix, sharded=True)
    ex = sharded.executor
    log(f"sharded executor: {type(ex).__name__}, {ex.n_shards} shards")
    for shard in ex.snap.rows.addressable_shards:
        log(f"  shard {shard.index} on {shard.device}")
    got4 = ph.run("serve sharded", serve_engine, sharded, Q, radii)

    with one.frontend(max_batch=BATCH, n_replicas=n_dev) as fe:
        gotr = ph.run("serve replicas", serve_frontend, fe, Q, radii)
        routing = fe.metrics()["routing"]["replicas"]
    for rep in routing:
        log(f"  replica {rep['rid']} on {rep['device']}: "
            f"{rep['batches']} batches, {rep['queries']} queries")
    return got1, got4, gotr


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    device = require_tpu(args.chips)
    from repro.env import use_compile_cache
    from repro.kernels.dispatch import kernel_mode
    log(f"device: {device['kind']} x {device['count']}, kernel_mode "
        f"{kernel_mode()}, compile cache {use_compile_cache()}")
    log(f"corpus: gauss_mix n={N_ROWS} d={DIM} seed={args.seed}, L2, "
        f"K={CLUSTERS} m=3 N=20; {BATCH} queries per batch")
    ph = Phases()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, ph)
    else:
        run_one_chip(args, ph)
    log(f"total: {time.perf_counter() - t0:.1f} s wall, "
        f"{ph.compile_s:.1f} s backend compile (smoke run)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
