"""Batch-engine throughput: the serving story (queries/sec).

Compares, on the same snapshot and workload:
  * ``BatchedLIMS.range_query_batch`` / ``knn_query_batch`` — one kernel
    launch sequence for the whole batch;
  * the per-query ``BatchedLIMS`` loop (same kernels, batch size 1) —
    what the device path did before the batch engine;
  * the host ``LIMSIndex`` per-query path;
  * a brute-force linear scan.

Emits ``name,us_per_call,derived`` rows where us_per_call is per *query*
and derived records queries/sec plus the batch-vs-per-query speedup.
The acceptance bar for the batch engine is ≥5× the per-query device loop
at batch size 64 on CPU-interpret.

``ServingEngine`` scaling: a second phase measures queries/sec through
the full serving frontend at 1 vs N simulated host devices.  The device
count is baked into the process at jax init, so each configuration runs
in a subprocess with ``--xla_force_host_platform_device_count`` set on
the CPU backend (``--serving`` puts this module in worker mode: run the
serving bench in-process, print one JSON record).  It runs before the
in-process phase: a chip belongs to one process at a time, so the parent
stays off JAX until its workers are done.  Results land in
``BENCH_serving.json``: q/s, the paper's pages/candidates per query,
kNN rounds + host syncs per batch (the plan/execute acceptance
metrics), and — for the ``paged-prefetch`` config — the async
prefetcher's overlap stats.  Each config also records: the frozen PR-4
golden drivers' q/s on the same workload (asserted: no config regresses
below them — the bar the interpret-mode rounds driver restores), the
``ServingFrontend`` metrics under concurrent single-query submitters
(achieved batch sizes, queue wait p50/p99, per-replica load, shed rate
from a deliberate overload burst), and — paged configs — the cache hit
rate of schedule-pinned eviction vs blind LRU under a squeezed
capacity (asserted: pinning wins).

``--real-io`` drops the OS page cache (``posix_fadvise(DONTNEED)`` on
the pages files) before each cold store pass, so the cold numbers
measure device IO instead of kernel-cached reads.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.core import LIMSIndex, MetricSpace
from repro.core.batched import BatchedLIMS
from repro.core.metrics import dist_one_to_many

from .common import QUICK, emit, write_json

BATCH = 64
SERVING_DEVICES = (1, 4)     # simulated-host-device counts to compare
# (label, device count, extra env) serving configurations: in-memory
# scaling, the paged storage tier (page-granular IO, the paper's
# headline cost metric, recorded alongside q/s), and the paged tier
# with async prefetch (kNN rounds' page IO overlapped with refinement)
SERVING_CONFIGS = tuple(
    # the single-device config additionally runs the open-loop Poisson
    # latency-under-load sweep (bench_load; BENCH_LOAD is a bench-driver
    # flag, not a REPRO_* knob)
    [(str(nd), nd, ({"BENCH_LOAD": "1"} if nd == 1 else {}))
     for nd in SERVING_DEVICES]
    + [("paged", 1, {"REPRO_STORAGE": "paged"}),
       ("paged-prefetch", 1, {"REPRO_STORAGE": "paged",
                              "REPRO_PREFETCH": "async"}),
       # the compiled XLA-CPU lane (interpret=off): jitted-XLA kernels +
       # autotuned tiles — the "fast as the hardware allows" lane on a
       # CPU-only host, held to the same golden no-regression bar (the
       # goldens run in the same lane inside the worker, so the bar
       # compares plan/execute vs the PR-4 drivers at compiled speed)
       ("xla-compiled", 1, {"REPRO_INTERPRET": "off"}),
       # continuous health monitoring: the background sampler thread +
       # detectors live (DESIGN.md §12), held to the same golden bar —
       # the monitor must not tax the query path it watches.
       ("monitor", 1, {"REPRO_MONITOR": "on"})])


def _bench(fn, reps: int) -> float:
    fn()                                    # warm-up (jit compile/trace)
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _bench_once(fn) -> float:
    """Single unwarmed call — for cold-cache IO measurements, where the
    first run IS the measurement."""
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _bench_paired(fn_a, fn_b, reps: int) -> tuple:
    """Best-of-``reps`` for two alternatives, interleaved a,b,a,b…

    Shared-CPU containers drift by tens of percent across seconds; a
    sequential mean charges that drift to whichever path ran in the slow
    window.  Interleaving exposes both paths to the same drift and
    best-of discards it — the standard timeit discipline — which is what
    the golden no-regression assertion needs to not be a coin flip."""
    fn_a(), fn_b()                          # warm-up (jit compile/trace)
    best_a = best_b = float("inf")
    for _ in range(max(reps, 3)):
        t0 = time.perf_counter()
        fn_a()
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def main() -> None:
    from repro.data.datasets import gauss_mix

    n = 6_000 if QUICK else 16_000
    d = 8
    X = gauss_mix(n, d, seed=0)
    sp = MetricSpace(X, "l2")
    ix = LIMSIndex(sp, n_clusters=16, m=3, n_rings=20)
    bx = BatchedLIMS(ix)

    rng = np.random.default_rng(1)
    Q = X[rng.choice(n, BATCH)] + rng.normal(0, 0.003, (BATCH, d))
    rs = np.array([float(np.quantile(dist_one_to_many(q, X, "l2"), 1e-3))
                   for q in Q])
    reps = 1 if QUICK else 3

    # --- range ------------------------------------------------------------
    t_batch = _bench(lambda: bx.range_query_batch(Q, rs), reps)
    t_loop = _bench(
        lambda: [bx.range_query(q, r) for q, r in zip(Q, rs)], reps)
    t_host = _bench(
        lambda: [ix.range_query(q, r) for q, r in zip(Q, rs)], reps)
    t_scan = _bench(
        lambda: [np.where(dist_one_to_many(q, X, "l2") <= r)[0]
                 for q, r in zip(Q, rs)], reps)
    speedup = t_loop / t_batch
    emit("batch_range/batch64", t_batch / BATCH * 1e6,
         f"qps={BATCH / t_batch:.0f} speedup_vs_per_query={speedup:.1f}x")
    from repro.kernels.dispatch import default_interpret
    emit("batch_range/per_query_device", t_loop / BATCH * 1e6,
         f"qps={BATCH / t_loop:.0f}")
    emit("batch_range/host_index", t_host / BATCH * 1e6,
         f"qps={BATCH / t_host:.0f}")
    emit("batch_range/linear_scan", t_scan / BATCH * 1e6,
         f"qps={BATCH / t_scan:.0f}")
    # the 5x bar is defined for CPU-interpret at full reps; a single
    # quick-mode iteration (or a compiled backend where both paths are
    # fast) is too noisy to gate on
    if speedup < 5.0:
        print(f"# WARNING: batch speedup {speedup:.1f}x below the 5x bar")
        if default_interpret() and not QUICK:
            raise AssertionError(
                f"batch engine only {speedup:.1f}x over the per-query "
                f"loop (acceptance bar: 5x at batch {BATCH})")

    # --- kNN --------------------------------------------------------------
    k = 10
    t_batch = _bench(lambda: bx.knn_query_batch(Q, k), reps)
    t_loop = _bench(lambda: [bx.knn_query(q, k) for q in Q], reps)
    t_host = _bench(lambda: [ix.knn_query(q, k) for q in Q], reps)
    t_scan = _bench(
        lambda: [np.argsort(dist_one_to_many(q, X, "l2"))[:k] for q in Q],
        reps)
    emit("batch_knn/batch64", t_batch / BATCH * 1e6,
         f"qps={BATCH / t_batch:.0f} "
         f"speedup_vs_per_query={t_loop / t_batch:.1f}x")
    emit("batch_knn/per_query_device", t_loop / BATCH * 1e6,
         f"qps={BATCH / t_loop:.0f}")
    emit("batch_knn/host_index", t_host / BATCH * 1e6,
         f"qps={BATCH / t_host:.0f}")
    emit("batch_knn/linear_scan", t_scan / BATCH * 1e6,
         f"qps={BATCH / t_scan:.0f}")


# --------------------------------------------------------- frontend metrics
def _bench_frontend(se, Q, k: int = 10, n_threads: int = 8) -> dict:
    """Drive the ServingFrontend with concurrent single-query submitter
    threads (the workload it exists for) and return its metrics record:
    achieved batch sizes, queue wait p50/p99, frontend q/s, per-replica
    load — plus the shed rate from a paused-queue overload burst."""
    import threading

    fe = se.frontend(max_batch=16, slo_ms=5.0, max_queue=256)
    try:
        per = max(len(Q) // n_threads, 1)

        def submitter(i: int) -> None:
            for q in Q[i * per:(i + 1) * per]:
                fe.knn_query(q, k)

        fe.knn_query(Q[0], k)           # warm the replica set / kernels
        t0 = time.perf_counter()
        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        out = fe.metrics()
        out["frontend_qps"] = round(n_threads * per / elapsed, 1)

        # overload burst: hold the batcher, fill the bounded queue, and
        # count how many extra submits admission control sheds
        from repro.serving import FrontendOverload
        ov = se.frontend(max_batch=8, slo_ms=5.0, max_queue=8)
        try:
            ov.pause()
            burst, outcome = 16, {"admitted": 0, "shed": 0}
            holders = []

            def hold(q) -> None:
                try:
                    ov.knn_query(q, k)
                    outcome["admitted"] += 1
                except FrontendOverload:
                    outcome["shed"] += 1

            for j in range(burst):
                th = threading.Thread(target=hold, args=(Q[j % len(Q)],))
                th.start()
                holders.append(th)
                time.sleep(0.002)       # let the queue actually fill
            ov.resume()
            for th in holders:
                th.join()
            m = ov.metrics()
            out["overload"] = {"burst": burst, **outcome,
                               "shed_rate": m["shed_rate"]}
        finally:
            ov.close()
    finally:
        fe.close()
    return out


# ---------------------------------------------------------- serving scaling
def serving_worker() -> dict:
    """Measure ServingEngine throughput with this process's device count
    (set by the parent via XLA_FLAGS). Returns one JSON-able record."""
    import jax
    from repro.data.datasets import gauss_mix
    from repro.core.serving import ServingEngine
    from repro.kernels.dispatch import kernel_mode
    from repro.obs.monitor import maybe_monitor

    # the "monitor" config's overhead gate: with REPRO_MONITOR=on the
    # sampler thread ticks (probes + series + detectors) for the whole
    # worker run, and the q/s below must still clear the golden bar
    mon = maybe_monitor()

    n = 4_000 if QUICK else 12_000
    d = 8
    X = gauss_mix(n, d, seed=0)
    sp = MetricSpace(X, "l2")
    ix = LIMSIndex(sp, n_clusters=16, m=3, n_rings=20)
    se = ServingEngine(ix)       # auto-shards over the visible devices
    rng = np.random.default_rng(1)
    Q = X[rng.choice(n, BATCH)] + rng.normal(0, 0.003, (BATCH, d))
    rs = np.array([float(np.quantile(dist_one_to_many(q, X, "l2"), 1e-3))
                   for q in Q])
    reps = 1 if QUICK else 3
    ex = se.executor

    # paired best-of timing against the frozen PR-4 drivers
    # (tests/_golden_drivers) — the no-regression bar every config must
    # clear (the PR-5 interpret-mode loop fell below it; the
    # vectorized-round driver is the fix)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tests"))
    import _golden_drivers as golden
    g_range, g_knn = ((golden.range_store, golden.knn_store)
                      if se.store is not None
                      else (golden.range_resident, golden.knn_resident))
    t_range, t_g_range = _bench_paired(
        lambda: se.range_query_batch(Q, rs),
        lambda: g_range(ex, Q, rs), reps)
    t_knn, t_g_knn = _bench_paired(
        lambda: se.knn_query_batch(Q, 10),
        lambda: g_knn(ex, Q, 10), reps)
    rec = {
        "devices": jax.device_count(),
        "n_shards": getattr(ex, "n_shards", 1),
        "executor": type(ex).__name__,
        "n": n, "d": d, "batch": BATCH, "quick": QUICK,
        # which kernel lane answered (interpret / xla / pallas) — the
        # compiled XLA-CPU config reports "xla" here
        "kernel_mode": kernel_mode(),
        "range_qps": round(BATCH / t_range, 1),
        "knn_qps": round(BATCH / t_knn, 1),
        # the plan/execute acceptance metrics: growing-radius rounds per
        # batch and device→host syncs per batch (O(1) in the compiled
        # resident loop; per-round in the host-driven paged backend),
        # plus which kNN driver answered (loop / rounds / paged)
        "knn": dict(ex.last_knn),
    }

    rec["golden"] = {"range_qps": round(BATCH / t_g_range, 1),
                     "knn_qps": round(BATCH / t_g_knn, 1)}

    # frontend phase: concurrent single-query submitters through the
    # dynamic batcher → router → replica set (one replica per device);
    # records achieved batch sizes, queue waits, per-replica balance,
    # and a deliberate overload burst for the shed rate
    rec["frontend"] = _bench_frontend(se, Q)
    if os.environ.get("BENCH_LOAD") == "1":
        # open-loop Poisson latency-under-load sweep (ROADMAP item 2):
        # latency percentiles vs offered load, knee where the frontend
        # stops keeping up (p99 blowout or admission-control shed)
        from .bench_load import bench_latency_under_load
        rec["latency_under_load"] = bench_latency_under_load(se, Q)
    if se.store is not None:
        # the paper's IO metric: page accesses (and candidates) per
        # query, from the store's cache stats over one clean batch each.
        # The cache is cleared first so misses are genuine disk reads
        # (the timing loops above fully warmed it); the kNN hit rate
        # then measures within-batch page reuse across growing-radius
        # rounds — Alg. 2's never-re-read-a-page contract — not the
        # tautological warm-cache 100%.  With --real-io the OS page
        # cache is additionally dropped (posix_fadvise DONTNEED) before
        # each cold pass, so page misses hit the device, not the
        # kernel's cache.
        from repro import env as repro_env
        real_io = repro_env.get("REPRO_REAL_IO") == "1"
        st = se.store

        def _cold():
            if ex.prefetcher is not None:
                # settle in-flight speculative fetches from the warm
                # loops — they would silently repopulate the cleared
                # cache and inflate the cold numbers
                ex.prefetcher.drain()
            st.cache.clear()
            st.stats.reset()
            if real_io:
                st.drop_os_cache()

        def _pf_fetched() -> int:
            # speculative reads bypass the buffer-pool counters
            # (record=False), so the cold passes account them
            # separately: genuine device reads = misses + this delta
            if ex.prefetcher is None:
                return 0
            ex.prefetcher.drain()
            return ex.prefetcher.pages_fetched

        _cold()
        pf0 = _pf_fetched()
        t_cold_range = _bench_once(lambda: se.range_query_batch(Q, rs))
        io_range = st.stats.snapshot()
        range_pf_reads = _pf_fetched() - pf0
        _cold()
        pf0 = _pf_fetched()
        t_cold_knn = _bench_once(lambda: se.knn_query_batch(Q, 10))
        io_knn = st.stats.snapshot()
        knn_pf_reads = _pf_fetched() - pf0
        rec["storage"] = {
            "mode": "paged",
            "real_io": real_io,
            "page_bytes": st.manifest.page_bytes,
            "total_pages": st.manifest.total_pages,
            "range_pages_per_query": io_range["pages_per_query"],
            "range_candidates_per_query": io_range["candidates_per_query"],
            "range_cold_page_reads": io_range["misses"],
            "range_cold_prefetch_reads": range_pf_reads,
            "cold_range_qps": round(BATCH / t_cold_range, 1),
            "knn_pages_per_query": io_knn["pages_per_query"],
            "knn_candidates_per_query": io_knn["candidates_per_query"],
            "knn_cold_page_reads": io_knn["misses"],
            "knn_cold_prefetch_reads": knn_pf_reads,
            "knn_within_batch_hit_rate": io_knn["hit_rate"],
            "cold_knn_qps": round(BATCH / t_cold_knn, 1),
        }
        rec["knn"] = dict(ex.last_knn)      # cold paged rounds/syncs
        if ex.prefetcher is not None:
            # prefetch overlap is measured on a point-lookup drilldown
            # workload (queries at pivot rows → near-zero seed radii):
            # its growing-radius rounds add pages incrementally, the
            # regime prefetch exists for.  Random-query batches over a
            # bench-sized corpus saturate the batch-deduped page union
            # in round 0, leaving later rounds no IO to overlap.
            piv = np.asarray(se.snapshot.pivots, np.float64).reshape(-1, d)
            _cold()
            ex.prefetcher.drain()
            ex.prefetcher.reset()
            se.knn_query_batch(piv[:16], 200)
            ex.prefetcher.drain()
            pf = ex.prefetcher.snapshot()
            pf["workload"] = "pivot-drilldown-16q-k200"
            pf["knn_rounds"] = ex.last_knn["rounds"]
            rec["storage"]["prefetch"] = pf

        # schedule pinning vs blind LRU: the same cold kNN batch through
        # a capacity-squeezed cache with plan pinning on vs off.  The
        # squeeze (a quarter of the batch's unique pages) forces
        # evictions mid-batch; blind LRU then drops pages the plan's
        # later rounds are guaranteed to re-demand, pinning holds them —
        # the acceptance signal is a strictly higher hit rate pinned.
        squeeze = max(4, io_knn["misses"] // 4)

        def _hit_rate(pin: bool) -> float:
            os.environ["REPRO_CACHE_PIN"] = "on" if pin else "off"
            cap0 = st.cache.capacity_pages
            st.cache.capacity_pages = squeeze
            try:
                _cold()
                se.knn_query_batch(Q, 10)
                return st.stats.snapshot()["hit_rate"]
            finally:
                st.cache.capacity_pages = cap0
                os.environ.pop("REPRO_CACHE_PIN", None)

        rec["storage"]["cache_pinning"] = {
            "squeezed_capacity_pages": int(squeeze),
            "hit_rate_pinned": _hit_rate(True),
            "hit_rate_blind_lru": _hit_rate(False),
        }

    # what the obs layer saw over the whole worker run: scalar metrics
    # (counters + gauges; histograms stay out of the committed JSON) and
    # the profile ring depth
    from repro import obs
    scalars = {k: v for k, v in obs.REGISTRY.snapshot().items()
               if not isinstance(v, dict)}
    rec["obs"] = {"mode": obs.obs_mode(),
                  "metrics": len(obs.REGISTRY),
                  "profiles": len(obs.profiles()),
                  "counters": scalars}
    if mon is not None:
        rec["obs"]["monitor"] = {"ticks": mon.store.ticks,
                                 "series": len(mon.store.names()),
                                 "findings": len(mon.findings())}
        mon.stop()
    return rec


def bench_serving_scaling(configs=SERVING_CONFIGS,
                          real_io: bool = False) -> None:
    """Run the serving worker once per configuration (device counts +
    the paged storage tier, with and without async prefetch) and record
    queries/sec — plus page accesses and candidates per query, kNN
    rounds and host syncs per batch, and prefetch overlap stats for
    store-backed runs — in BENCH_serving.json (committed alongside the
    code).  ``real_io`` (the --real-io flag) drops the OS page cache
    before each cold store pass so pages/query reflects device IO."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # one process per chip: this parent never initializes a JAX backend
    # (a child could not open a chip the parent holds), so a probe child
    # reports which backend the workers will see
    backend = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, check=True).stdout.split()[-1]
    results = {}
    for label, nd, extra_env in configs:
        env = dict(os.environ)
        if backend == "cpu":
            # simulated host devices exist only on the CPU backend; an
            # accelerator host runs every config on its real devices
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f]
            flags.append(f"--xla_force_host_platform_device_count={nd}")
            env["XLA_FLAGS"] = " ".join(flags)
        env["REPRO_STORAGE"] = ""
        env["REPRO_PREFETCH"] = ""
        env["REPRO_INTERPRET"] = ""
        env["REPRO_OBS"] = ""           # blank -> the default ("on")
        env["REPRO_MONITOR"] = ""       # blank -> the default ("off")
        env.pop("BENCH_LOAD", None)
        env.update(extra_env)
        if real_io:
            env["REPRO_REAL_IO"] = "1"
        env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_batch", "--serving"],
            cwd=root, env=env, capture_output=True, text=True, check=True)
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        results[label] = rec
        # no-regression bar (satellite of the rounds-driver fix): every
        # config must keep up with the PR-4 golden drivers it replaced
        # (10% measurement slack; the regression this guards against was
        # a 2.4x q/s drop).  Async-prefetch configs get a wider band:
        # speculation eagerly evaluates the next round's mask on the
        # foreground thread, a real per-round kernel cost the
        # never-prefetching golden doesn't pay — on interpret-CPU fake
        # IO that overhead buys nothing back (the overlap it exists for
        # is measured on the drilldown workload below), so the bar here
        # only guards against driver regressions, not the documented
        # speculation cost.
        slack = 0.75 if extra_env.get("REPRO_PREFETCH") == "async" else 0.9
        for kind in ("range", "knn"):
            new, old = rec[f"{kind}_qps"], rec["golden"][f"{kind}_qps"]
            assert new >= slack * old, (
                f"serving config '{label}': {kind} at {new} q/s is "
                f"slower than the PR-4 golden driver ({old} q/s)")
        cp = (rec.get("storage") or {}).get("cache_pinning")
        if cp:
            assert cp["hit_rate_pinned"] > cp["hit_rate_blind_lru"], (
                f"serving config '{label}': schedule pinning "
                f"({cp['hit_rate_pinned']}) did not beat blind LRU "
                f"({cp['hit_rate_blind_lru']}) under a squeezed cache")
        io = rec.get("storage")
        extra = (f" pages/q={io['range_pages_per_query']:.0f}r"
                 f"/{io['knn_pages_per_query']:.0f}k"
                 f" of {io['total_pages']}") if io else ""
        if io and "prefetch" in io:
            extra += (f" prefetch_overlap="
                      f"{io['prefetch']['overlapped_rounds']}rounds")
        emit(f"serving/range_{label}", 1e6 / rec["range_qps"],
             f"qps={rec['range_qps']:.0f} shards={rec['n_shards']} "
             f"({rec['executor']}){extra}")
        emit(f"serving/knn_{label}", 1e6 / rec["knn_qps"],
             f"qps={rec['knn_qps']:.0f} rounds={rec['knn']['rounds']} "
             f"syncs={rec['knn']['host_syncs']} "
             f"driver={rec['knn'].get('driver')} "
             f"golden_qps={rec['golden']['knn_qps']:.0f}")
        fr = rec.get("frontend")
        if fr:
            emit(f"serving/frontend_{label}", 1e6 / fr["frontend_qps"],
                 f"qps={fr['frontend_qps']:.0f} "
                 f"batch_mean={fr['batch_size_mean']} "
                 f"wait_p99_ms={fr['queue_wait_ms_p99']} "
                 f"overload_shed_rate={fr['overload']['shed_rate']}")
    write_json(os.path.join(root, "BENCH_serving.json"),
               {"bench": "ServingEngine queries/sec, 1 vs N simulated "
                         "host devices (CPU-interpret kernels) + the "
                         "paged storage tier (page accesses per query, "
                         "kNN rounds / host syncs per batch, async "
                         "prefetch overlap) + the serving frontend "
                         "(dynamic batching, queue waits, shed rate, "
                         "per-replica load) with PR-4 golden-driver "
                         "baselines and pinned-vs-LRU cache hit rates",
                "batch": BATCH, "devices": results})


if __name__ == "__main__":
    if "--serving" in sys.argv:
        print(json.dumps(serving_worker()))
    else:
        print("name,us_per_call,derived")
        # the per-config workers run first, while this process is still
        # off JAX; only the full phase rewrites the committed
        # BENCH_serving.json — a BENCH_QUICK sanity run must not clobber
        # it with 1-rep numbers
        if not QUICK:
            bench_serving_scaling(real_io="--real-io" in sys.argv)
        main()
