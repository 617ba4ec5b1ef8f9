"""Retrieval serving: the paper's index as the framework's retrieval layer.

An LM encodes queries into its embedding space; LIMS answers *exact* kNN
over a corpus of embeddings. Serving runs through the layered stack
(DESIGN.md §1): a ``BatchedLIMS`` snapshot executor first (the whole
query batch through the Pallas kernels `pdist` → `rankeval` →
`range_filter` in one launch sequence — compiled on TPU/GPU, interpreted
on CPU), then the full ``ServingEngine`` lifecycle: online inserts with
double-buffered snapshot refresh, auto-sharding across every visible
device — and finally the ``ServingFrontend`` (DESIGN.md §9), which
coalesces concurrent single-query submitters into kernel batches and
routes them across a replica set, bit-identically. The host index
answers the same queries as a cross-check; both are exact. This is the
deployment story in DESIGN.md §2: the index serves the models the
framework trains.

    PYTHONPATH=src python examples/retrieval_serving.py
    # exercise the cluster-sharded executor on fake host devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/retrieval_serving.py
"""
import glob
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import LIMSIndex, MetricSpace, ServingEngine
from repro.core.batched import BatchedLIMS
from repro.core.metrics import dist_one_to_many
from repro.models import zoo
from repro.models.params import init_params
from repro.models.transformer import forward_seq


def main() -> None:
    # 1) a small encoder LM produces the embedding space
    cfg = ModelConfig(
        name="encoder-20m", family="dense", n_layers=4, d_model=256,
        n_heads=4, n_kv_heads=4, d_ff=1024, vocab=8192, head_dim=64,
        attn_impl="dense", remat="none", dtype="float32")
    params = init_params(zoo.model_specs(cfg), jax.random.PRNGKey(0),
                         cfg.dtype)

    @jax.jit
    def encode(tokens):
        x, _, _ = forward_seq(params, tokens, cfg)
        # mean-pool, then matryoshka-style truncation to 32 dims: metric
        # indexes live in moderate intrinsic dimension (the paper evaluates
        # ≤65d); retrieval is exact in the indexed embedding space
        return x.mean(axis=1)[:, :32]

    rng = np.random.default_rng(0)
    # a realistic corpus clusters by topic: 100 anchor docs, 50 noisy
    # variants each (edit a few tokens) — similar docs ⇒ nearby embeddings
    anchors = rng.integers(0, cfg.vocab, (100, 32))
    corpus_tokens = np.repeat(anchors, 50, axis=0)
    for i in range(5_000):
        corpus_tokens[i, rng.integers(0, 32)] = rng.integers(0, cfg.vocab)
    corpus = np.asarray(encode(jnp.asarray(corpus_tokens)))
    print(f"corpus: {corpus.shape[0]:,} docs embedded to d={corpus.shape[1]}")

    # 2) LIMS indexes the embedding corpus (exact metric index)
    sp = MetricSpace(corpus.astype(np.float64), "l2")
    # K should track the corpus's natural cluster count (the paper's
    # OR+λMAE elbow finds this automatically; here the corpus has 100
    # topics, so clusters must be at least that fine to be tight)
    ix = LIMSIndex(sp, n_clusters=100, m=3, n_rings=20)
    print(f"LIMS built in {ix.build_time_s:.2f}s "
          f"({ix.index_nbytes()/2**20:.2f} MiB index)")

    # 3) serve batched queries: encode -> exact kNN (queries are noisy
    # variants of corpus docs, the retrieval workload)
    q_tokens = np.repeat(anchors[:16], 1, axis=0)
    for i in range(16):
        q_tokens[i, rng.integers(0, 32)] = rng.integers(0, cfg.vocab)
    # calibrate the kNN radius step Δr to the neighbor-distance scale
    # (Alg. 2 takes Δr as input; too-large steps overshoot the kth ball)
    probe = sp.data[rng.choice(sp.n, 64)]
    nn_scale = np.median([np.partition(
        dist_one_to_many(p, sp.data, "l2"), 6)[6] for p in probe])
    q_emb = np.asarray(encode(jnp.asarray(q_tokens)))
    t0 = time.perf_counter()          # time the serving loop, not encoding
    pages = 0
    for i, q in enumerate(q_emb.astype(np.float64)):
        ids, ds, st = ix.knn_query(q, 5, delta_r=float(nn_scale) / 2)
        pages += st.pages
        truth = np.argsort(dist_one_to_many(q, sp.data, "l2"))[:5]
        assert abs(np.sort(ds)[-1] -
                   dist_one_to_many(q, sp.data, "l2")[truth[-1]]) < 1e-9, \
            "retrieval must be exact"
    dt = time.perf_counter() - t0
    total_pages = -(-sp.n // ix.clusters[0].store.omega)
    print(f"16 queries: {dt*1e3:.1f} ms end-to-end, "
          f"avg pages/query={pages/16:.1f} "
          f"(corpus is {total_pages} pages — "
          f"{total_pages/(pages/16):.0f}x less I/O than a scan)")
    print("all 16 kNN results verified exact. OK")

    # 4) the batched serving path: one snapshot, the whole query batch
    # through the Pallas kernels in a single launch sequence
    bx = BatchedLIMS(ix)
    # warm-up with the serving batch shape (jit caches key on shapes)
    bx.knn_query_batch(q_emb.astype(np.float64), 5)
    t0 = time.perf_counter()
    ids_b, ds_b = bx.knn_query_batch(q_emb.astype(np.float64), 5)
    dt_b = time.perf_counter() - t0
    for i, q in enumerate(q_emb.astype(np.float64)):
        d_all = dist_one_to_many(q, sp.data, "l2")
        assert abs(np.sort(ds_b[i])[-1] - np.sort(d_all)[4]) < 1e-9, \
            "batched retrieval must be exact"
    print(f"batched engine: 16 queries in {dt_b*1e3:.1f} ms "
          f"({16/dt_b:.0f} q/s, {dt/dt_b:.1f}x vs per-query host serving); "
          f"all 16 verified exact. OK")

    # 5) the serving frontend: online updates + double-buffered snapshot
    # refresh, auto-sharded across every visible device (DESIGN.md §4-5)
    # build_backend pinned so the retrain demo below takes the device
    # path even on CPU-interpret (the default resolves by dispatch
    # policy: device wherever the kernels compile)
    se = ServingEngine(ix, refresh_every=8, build_backend="device")
    ex = se.executor
    print(f"ServingEngine: {type(ex).__name__} over "
          f"{getattr(ex, 'n_shards', 1)} of {jax.device_count()} device(s)")
    # new docs arrive while serving: 8 fresh variants of anchor 0
    fresh_tokens = np.repeat(anchors[:1], 8, axis=0)
    for i in range(8):
        fresh_tokens[i, rng.integers(0, 32)] = rng.integers(0, cfg.vocab)
    fresh = np.asarray(encode(jnp.asarray(fresh_tokens)), np.float64)
    gids = [se.insert(row) for row in fresh]        # 8th insert → refresh
    assert se.generation == 1, "refresh_every=8 must have fired"
    ids_f, ds_f = se.knn_query_batch(fresh, 1)
    assert [int(i) for i in ids_f[:, 0]] == gids, \
        "each fresh doc must be its own exact 1-NN after the swap"
    print(f"inserted {len(gids)} docs; snapshot generation "
          f"{se.generation} swapped in, all {len(gids)} retrievable. OK")

    # 6) device-side (re)builds: the whole §4 build pipeline — batched
    # clustering, FFT pivots, pdist-kernel distance columns, every rank/
    # position model in one least-squares launch — runs through
    # repro.build (DESIGN.md §6); results stay exact because all bounds
    # are recomputed exactly at materialization
    t0 = time.perf_counter()
    ix_dev = LIMSIndex(MetricSpace(sp.data, "l2"), n_clusters=100, m=3,
                       n_rings=20, backend="device")
    t_dev = time.perf_counter() - t0
    q0 = q_emb.astype(np.float64)[0]
    _, ds_d, _ = ix_dev.knn_query(q0, 5, delta_r=float(nn_scale) / 2)
    truth = np.sort(dist_one_to_many(q0, sp.data, "l2"))[:5]
    # (the serving engine above already folded fresh docs into `ix`, so
    # the freshly device-built index is checked against ground truth
    # over its own corpus)
    assert np.array_equal(np.sort(ds_d), truth), \
        "device-built index must be exact"
    print(f"device builder: full rebuild in {t_dev:.2f}s vs "
          f"{ix.build_time_s:.2f}s host build; exact 5-NN verified. OK")

    # online retrain of a dirty cluster through the device builder:
    # fold the freshest cluster's insert buffer into its ring structure
    dirty = max(range(ix.K), key=lambda c: len(ix.clusters[c].buf_ids))
    t0 = time.perf_counter()
    se.retrain_cluster(dirty)                       # device-routed
    t_retrain = time.perf_counter() - t0
    t0 = time.perf_counter()
    ix.retrain_cluster(dirty, backend="host")       # now-idempotent rerun
    t_host_retrain = time.perf_counter() - t0
    ids_f, _ = se.knn_query_batch(fresh, 1)
    assert [int(i) for i in ids_f[:, 0]] == gids, \
        "retrained cluster must still serve every folded-in doc"
    print(f"retrain_cluster({dirty}): {t_retrain*1e3:.0f} ms via the "
          f"device builder ({t_host_retrain*1e3:.0f} ms host rerun); "
          f"all inserts still retrievable. OK")

    # 7) the paged storage tier (DESIGN.md §7): spill the snapshot to
    # disk — rows laid out in learned-position page extents — then
    # cold-start a fresh replica from the spilled directory.  Only the
    # manifest + metadata load up front; row pages fault in on demand,
    # driven by the certified candidate intervals, so the learned
    # positions finally do the job the paper built them for: deciding
    # which disk pages a query touches.
    spill_dir = tempfile.mkdtemp(prefix="lims-spill-")
    t0 = time.perf_counter()
    manifest = ix.spill(spill_dir)
    t_spill = time.perf_counter() - t0
    t0 = time.perf_counter()
    cold = ServingEngine.from_spill(spill_dir)
    t_cold = time.perf_counter() - t0
    ids_cold, _ = cold.knn_query_batch(fresh, 1)
    assert [int(i) for i in ids_cold[:, 0]] == gids, \
        "cold-started replica must serve the spilled snapshot exactly"
    io = cold.executor.last_io
    st = cold.store.stats.snapshot()
    print(f"paged store: spilled {manifest.total_pages} pages "
          f"({cold.store.nbytes_file()/2**20:.1f} MiB) in {t_spill:.2f}s; "
          f"cold start in {t_cold:.2f}s")
    print(f"cold replica: batch of {len(fresh)} kNN queries touched "
          f"{io['pages']} pages ({st['pages_per_query']:.1f}/query, "
          f"{st['candidates_per_query']:.0f} candidates/query, cache hit "
          f"rate {st['hit_rate']:.0%}); results match the warm engine. OK")

    # 8) the serving frontend (DESIGN.md §9): real traffic is single
    # queries from many clients, not pre-assembled batches.  The
    # frontend coalesces concurrent submitters into kernel-shaped
    # batches under a latency SLO and routes each batch's sub-batches
    # across a replica set (one replica per device) by the batch's own
    # CandidatePlan — per-query results stay bit-identical to a direct
    # executor call, so batching and routing are pure performance.
    import threading
    with cold.frontend(max_batch=16, slo_ms=10.0, max_queue=64) as fe:
        got = [None] * len(fresh)
        threads = [threading.Thread(
            target=lambda j=j: got.__setitem__(j, fe.knn_query(fresh[j], 1)))
            for j in range(len(fresh))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert [int(ids[0]) for ids, _ in got] == gids, \
            "frontend results must equal the direct executor's"
        m = fe.metrics()
    repl = m["routing"]["replicas"]
    print(f"frontend: {m['submitted']} concurrent submitters → "
          f"{m['batches']} kernel batch(es) "
          f"(mean size {m['batch_size_mean']}, queue wait "
          f"p99 {m['queue_wait_ms_p99']:.1f} ms, shed rate "
          f"{m['shed_rate']:.0%}) over {len(repl)} replica(s); "
          f"all results exact. OK")

    # 9) observability (DESIGN.md §11): everything above was also being
    # measured.  Every span on the query path — frontend coalescing,
    # plan construction, kernel execution, device→host copies — is a
    # lims.* annotation in a jax.profiler capture, beside the device's
    # ops; every served batch yields a structured QueryProfile (the
    # paper's per-query costs: pages, candidates, pruning power, rounds,
    # bytes copied, per-stage latency), and the registry holds the
    # long-run counters and latency histograms.
    from repro import obs
    obs.configure("on")
    trace_dir = os.path.join(spill_dir, "serving-trace")
    with jax.profiler.trace(trace_dir):
        cold.knn_query_batch(fresh, 1)      # one captured batch
    prof = cold.executor.last_profile
    assert prof is not None and prof.missing() == [], \
        f"served batch must yield a complete QueryProfile: {prof}"
    assert glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True), \
        "the capture must write a profiler trace"
    d = prof.as_dict()
    print(f"observability: {d['kind']} batch of {d['batch']} on "
          f"{d['backend']}/{d['storage']} → profile: "
          f"{d['pages_per_query']:.1f} pages/query, "
          f"{d['candidates_per_query']:.0f} candidates/query, "
          f"{d['clusters_per_query']:.1f}/{d['n_clusters']} clusters, "
          f"{d['rounds']} round(s), stages "
          f"{ {k: round(v, 2) for k, v in d['stages_ms'].items()} } ms, "
          f"{d['d2h_bytes']} bytes to the host; profiler trace -> "
          f"{trace_dir}. OK")

    # 10) continuous health monitoring (DESIGN.md §12): inject placement
    # drift — pin every cluster's ownership to replica 0 while query
    # heat stays spread — then drive manual monitor ticks and watch the
    # closed loop repair it: the heat-skew detector fires a finding, the
    # MonitorDaemon rebalances ownership from live heat (within its
    # action cooldown), and the health report shows the recovery.
    # Results stay bit-identical throughout: ownership only biases
    # routing, never answers.
    from repro.obs.monitor import Monitor
    from repro.serving import MonitorDaemon, PlanRouter, ReplicaSet
    snap = cold.executor.snap
    replicas = ReplicaSet(snap, n_replicas=4)
    router = PlanRouter(replicas, max_batch=len(fresh))
    mon = Monitor(interval=3600.0)          # ticked by hand below
    daemon = MonitorDaemon(mon, lambda: router, engine=cold,
                           cooldown_ticks=3)
    baseline_ids, _ = router.knn_query_batch(fresh, 3)
    replicas.set_ownership(np.zeros(snap.K, np.int64))   # the drift
    for _ in range(6):
        ids, _ = router.knn_query_batch(fresh, 3)
        assert np.array_equal(ids, baseline_ids), \
            "results must stay exact under drift and rebalance"
        mon.tick()
    findings = [f for f in mon.findings() if f.detector == "heat_skew"]
    rebalances = [e for e in daemon.events() if e["action"] == "rebalance"]
    assert findings and rebalances, \
        "injected drift must fire a finding and a rebalance"
    from repro.obs.report import render_health
    print(render_health(mon, daemon))
    print(f"monitoring: drift skew {findings[0].value:.1f}x fired at "
          f"tick {findings[0].tick}, daemon rebalanced at tick "
          f"{rebalances[0]['tick']}, post-rebalance skew "
          f"{mon.store.get('router.heat_skew').last():.2f}x; results "
          f"bit-identical throughout. OK")


if __name__ == "__main__":
    from repro.env import use_compile_cache
    use_compile_cache()
    main()
