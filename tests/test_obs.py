"""Observability layer (DESIGN.md §11): registry, tracing, profiles,
exporters.

Covers the obs acceptance properties: the registry is thread-safe and
its histograms are bounded reservoirs whose percentiles match numpy
bit-for-bit below the cap; ``REPRO_OBS=off`` makes every recording
helper a no-op that allocates nothing (tracemalloc-pinned); every
served batch — resident, paged, sharded — yields a *complete*
``QueryProfile``; a ``jax.profiler`` capture of served batches holds
the program's ``lims.*`` spans, nested on the batcher's thread; each
batch's host syncs, device→host bytes and compiles are its own; the
exporters emit well-formed Prometheus text; the frontend's metric
memory stays bounded under a 10k-request soak; and the buffer-pool +
prefetch counters sum to total page reads (``misses + prefetch_reads
== page_reads``).
"""
import glob
import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import LIMSIndex, MetricSpace
from repro.core.executor import QueryExecutor, ShardedExecutor, _pack_width
from repro.core.metrics import dist_one_to_many
from repro.core.snapshot import LIMSSnapshot
from repro.obs import registry as _reg
from repro.obs.registry import Counter, Histogram, MetricsRegistry
from repro.obs.trace import _NULL, span

N, D = 900, 5


@pytest.fixture(autouse=True)
def _restore_mode():
    """Tests flip the cached obs mode; put it back for the rest of the
    suite (metric *values* are process-global and harmless to leave)."""
    before = obs.obs_mode()
    yield
    obs.configure(before)


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    from repro.data.datasets import gauss_mix
    X = gauss_mix(N, D, seed=7)
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=5, m=3, n_rings=8)
    snap = LIMSSnapshot.build(ix)
    path = str(tmp_path_factory.mktemp("obs-store"))
    snap.spill(path)
    rng = np.random.default_rng(3)
    Q = X[rng.choice(N, 8)] + rng.normal(0, 0.004, (8, D))
    rs = np.array([float(np.quantile(dist_one_to_many(q, X, "l2"), 0.02))
                   for q in Q])
    return X, ix, snap, path, Q, rs


# ---------------------------------------------------------------- registry
def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    assert reg.counter("a.b") is c
    with pytest.raises(TypeError):
        reg.gauge("a.b")
    reg.histogram("a.h").observe(2.0)
    g = reg.gauge("a.g")
    g.set(3.5)
    snap = reg.snapshot()
    assert snap["a.b"] == 0 and snap["a.g"] == 3.5
    assert snap["a.h"]["count"] == 1
    reg.reset()
    assert reg.snapshot()["a.h"]["count"] == 0
    assert reg.counter("a.b") is c          # reset keeps registrations


def test_registry_thread_safety():
    """Concurrent increments and observations lose nothing: counts and
    sums are exact (each metric's lock), and get-or-create under racing
    threads yields one object per name."""
    reg = MetricsRegistry()
    n_threads, per = 8, 2000

    def worker(i: int) -> None:
        for j in range(per):
            reg.counter("t.count").inc()
            reg.histogram("t.hist", cap=64).observe(float(j))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("t.count").value == n_threads * per
    h = reg.histogram("t.hist")
    assert h.count == n_threads * per
    assert h.sum == pytest.approx(n_threads * sum(range(per)))
    assert len(h) == 64                     # reservoir stayed bounded
    assert h.min == 0.0 and h.max == float(per - 1)


def test_histogram_percentiles_match_numpy():
    """Below the cap the reservoir holds everything, so percentiles are
    exact — bit-identical to numpy's default linear interpolation."""
    rng = np.random.default_rng(11)
    xs = rng.lognormal(0.0, 1.5, 500)
    h = Histogram("pct.test", cap=1024)
    for x in xs:
        h.observe(float(x))
    for p in (0.0, 25.0, 50.0, 90.0, 99.0, 100.0):
        assert h.percentile(p) == float(np.percentile(xs, p))
    assert h.mean == pytest.approx(float(np.mean(xs)))


def test_histogram_reservoir_bounded_stats_exact():
    """Past the cap, memory stays O(cap) while count/sum/min/max remain
    exact and percentiles stay plausible (uniform reservoir sample)."""
    h = Histogram("res.test", cap=128)
    n = 10_000
    for i in range(n):
        h.observe(float(i))
    assert len(h) == 128 and h.count == n
    assert h.min == 0.0 and h.max == float(n - 1)
    assert h.sum == pytest.approx(n * (n - 1) / 2)
    p50 = h.percentile(50)
    assert 0.2 * n < p50 < 0.8 * n          # sampled median is sane


def test_mode_gating_and_configure():
    obs.configure("off")
    assert not _reg.enabled()
    assert span("x") is _NULL               # shared no-op singleton
    obs.configure("on")
    assert _reg.enabled()
    assert span("x") is not _NULL
    # a trace is a jax.profiler capture, not a mode
    for bad in ("loud", "trace"):
        with pytest.raises(ValueError):
            obs.configure(bad)


def test_off_mode_records_and_allocates_nothing():
    """The disabled path is one string compare: no metric mutation and
    zero allocations attributable to the obs modules (the contract that
    makes default-on instrumentation of hot paths acceptable)."""
    import time
    import tracemalloc

    import repro.obs.registry as regmod
    import repro.obs.trace as trmod
    from repro.storage.prefetch import drain_queue

    def quiesce():
        # background work from earlier tests runs obs calls off the main
        # thread (the prefetch worker pins pages -> set_gauge; transient
        # engine-refresh threads count refreshes), and a frame allocated
        # there is charged to registry.py: wait for transient threads to
        # exit, then drain the shared prefetch worker's queue
        deadline = time.monotonic() + 30.0
        persistent = {"MainThread", "lims-page-prefetch"}
        while time.monotonic() < deadline:
            if all(t.name in persistent for t in threading.enumerate()):
                break
            time.sleep(0.05)
        assert drain_queue(timeout=30.0)

    quiesce()
    obs.configure("on")
    obs.count("offtest.c")                  # materialize the metrics
    obs.observe("offtest.h", 1.0)
    before = obs.REGISTRY.counter("offtest.c").value
    obs.configure("off")
    for attempt in range(5):
        for _ in range(50):                 # settle frame freelists etc.
            obs.count("offtest.c")
            obs.observe("offtest.h", 2.0)
            obs.set_gauge("offtest.g", 3.0)
            with span("offtest.span"):
                pass
        tracemalloc.start()
        try:
            for _ in range(200):
                obs.count("offtest.c")
                obs.observe("offtest.h", 2.0)
                obs.set_gauge("offtest.g", 3.0)
                with span("offtest.span"):
                    pass
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        obs_alloc = sum(
            st.size for st in snap.statistics("filename")
            if st.traceback[0].filename in (regmod.__file__, trmod.__file__))
        if obs_alloc == 0:
            break
        quiesce()                           # a straggler landed mid-window
    assert obs_alloc == 0
    assert obs.REGISTRY.counter("offtest.c").value == before
    assert obs.REGISTRY.histogram("offtest.h").count == 1


# ---------------------------------------------------------------- profiles
def _assert_complete(p, *, kind, backend, storage):
    assert p is not None, "no QueryProfile was recorded"
    assert p.missing() == [], f"incomplete profile: {p.missing()}"
    assert p.kind == kind and p.backend == backend and p.storage == storage
    assert p.batch > 0 and p.rounds >= 1 and p.n_clusters > 0
    assert p.total_s > 0
    assert all(v >= 0 for v in p.stages.values())
    if storage == "resident":
        assert p.pages == 0 and p.pages_per_query == 0
    else:
        assert p.pages > 0 and p.pages_per_query > 0


def test_profile_resident_complete(setup):
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    ex = QueryExecutor(snap)
    ex.knn_query_batch(Q, 5)
    _assert_complete(ex.last_profile, kind="knn", backend="resident",
                     storage="resident")
    assert ex.last_profile.k == 5
    assert ex.last_profile.candidates_per_query >= 5
    ex.range_query_batch(Q, rs)
    _assert_complete(ex.last_profile, kind="range", backend="resident",
                     storage="resident")
    assert ex.last_profile.k is None
    assert obs.last_profile() is ex.last_profile


def test_profile_paged_complete(setup):
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    paged = LIMSSnapshot.load(path, store=True, cache_pages=8)
    ex = QueryExecutor(paged)
    ex.knn_query_batch(Q, 5)
    _assert_complete(ex.last_profile, kind="knn", backend="paged",
                     storage="paged")
    ex.range_query_batch(Q, rs)
    _assert_complete(ex.last_profile, kind="range", backend="paged",
                     storage="paged")


def test_profile_sharded_complete(setup):
    import jax
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    sx = ShardedExecutor(snap)
    sx.knn_query_batch(Q, 5)
    _assert_complete(sx.last_profile, kind="knn", backend="resident",
                     storage="resident")
    assert sx.last_profile.n_shards == jax.device_count()


def test_profile_off_mode_records_nothing(setup):
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    ex = QueryExecutor(snap)
    ex.knn_query_batch(Q, 3)
    obs.clear_profiles()
    obs.configure("off")
    ex.knn_query_batch(Q, 3)
    assert obs.last_profile() is None


def test_profile_ring_bounded(setup):
    from repro.obs.profile import profile_cap
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    obs.clear_profiles()
    ex = QueryExecutor(snap)
    for _ in range(3):
        ex.knn_query_batch(Q[:2], 3)
    assert 0 < len(obs.profiles()) <= profile_cap()
    assert obs.profiles(1) == [obs.last_profile()]


# ---------------------------------------------------------------- exporters
def test_prometheus_text_format():
    obs.configure("on")
    reg = obs.REGISTRY
    reg.counter("exp.count").inc(7)
    reg.gauge("exp.gauge").set(2.5)
    h = reg.histogram("exp.hist")
    for x in range(10):
        h.observe(float(x))
    text = obs.prometheus_text()
    lines = text.splitlines()
    assert text.endswith("\n")
    assert "# TYPE lims_exp_count counter" in lines
    assert "lims_exp_count 7" in lines
    assert "# TYPE lims_exp_gauge gauge" in lines
    assert "lims_exp_gauge 2.5" in lines
    assert "# TYPE lims_exp_hist summary" in lines
    assert 'lims_exp_hist{quantile="0.5"} 4.5' in lines
    assert "lims_exp_hist_count 10" in lines
    assert "lims_exp_hist_sum 45" in lines
    # every non-comment line is `name[{labels}] value` with a legal name
    for ln in lines:
        if ln.startswith("#"):
            continue
        name = ln.split("{")[0].split(" ")[0]
        assert name.startswith("lims_")
        assert all(c.isalnum() or c == "_" for c in name)


def test_json_snapshot_round_trips(setup):
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    QueryExecutor(snap).knn_query_batch(Q, 3)
    doc = obs.json_snapshot(n_profiles=4)
    assert doc["mode"] == "on"
    assert doc["profiles"] and doc["profiles"][-1]["kind"] == "knn"
    assert "profile.batches" in doc["metrics"]
    json.dumps(doc)                         # fully JSON-serializable


def test_report_demo_smoke(tmp_path):
    """The packaged reporter end-to-end: demo workload under a profiler
    capture, both exports, complete profile asserted inside."""
    from repro.obs import report
    out_json = str(tmp_path / "obs.json")
    out_prom = str(tmp_path / "obs.prom")
    out_trace = str(tmp_path / "obs-trace")
    rc = report.main(["--demo", "--json", out_json, "--prom", out_prom,
                      "--trace", out_trace])
    assert rc == 0
    with open(out_json) as f:
        doc = json.load(f)
    assert doc["profiles"]
    with open(out_prom) as f:
        assert "lims_" in f.read()
    assert glob.glob(f"{out_trace}/**/*.xplane.pb", recursive=True)
    assert glob.glob(f"{out_trace}/**/perfetto_trace.json.gz",
                     recursive=True)
    with pytest.raises(SystemExit):          # --trace captures the demo
        report.main(["--trace", out_trace])


# ----------------------------------------------------- frontend boundedness
def test_frontend_soak_memory_bounded(setup):
    """10k requests' worth of metric accounting holds O(reservoir)
    state — the unbounded `_waits`/`_batch_sizes` lists this PR removed
    would hold 10k floats here."""
    from repro.serving import ServingFrontend
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    fe = ServingFrontend(QueryExecutor(snap), max_batch=8, slo_ms=1.0)
    try:
        fe.knn_query(Q[0], 3)               # one real served request
        # …then the soak drives the per-batch accounting path directly
        # (serving 10k real queries through interpret-mode kernels is
        # minutes of test time for the same metric-path coverage)
        for i in range(9_999):
            fe._obs_record(1, [1e-4])
        m = fe.metrics()
        assert m["batches"] == 10_000
        cap = fe._wait_hist.cap
        assert len(fe._wait_hist) <= cap
        assert len(fe._size_hist) <= cap
        assert m["queue_wait_ms_p50"] >= 0
        # the registry mirrors are bounded the same way
        assert len(obs.REGISTRY.histogram("frontend.queue_wait_s")) <= \
            obs.REGISTRY.histogram("frontend.queue_wait_s").cap
    finally:
        fe.close()


# ------------------------------------------------------- storage invariant
def test_prefetch_reads_sum_to_page_reads(setup):
    """Speculative (record=False) reads are no longer invisible: the
    buffer-pool misses plus the explicit prefetch_reads counter equal
    every page actually read into the cache."""
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    paged = LIMSSnapshot.load(path, store=True, cache_pages=64)
    st = paged.store
    st.cache.clear()
    st.stats.reset()
    total = st.manifest.total_pages
    demand = np.arange(0, min(4, total), dtype=np.int64)
    spec = np.arange(0, min(8, total), dtype=np.int64)
    st.fetch_pages(demand)                  # demand path: misses
    st.fetch_pages(spec, record=False)      # speculative: prefetch_reads
    st.fetch_pages(demand)                  # warm: hits, no reads
    s = st.stats.snapshot()
    assert s["misses"] == len(demand)
    assert s["prefetch_reads"] == len(spec) - len(demand)
    assert s["page_reads"] == s["misses"] + s["prefetch_reads"]
    # and the set actually resident is exactly what was read
    assert s["page_reads"] == len(set(spec) | set(demand))


# ----------------------------------------------- per-batch costs, traced
def _frontend(snap, B):
    from repro.serving import ServingFrontend
    return ServingFrontend(QueryExecutor(snap), n_replicas=1, max_batch=B,
                           slo_ms=1000.0)


def _serve_batch(fe, Q, kind, arg):
    """Dispatch the rows of ``Q`` as exactly one frontend batch: held
    until every request has queued, then released."""
    submit = fe.knn_query if kind == "knn" else fe.range_query
    fe.pause()
    before = fe.metrics()["submitted"]
    threads = [threading.Thread(target=submit, args=(q, arg)) for q in Q]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60.0
    while fe.metrics()["submitted"] < before + len(Q):
        assert time.monotonic() < deadline
        time.sleep(0.001)
    fe.resume()
    for t in threads:
        t.join(120.0)
        assert not t.is_alive()


_BATCH_SPANS = ("frontend.execute", "router.assign", "planner.plan_knn",
                "planner.plan_range", "executor.knn_execute",
                "executor.range_execute", "executor.refine",
                "executor.d2h", "obs.profile")


def test_profiler_capture_holds_program_spans(setup, tmp_path):
    """Served batches under a jax.profiler capture (CPU): every program
    span of a batch is a ``lims.*`` event on the batcher thread's line,
    inside its ``frontend.execute`` (which carries the batch's sequence
    number), and each kNN execute holds its device→host copy."""
    import jax
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    fe = _frontend(snap, 4)
    try:
        _serve_batch(fe, Q[:4], "knn", 3)           # compiles outside
        _serve_batch(fe, Q[:4], "range", float(rs[0]))
        with jax.profiler.trace(str(tmp_path)):
            _serve_batch(fe, Q[4:8], "knn", 3)
            _serve_batch(fe, Q[4:8], "range", float(rs[0]))
    finally:
        fe.close()
    pb = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    lines = [line for plane in jax.profiler.ProfileData.from_file(pb).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    line = next(ln for ln in lines if any(
        e.name == "lims.frontend.execute" for e in ln.events))
    ev = [(e.name[len("lims."):], e.start_ns, e.start_ns + e.duration_ns,
           dict(e.stats)) for e in line.events
          if e.name.startswith("lims.")]
    assert set(_BATCH_SPANS) <= {e[0] for e in ev}
    outer = [e for e in ev if e[0] == "frontend.execute"]
    assert [e[3]["seq"] for e in outer] == [3, 4]
    for name, a, b, _ in ev:
        assert any(o[1] <= a and b <= o[2] for o in outer), name
    for _, a, b, _ in [e for e in ev if e[0] == "executor.knn_execute"]:
        assert any(e[0] == "executor.d2h" and a <= e[1] and e[2] <= b
                   for e in ev)


def test_frontend_host_syncs_same_every_batch(setup):
    """Regression: through the frontend, a batch's host syncs are its
    own — the same count on every one of consecutive equal batches, not
    a per-thread total that grows batch after batch."""
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    obs.clear_profiles()
    fe = _frontend(snap, 4)
    try:
        for _ in range(6):
            _serve_batch(fe, Q[:4], "knn", 5)
    finally:
        fe.close()
    syncs = [p.host_syncs for p in obs.profiles()]
    # the plan's seed distances, the router's routing, the loop's masks
    assert syncs == [3] * 6


@pytest.mark.parametrize("kind,compact", [
    ("knn", "on"), ("range", "off"), ("range", "on")])
def test_d2h_bytes_match_shapes(setup, monkeypatch, kind, compact):
    """A routed batch's ``d2h_bytes`` is exactly what its copies hold:
    kNN — (B, K, m) f32 seed distances, (B, K) bool routing, the
    banded candidate mask packed into (B, W) uint32 words, the int32
    round count and the certified set's (B,) int32 row and cluster
    counts; range — routing and the packed hits; with compaction on, the
    (B, n_slots) bool host candidate mask it reads comes first, and the
    (B, bucket) uint8 ball replaces the packed hits unless the union is
    too large to gather.  No sync is added for the packing or the
    counts: kNN syncs 3 times (seed, routing, words with the round count
    and the counts), range twice (routing,
    hits) or, compacted, three times (routing, mask, ball); every batch
    whose mask is computed on the device counts
    ``executor.packed_batches``, every other ``executor.dense_batches``."""
    monkeypatch.setenv("REPRO_COMPACT", compact)
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    fe = _frontend(snap, 4)
    packed_n = obs.REGISTRY.counter("executor.packed_batches")
    dense_n = obs.REGISTRY.counter("executor.dense_batches")
    before = packed_n.value, dense_n.value
    try:
        _serve_batch(fe, Q[:4], kind, 5 if kind == "knn" else float(rs[0]))
        ex = fe._router_obj.routing_ex
        p = ex.last_profile
    finally:
        fe.close()
    s = ex.snap
    B, K, m = 4, s.K, s.m
    packed = 4 * B * _pack_width(s.n_slots)
    want = B * K
    lc = ex.last_compact
    if kind == "knn":
        want += B * K * m * 4 + packed + 4 + 2 * 4 * B
    elif compact == "on":
        want += B * s.n_slots + (packed if lc is None else B * lc["bucket"])
    else:
        want += packed
    assert p.d2h_bytes == want
    assert p.host_syncs == (3 if kind == "knn" or compact == "on" else 2)
    gathered = kind == "range" and compact == "on" and lc is not None
    assert (packed_n.value, dense_n.value) == \
        (before[0] + (not gathered), before[1] + gathered)
    assert p.stages["d2h"] > 0 and p.stages["route"] > 0


@pytest.mark.parametrize("kind", ["knn", "range"])
def test_split_batch_charges_each_copy_once(setup, monkeypatch, kind):
    """A batch the router splits across replicas charges its planning
    and routing copies to one sub-batch: summed over the batch's
    profiles, ``d2h_bytes`` and ``host_syncs`` are exactly the batch's
    copies — kNN: the (n, K, m) f32 seed distances and (n, K) bool
    routing once, at the n rows the batch is planned at, then each
    sub-batch's packed (cap, W) uint32 mask, padded to the replica's
    cap rows, int32 round count and (cap,) int32 certified row and
    cluster counts; range: the routing once, then
    each sub-batch's packed hits."""
    from repro.serving import ServingFrontend
    monkeypatch.setenv("REPRO_COMPACT", "off")
    X, ix, snap, path, Q, rs = setup
    obs.configure("on")
    fe = ServingFrontend(QueryExecutor(snap), n_replicas=4, max_batch=8,
                         slo_ms=1000.0)
    try:
        _serve_batch(fe, Q, kind, 5)             # compiles outside
        obs.clear_profiles()
        _serve_batch(fe, Q, kind, 5 if kind == "knn" else float(rs[0]))
    finally:
        fe.close()
    ps = obs.profiles()
    G = len(ps)
    assert G > 1                                 # the batch was split
    assert sum(p.batch for p in ps) == len(Q)
    K, m = snap.K, snap.m
    n, cap = fe._router_obj.shapes(len(Q))
    packed = 4 * G * cap * _pack_width(snap.n_slots)
    if kind == "knn":
        want = n * K * m * 4 + n * K + packed + 4 * G + 2 * 4 * G * cap
        syncs = 2 + G
    else:
        want = n * K + packed
        syncs = 1 + G
    assert sum(p.d2h_bytes for p in ps) == want
    assert sum(p.host_syncs for p in ps) == syncs
    assert sum(p.stages["route"] > 0 for p in ps) == 1


def test_compiles_charged_to_the_compiling_batch(setup):
    """A batch of a new size compiles; that batch's profile counts the
    compiles and no batch before or after it does."""
    from repro.data.datasets import gauss_mix
    X = gauss_mix(613, 7, seed=29)          # shapes no other test serves
    ix = LIMSIndex(MetricSpace(X, "l2"), n_clusters=3, m=2, n_rings=5)
    Q = X[:5] + 0.002
    obs.configure("on")
    fe = _frontend(LIMSSnapshot.build(ix), 5)
    try:
        _serve_batch(fe, Q[:4], "knn", 4)       # first size: compiles
        obs.clear_profiles()
        for n in (4, 3, 4, 3):
            _serve_batch(fe, Q[:n], "knn", 4)
    finally:
        fe.close()
    compiles = [p.compiles for p in obs.profiles()]
    assert compiles[0] == 0 and compiles[1] > 0
    assert compiles[2:] == [0, 0]
