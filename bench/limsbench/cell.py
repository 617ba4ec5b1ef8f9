"""One run of one cell: set-up, warm-up, the measured window, the
check against the reference, and the result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the configuration's corpus with ``LIMSIndex(...,
backend=<build>)``, a ``ServingEngine`` that never refreshes on its own,
and the engine's ``ServingFrontend``; then it dispatches every batch
size the cell can produce once, so nothing compiles in the window.  The
window drives the frontend from the traffic file's closed or open loop.
After it, a seeded sample of the answers is compared with brute force
(:mod:`limsbench.check`).  With ``--trace 1`` the window runs under the
profiler with the layer spans of :mod:`limsbench.instrument`, and the
per-layer metrics replace the end-to-end ones on the result line.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import check, loadgen, roofline, tracereduce, traffic
from .compiles import CompileCounter
from .spec import ROOT, load_cell, load_module

# the tuning table a run reads: the program's shipped defaults merged
# with this file, which never exists, so a user's tuning cache under
# $HOME cannot change the tiles a run uses
TUNE_CACHE = os.path.join(ROOT, ".bench-tune-cache.json")
# kernel name -> regex over "program/op" in the device trace: a Pallas
# call appears as an op named after its jitted wrapper
KERNEL_OPS = {"pdist": r"/pdist_pallas(\.\d+)?$",
              "range_filter": r"/range_filter_pallas(\.\d+)?$"}


class NoChip(RuntimeError):
    """The run is not on the accelerator (or the lane) it measures."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="One benchmark run of a cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def program_env(settings: dict, traced: bool) -> None:
    """The program's settings for a run: none inherited, the traffic
    file's ``program`` knobs, every other ``REPRO_*`` knob at its
    default, the tuning table pinned, and in a traced run a profile
    ring that holds every batch of the window."""
    for k in [k for k in os.environ if k.startswith("REPRO_")]:
        del os.environ[k]
    os.environ.update({k: str(v) for k, v in settings.items()})
    os.environ["REPRO_TUNE_CACHE"] = TUNE_CACHE
    if traced:
        os.environ["REPRO_OBS_PROFILES"] = "10000000"


def require_chip(chips: int) -> dict:
    """Refuse anything but ``chips`` or more TPU chips on the pallas lane;
    returns the device record."""
    import jax
    from repro.kernels.dispatch import kernel_mode
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
    if kernel_mode() != "pallas":
        raise NoChip(f"kernel lane is {kernel_mode()!r}, not 'pallas'")
    return device_record(chips)


def device_record(chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def compile_cache() -> str:
    """JAX's persistent cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program however
    fast it compiled, so a warm run compiles nothing."""
    import jax
    from repro.env import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ------------------------------------------------------------- set-up
def build(cfg: dict, X: np.ndarray):
    from repro.core import LIMSIndex, MetricSpace, ServingEngine
    ix = LIMSIndex(MetricSpace(X, cfg["metric"]), n_clusters=int(cfg["K"]),
                   m=int(cfg["m"]), n_rings=int(cfg["N"]),
                   backend=cfg["build"])
    return ServingEngine(ix, refresh_every=0)


def submitter(fe):
    from repro.serving import FrontendOverload

    def submit(req):
        try:
            if req.kind == "knn":
                return fe.knn_query(req.q, req.arg)
            return fe.range_query(req.q, req.arg)
        except FrontendOverload as e:
            raise loadgen.Overload() from e
    return submit


def batch_sizes(tr: dict) -> list:
    """Every batch size the cell's loop can dispatch.  A closed loop of
    one batching key (kind, and k for kNN; range radii share a batch)
    with at least two full batches of clients keeps every batch full,
    since it starts paused until all clients have queued; anything else
    can dispatch any size up to the largest."""
    mb = int(tr["frontend"]["max_batch"])
    keys = {(c["kind"], c.get("k")) for c in tr["queries"]}
    if tr["loop"] == "closed" and len(keys) == 1 and \
            int(tr["clients"]) >= 2 * mb:
        return [mb]
    return list(range(1, mb + 1))


def warm(fe, submit, pool: list, sizes: list) -> None:
    """Dispatch one batch of each size for each key the frontend batches
    by (kind, and k for kNN): the frontend is held until all B requests
    are queued, so each goes out as one batch of exactly B."""
    keys = {}
    for r in pool:
        keys.setdefault((r.kind, r.arg if r.kind == "knn" else None), r)
    with ThreadPoolExecutor(max(sizes)) as ex:
        for key in keys:
            reqs = [r for r in pool if (r.kind, r.arg if r.kind == "knn"
                                        else None) == key]
            for B in sizes:
                fe.pause()
                before = fe.metrics()["submitted"]
                futs = [ex.submit(submit, reqs[i % len(reqs)])
                        for i in range(B)]
                while fe.metrics()["submitted"] < before + B:
                    time.sleep(0.0005)
                fe.resume()
                for f in futs:
                    f.result()


def warm_range_buckets(engine, pool: list, sizes: list) -> int:
    """The compacted range path filters the batch's candidate union in
    a power-of-two bucket of rows; dispatch ``range_filter`` once at
    every bucket it can pick (128 up to half the slots) for each batch
    size.  Returns the number of buckets."""
    import jax.numpy as jnp
    from repro.kernels import ops
    snap = engine.snapshot
    top = max(128, 1 << max(int(snap.n_slots * 0.5) - 1, 1).bit_length())
    buckets = [1 << e for e in range(7, top.bit_length())]
    q = [r.q for r in pool if r.kind == "range"]
    for B in sizes:
        qf = jnp.asarray(np.stack([q[i % len(q)] for i in range(B)]),
                         jnp.float32)
        rf = jnp.ones((B,), jnp.float32)
        for b in buckets:
            ops.range_filter(qf, jnp.zeros((b, snap.d), jnp.float32),
                             rf).block_until_ready()
    return len(buckets)


# ------------------------------------------------------------- metrics
def end_to_end(cell, t0: float, seconds: float, records: list,
               setup_s: float) -> dict:
    lat = loadgen.latencies_ms(records)
    done = sum(1 for r in records
               if r.result is not None and r.done <= t0 + seconds)
    values = {"setup_s": setup_s, "qps": done / seconds}
    if len(lat):
        values["p50_ms"] = float(np.percentile(lat, 50))
        values["p95_ms"] = float(np.percentile(lat, 95))
    out = {}
    for m in cell.end_to_end:
        if m["name"] in values:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        v = load_module("metrics", m["name"], cell.root).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def memory_peak(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


# ----------------------------------------------------------------- run
class Session:
    """A served deployment in one process: corpus, index, engine and
    frontend, built once.  Several windows may run against it (the
    benchmark runs one; the control script one per seed)."""

    def __init__(self, cell, chip: bool = True, traced: bool = False):
        self.cell = cell
        program_env(cell.traffic.get("program", {}), traced)
        self.device = require_chip(cell.chips) if chip \
            else device_record(cell.chips)
        self.chip = chip
        log(f"cell {cell.name}: {cell.config_name} x {cell.traffic_name}, "
            f"compile cache {compile_cache()}")
        self.counts = CompileCounter()
        self.instr = None
        if traced:
            from .instrument import Instruments
            self.instr = Instruments()
            self.instr.install()
        cfg = cell.config
        t = time.perf_counter()
        self.X = traffic.corpus(cfg, cell.root)
        log(f"corpus {cfg['n']} x {cfg['d']}: "
            f"{time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        self.engine = build(cfg, self.X)
        snap = self.engine.snapshot
        self.shape = {"n_slots": snap.n_slots, "K": snap.K, "m": snap.m,
                      "d": snap.d}
        log(f"build ({cfg['build']}) + snapshot: "
            f"{time.perf_counter() - t:.2f} s; {self.shape}, "
            f"n_max {snap.n_max}")
        self.fe = self.engine.frontend(n_replicas=cell.chips,
                                       **cell.traffic["frontend"])
        self.submit = submitter(self.fe)
        self._warmed = False

    def traffic(self, seed: int, seconds: float) -> tuple[list, object]:
        """The window's requests (and, open loop, their due offsets)."""
        tr = self.cell.traffic
        t = time.perf_counter()
        radius = traffic.radii(self.cell.config, tr, self.X, seed)
        if radius:
            log(f"radii {radius}: {time.perf_counter() - t:.2f} s")
        if tr["loop"] == "open":
            due = traffic.arrivals(float(tr["rate"]), seconds, seed)
            return traffic.requests(tr, self.X, len(due), seed, radius), due
        return traffic.requests(tr, self.X, int(tr["pool"]), seed,
                                radius), None

    def warm(self, reqs: list) -> None:
        if self._warmed:
            return
        sizes = batch_sizes(self.cell.traffic)
        t = time.perf_counter()
        warm(self.fe, self.submit, reqs, sizes)
        from repro.kernels.dispatch import compact_enabled
        if compact_enabled() and any(r.kind == "range" for r in reqs):
            nb = warm_range_buckets(self.engine, reqs, sizes)
            log(f"warmed {nb} range buckets")
        self._warmed = True
        log(f"warm-up of {len(sizes)} batch size(s): "
            f"{time.perf_counter() - t:.2f} s; so far "
            f"{self.counts.snapshot()}")

    def window(self, reqs: list, due, seconds: float) -> dict:
        """Drive the frontend for one window (traced when the session
        is); returns what the window saw."""
        import gc

        from repro.obs import profile as _prof
        from repro.obs import registry as _reg
        tr = self.cell.traffic
        log(f"{len(gc.get_objects())} live Python objects at the window")
        c0 = self.counts.snapshot()
        n_prof = len(_prof.profiles())
        q0 = _reg.REGISTRY.counter("frontend.queries").value
        b0 = _reg.REGISTRY.counter("frontend.batches").value
        trace_dir = None
        if self.instr is not None:
            import jax
            trace_dir = tempfile.mkdtemp(prefix="trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            self.instr.recording = True
            span = jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN)
            span.__enter__()
        if tr["loop"] == "open":
            t0, records = loadgen.open_loop(self.submit, reqs, due,
                                            int(tr["workers"]))
        else:
            t0, records = loadgen.closed_loop(
                self.submit, reqs, int(tr["clients"]), seconds,
                self.fe.pause, self.fe.resume,
                lambda: self.fe.metrics()["submitted"])
        t_end = time.perf_counter()
        if trace_dir is not None:
            span.__exit__(None, None, None)
            self.instr.recording = False
            jax.profiler.stop_trace()
        c1 = self.counts.snapshot()
        inside = {k: c1[k] - c0[k] for k in c1}
        log(f"in the window: {inside['compiles']} backend compiles "
            f"({inside['compile_s']:.3f} s), {inside['traces']} traces, "
            f"{inside['cache_hits']} cache hits")
        out = {"t0": t0, "records": records, "trace_dir": trace_dir,
               "profiles": _prof.profiles()[n_prof:],
               "frontend": {
                   "queries": _reg.REGISTRY.counter(
                       "frontend.queries").value - q0,
                   "batches": _reg.REGISTRY.counter(
                       "frontend.batches").value - b0},
               "attempted": [r for r in records if r.due < t0 + seconds]}
        att = out["attempted"]
        log(f"window {seconds} s: {len(att)} attempted, "
            f"{sum(r.result is None for r in att)} failed "
            f"({sum(r.shed for r in att)} shed), "
            f"{out['frontend']['batches']} batches; closed after "
            f"{t_end - t0:.2f} s")
        return out

    def close(self) -> None:
        """Stop the frontend, take the instruments out and free the
        program's state."""
        self.fe.close()
        if self.instr is not None:
            self.instr.uninstall()
        self.fe = self.engine = self.submit = None


def judge(session: Session, win: dict, seed: int, answer_fn=None):
    """(correct, checks) of one window's answers."""
    recs = win["records"]
    unanswered = sum(1 for r in recs if r.result is None and not r.shed)
    t = time.perf_counter()
    ok, checks, n = check.judge(session.X, recs,
                                int(session.cell.traffic["check_sample"]),
                                seed, unanswered, answer_fn)
    log(f"check: {n} answers against brute force in "
        f"{time.perf_counter() - t:.2f} s")
    return ok, checks


def run(args, t_start: float, chip: bool = True,
        root: str = ROOT) -> dict:
    """One run; returns the result object (the last stdout line).
    ``chip=False`` skips the look for a TPU (the CPU tests); ``root`` is
    the checkout whose ``BENCHMARK.json`` and ``bench/`` files define
    the cell."""
    cell = load_cell(args.workload, root)
    traced = bool(args.trace)
    ses = Session(cell, chip, traced)
    reqs, due = ses.traffic(args.seed, args.seconds)
    ses.warm(reqs)
    win = ses.window(reqs, due, args.seconds)
    setup_s = win["t0"] - t_start
    peak = memory_peak(cell.chips)
    ses.close()
    ok, checks = judge(ses, win, args.seed)

    att = win["attempted"]
    result = {"correct": ok, "attempted": len(att),
              "failed": sum(1 for r in att if r.result is None)}
    device = ses.device
    if traced:
        t = time.perf_counter()
        red = tracereduce.reduce(tracereduce.extract(win["trace_dir"]),
                                 KERNEL_OPS)
        shutil.rmtree(win["trace_dir"], ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.2f} s: "
            f"busy {red['busy_s']:.4f} s of {red['window_s']:.4f} s, "
            f"kernels {red['kernel_s']} {red['kernel_events']}")
        ctx = {"profiles": win["profiles"], "frontend": win["frontend"],
               "waits_s": ses.instr.waits_s, "calls": ses.instr.calls,
               "records": att, "trace": red, "snapshot": ses.shape,
               "peak": roofline.peaks(device["kind"]) if chip else None,
               "loop": cell.traffic["loop"]}
        result["metrics"] = per_layer(cell, ctx)
        device = dict(device, busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = end_to_end(cell, win["t0"], args.seconds, att,
                                       setup_s)
    result["device"] = dict(device, memory_peak_bytes=peak)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        result = run(args, t_start)
    except NoChip as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
