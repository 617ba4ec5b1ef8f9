"""Per-backend kernel tile autotuner with a persistent tuning table.

The static heuristics in ``ops.local_blocks``/``ops._point_block`` pick
one tile size per kernel regardless of backend or operand shape.  This
module replaces them on the compiled lanes with a measured table:

* **Key**: ``backend/kernel/metric/dim=bucket…`` where ``backend`` is
  ``dispatch.backend_key()`` (xla-cpu | tpu | gpu) and every operand
  dimension is bucketed to the next power of two (floor 8) — one entry
  covers a whole shape bucket, and because ``ops`` pads operands to tile
  multiples anyway, tuning at the bucket shape measures the same
  computation the serving path runs.
* **Value**: ``{"tiles": {"bq": …, "bp": …, "qb": …}, "us": best_time,
  "static_us": static_time, "v": 2}``.
* **Search**: a small per-backend candidate grid (always containing the
  static-heuristic tile), each candidate timed via a compiled micro-run
  (warm-up call to compile, then best-of-N).  The winner is then
  *paired-timed* against the static heuristic and accepted only when it
  beats it by more than a noise margin — a near-tie would otherwise pin
  one noisy measurement into the cache forever, and a regression (a
  "tuned" tile slower than the heuristic at serving time) could ride
  along.  ``revalidate()`` re-measures cached entries whose recorded
  win may have evaporated (new kernel code, different machine load).
* **Persistence**: repo-shipped defaults (``tuning_defaults.json`` next
  to this file) overlaid by a user cache (``~/.cache/repro-tune.json``
  or ``$REPRO_TUNE_CACHE``), written atomically (temp + rename).
  Entries failing validation — wrong schema version, missing or
  non-integer tiles, alignment violations — are dropped on load and
  retuned under ``force``.

``REPRO_AUTOTUNE`` controls consultation (see ``repro.env``): ``off`` →
static heuristics only; ``on`` (default) → table lookups, heuristic on
miss, never tunes implicitly (steady-state serving pays zero tuning
cost); ``force`` → tune misses now and write the cache.  Interpret mode
never consults the table (``ops`` keeps today's interpret heuristics).
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from .. import env
from . import dispatch

SCHEMA_VERSION = 2   # v2: query×points kernels gained the "qb" sub-block
_DEFAULTS_PATH = Path(__file__).parent / "tuning_defaults.json"

# tuned entries must beat the static heuristic by this fraction on a
# paired best-of-3 to be accepted (and to survive revalidation)
_NOISE_MARGIN = 0.03

_lock = threading.RLock()
_table: dict[str, dict] | None = None

# tile-name sets per kernel (also the validation contract); "qb" is the
# xla lane's query sub-block — "no sub-blocking" is stored as qb == bq
_TILE_NAMES = {
    "pdist": ("bq", "bp", "qb"),
    "range_filter": ("bq", "bp", "qb"),
    "rankeval": ("bg", "bb"),
    "pdist_rankeval": ("bg", "bb"),
}
# tile axes that address the 128-wide lane dimension on TPU/GPU
_LANE_TILES = {"pdist": ("bp",), "range_filter": ("bp",),
               "rankeval": ("bb",), "pdist_rankeval": ("bg", "bb")}


def mode() -> str:
    return env.get("REPRO_AUTOTUNE")


def cache_path() -> Path:
    p = env.get("REPRO_TUNE_CACHE")
    if p:
        return Path(p)
    return Path(os.path.expanduser("~")) / ".cache" / "repro-tune.json"


def bucket(n: int) -> int:
    """Next power of two, floor 8 — the shape-bucketing of table keys."""
    return max(8, 1 << (int(max(n, 1)) - 1).bit_length())


def _key(backend: str, kernel: str, metric: str | None,
         bdims: dict[str, int]) -> str:
    dims = "/".join(f"{k}={v}" for k, v in sorted(bdims.items()))
    return f"{backend}/{kernel}/{metric or '-'}/{dims}"


def _valid_entry(backend: str, kernel: str, ent) -> bool:
    if not isinstance(ent, dict) or ent.get("v") != SCHEMA_VERSION:
        return False
    tiles = ent.get("tiles")
    names = _TILE_NAMES.get(kernel)
    if names is None or not isinstance(tiles, dict):
        return False
    if set(tiles) != set(names):
        return False
    for name, t in tiles.items():
        if not isinstance(t, int) or t <= 0 or t % 8 != 0:
            return False
        if backend in ("tpu", "gpu") and name in _LANE_TILES[kernel] \
                and t % 128 != 0:
            return False
    if not isinstance(ent.get("us"), (int, float)):
        return False
    return True


def _load_file(path: Path) -> dict[str, dict]:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if not isinstance(data, dict):
        return {}
    entries = data.get("entries")
    return entries if isinstance(entries, dict) else {}


def _entries() -> dict[str, dict]:
    """Validated merged table (shipped defaults overlaid by user cache)."""
    global _table
    with _lock:
        if _table is None:
            merged: dict[str, dict] = {}
            for path in (_DEFAULTS_PATH, cache_path()):
                for key, ent in _load_file(path).items():
                    parts = key.split("/")
                    if len(parts) < 3:
                        continue
                    if _valid_entry(parts[0], parts[1], ent):
                        merged[key] = ent
            _table = merged
        return _table


def _reset() -> None:
    """Drop the in-memory table (tests re-point REPRO_TUNE_CACHE)."""
    global _table
    with _lock:
        _table = None


def tiles_for(kernel: str, metric: str | None,
              dims: dict[str, int]) -> dict[str, int] | None:
    """Tuned tiles for this call, or None → caller's static heuristics.

    Looks up the (backend, kernel, metric, shape-bucket) entry; under
    ``REPRO_AUTOTUNE=force`` a miss (or an entry invalidated on load)
    is tuned on the spot and cached.
    """
    m = mode()
    if m == "off":
        return None
    backend = dispatch.backend_key()
    bdims = {k: bucket(v) for k, v in dims.items()}
    ent = _entries().get(_key(backend, kernel, metric, bdims))
    if ent is not None:
        return dict(ent["tiles"])
    if m == "force":
        return dict(tune(kernel, metric, dims)["tiles"])
    return None


# ---------------------------------------------------------------- tuning

def _round8(t: int) -> int:
    return max(8, (int(t) + 7) // 8 * 8)


def _candidates(backend: str, kernel: str, metric: str | None,
                bd: dict[str, int]) -> list[dict[str, int]]:
    """Per-backend candidate tile grid; always includes the static
    heuristic so "tuned" can only tie or beat it on the measurements."""
    if kernel in ("pdist", "range_filter"):
        nq, npts, d = bd["q"], bd["p"], bd["d"]
        if backend == "xla-cpu":
            if metric in (None, "sql2"):
                bqs = {128, nq}
                bps = {128, 1024, 8192, npts}
                qbs = {16, 32, 0}        # 0 -> qb = bq (no sub-blocking)
            else:  # broadcast (bq, bp, d) intermediate — bound it
                bqs = {32, 128}
                bps = {128, 512, 2048}
                qbs = {8, 0}
        else:  # pallas lanes: bp rides the 128-lane axis; the grid is
            # point-major so qb sub-blocking adds nothing — pin qb = bq
            bqs = {128, 256}
            bps = {128, 256, 512, 1024}
            qbs = {0}
        cands = []
        for bq in bqs:
            for bp in bps:
                bqf = min(_round8(bq), nq)
                bpf = min(_round8(bp), npts)
                for qb in qbs:
                    # bucket dims are powers of two (floor 8), so a
                    # clamped qb always divides bq
                    qbf = bqf if qb == 0 else min(_round8(qb), bqf)
                    cands.append({"bq": bqf, "bp": bpf, "qb": qbf})
        if metric in ("l1", "linf"):
            cands = [c for c in cands
                     if c["qb"] * c["bp"] * d * 4 <= 512 * 2 ** 20]
    elif kernel in ("rankeval", "pdist_rankeval"):
        g, b = bd["g"], bd["b"]
        if backend == "xla-cpu":
            bgs = {8, 64, g}
            bbs = {128, 2048, b}
        else:
            bgs = {8, 16, 32}
            bbs = {128, 256, 512}
        cands = [{"bg": min(_round8(bg), g), "bb": min(_round8(bb), b)}
                 for bg in bgs for bb in bbs]
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    # lane-align + dedup, preserving a stable order
    if backend in ("tpu", "gpu"):
        for c in cands:
            for name in _LANE_TILES[kernel]:
                c[name] = max(128, (c[name] + 127) // 128 * 128)
    seen, out = set(), []
    for c in sorted(cands, key=lambda c: sorted(c.items())):
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


def _bench_thunk(kernel: str, metric: str | None, bd: dict[str, int],
                 tiles: dict[str, int]):
    """A zero-arg callable running one compiled kernel call at the
    bucket shape with explicit tiles (explicit tiles bypass the table,
    so tuning never recurses into a lookup)."""
    from . import ops  # deferred: ops imports this module
    rng = np.random.default_rng(0)
    if kernel in ("pdist", "range_filter"):
        q = rng.standard_normal((bd["q"], bd["d"])).astype(np.float32)
        p = rng.standard_normal((bd["p"], bd["d"])).astype(np.float32)
        if kernel == "pdist":
            return lambda: ops.pdist(q, p, metric or "sql2",
                                     bq=tiles["bq"], bp=tiles["bp"],
                                     qb=tiles.get("qb"))
        r = np.full((bd["q"],), 1.0, np.float32)
        return lambda: ops.range_filter(q, p, r, bq=tiles["bq"],
                                        bp=tiles["bp"],
                                        qb=tiles.get("qb"))
    if kernel == "rankeval":
        x = rng.standard_normal((bd["g"], bd["b"])).astype(np.float32)
        coef = rng.standard_normal((bd["g"], bd["c"])).astype(np.float32)
        lo = np.zeros((bd["g"],), np.float32)
        hi = np.ones((bd["g"],), np.float32)
        n = np.full((bd["g"],), 1000.0, np.float32)
        return lambda: ops.rankeval(x, coef, lo, hi, n, bg=tiles["bg"],
                                    bb=tiles["bb"])
    if kernel == "pdist_rankeval":
        q = rng.standard_normal((bd["b"], bd["d"])).astype(np.float32)
        piv = rng.standard_normal((bd["g"], bd["d"])).astype(np.float32)
        coef = rng.standard_normal((bd["g"], bd["c"])).astype(np.float32)
        lo = np.zeros((bd["g"],), np.float32)
        hi = np.ones((bd["g"],), np.float32)
        n = np.full((bd["g"],), 1000.0, np.float32)
        rg = np.full((bd["b"],), 0.5, np.float32)
        return lambda: ops.pdist_rankeval(q, piv, coef, lo, hi, n, rg,
                                          bg=tiles["bg"], bb=tiles["bb"])
    raise ValueError(f"unknown kernel {kernel!r}")


def _time_us(thunk, reps: int = 3) -> float:
    import jax
    jax.block_until_ready(thunk())        # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _paired_us(thunk_a, thunk_b, reps: int = 3) -> tuple[float, float]:
    """Interleaved best-of-``reps`` timing of two compiled thunks
    (A/B/A/B/…) so machine-load drift hits both measurements equally —
    the comparison the acceptance margin is applied to."""
    import jax
    jax.block_until_ready(thunk_a())      # compile + warm both first
    jax.block_until_ready(thunk_b())
    best_a = best_b = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk_a())
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(thunk_b())
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a * 1e6, best_b * 1e6


def _static_tiles(kernel: str, metric: str | None,
                  bd: dict[str, int]) -> dict[str, int]:
    from . import ops  # deferred: ops imports this module
    return ops.static_tiles(kernel, metric, bd)


def tune(kernel: str, metric: str | None, dims: dict[str, int],
         verbose: bool = False) -> dict:
    """Search the candidate grid for this shape bucket, persist and
    return the winning entry.

    The grid winner is accepted only when a *paired* best-of-3 against
    the static heuristic shows it faster by more than ``_NOISE_MARGIN``
    — otherwise the static tiles are cached (with their measured time),
    so a noisy micro-run can never pin a regression into the table."""
    backend = dispatch.backend_key()
    bd = {k: bucket(v) for k, v in dims.items()}
    key = _key(backend, kernel, metric, bd)
    static = _static_tiles(kernel, metric, bd)
    best_tiles, best_us = None, float("inf")
    for tiles in _candidates(backend, kernel, metric, bd):
        us = _time_us(_bench_thunk(kernel, metric, bd, tiles))
        if verbose:
            print(f"  {key} {tiles} -> {us:.0f}us")
        if us < best_us:
            best_tiles, best_us = tiles, us
    static_us = best_us
    if best_tiles != static:
        best_us, static_us = _paired_us(
            _bench_thunk(kernel, metric, bd, best_tiles),
            _bench_thunk(kernel, metric, bd, static))
        if best_us >= static_us * (1.0 - _NOISE_MARGIN):
            best_tiles, best_us = dict(static), static_us
            if verbose:
                print(f"  {key} grid winner within noise of static "
                      f"-> keeping static {static}")
    ent = {"tiles": best_tiles, "us": round(best_us, 1),
           "static_us": round(static_us, 1), "v": SCHEMA_VERSION}
    with _lock:
        _entries()[key] = ent
        _write_user_cache(key, ent)
    return ent


def _parse_key(key: str):
    """(backend, kernel, metric, bucket-dims) from a table key, or None
    when malformed."""
    parts = key.split("/")
    if len(parts) < 4:
        return None
    backend, kernel, metric = parts[0], parts[1], parts[2]
    try:
        dims = {k: int(v) for k, v in (s.split("=") for s in parts[3:])}
    except ValueError:
        return None
    return backend, kernel, None if metric == "-" else metric, dims


def revalidate(verbose: bool = False) -> dict:
    """Re-measure every cached entry for the current backend.

    Entries whose tiles no longer beat the static heuristic by the
    noise margin (stale after kernel changes or a machine move) are
    re-tuned from scratch; still-winning entries get their timings
    refreshed.  Returns {key: entry} for every entry touched."""
    backend = dispatch.backend_key()
    out = {}
    for key, ent in sorted(_entries().items()):
        parsed = _parse_key(key)
        if parsed is None or parsed[0] != backend:
            continue
        _, kernel, metric, bd = parsed
        static = _static_tiles(kernel, metric, bd)
        if ent["tiles"] == static:
            continue                      # static entries can't go stale
        tuned_us, static_us = _paired_us(
            _bench_thunk(kernel, metric, bd, ent["tiles"]),
            _bench_thunk(kernel, metric, bd, static))
        if tuned_us >= static_us * (1.0 - _NOISE_MARGIN):
            if verbose:
                print(f"  {key}: stale ({tuned_us:.0f}us vs static "
                      f"{static_us:.0f}us) -> retuning")
            out[key] = tune(kernel, metric, bd, verbose=verbose)
        else:
            new = {"tiles": dict(ent["tiles"]), "us": round(tuned_us, 1),
                   "static_us": round(static_us, 1), "v": SCHEMA_VERSION}
            with _lock:
                _entries()[key] = new
                _write_user_cache(key, new)
            out[key] = new
    return out


def _write_user_cache(key: str, ent: dict) -> None:
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    entries = _load_file(path)
    entries[key] = ent
    payload = {"version": SCHEMA_VERSION, "entries": entries}
    fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                               prefix=path.name + ".")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# ------------------------------------------------------------------ warm

# the pipeline's standard shape buckets: (kernel, metric, dims) — a
# 256 × 65k × 32 kernel shape plus the serving refinement bucket
# (batch 64 queries × ~100k padded slots × d=8, the shape the resident
# executor's range/knn filters actually launch at)
_WARM_FULL = (
    ("pdist", "sql2", {"q": 256, "p": 65536, "d": 32}),
    ("range_filter", "sql2", {"q": 256, "p": 65536, "d": 32}),
    ("pdist", "sql2", {"q": 64, "p": 92544, "d": 8}),
    ("range_filter", "sql2", {"q": 64, "p": 92544, "d": 8}),
    ("rankeval", None, {"g": 64, "b": 4096, "c": 16}),
    ("rankeval", None, {"g": 48, "b": 128, "c": 21}),
    ("pdist_rankeval", None, {"g": 64, "b": 256, "d": 32, "c": 16}),
    ("pdist_rankeval", None, {"g": 48, "b": 64, "d": 8, "c": 21}),
)
_WARM_QUICK = (
    ("pdist", "sql2", {"q": 128, "p": 4096, "d": 16}),
    ("range_filter", "sql2", {"q": 128, "p": 4096, "d": 16}),
    ("rankeval", None, {"g": 64, "b": 512, "c": 16}),
    ("pdist_rankeval", None, {"g": 64, "b": 128, "d": 16, "c": 16}),
)


def warm(shapes=None, quick: bool = False, verbose: bool = False) -> dict:
    """Tune (and cache) the standard pipeline shape buckets; returns
    {key: entry}.  Tunes unconditionally — the CLI entry point for CI
    and first-boot cache warming, regardless of REPRO_AUTOTUNE."""
    shapes = shapes if shapes is not None else (
        _WARM_QUICK if quick else _WARM_FULL)
    out = {}
    for kernel, metric, dims in shapes:
        ent = tune(kernel, metric, dims, verbose=verbose)
        bd = {k: bucket(v) for k, v in dims.items()}
        out[_key(dispatch.backend_key(), kernel, metric, bd)] = ent
    return out


def main(argv=None) -> int:  # pragma: no cover - thin CLI
    import argparse
    ap = argparse.ArgumentParser(
        description="Warm the kernel tile tuning cache.")
    ap.add_argument("--warm", action="store_true",
                    help="tune the standard pipeline shape buckets")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes (CI smoke)")
    ap.add_argument("--revalidate", action="store_true",
                    help="re-measure cached entries; retune stale ones "
                         "whose win over the static heuristic is gone")
    args = ap.parse_args(argv)
    if not (args.warm or args.revalidate):
        ap.print_help()
        return 2
    res = warm(quick=args.quick, verbose=True) if args.warm else {}
    if args.revalidate:
        res.update(revalidate(verbose=True))
    print(f"tuned {len(res)} entries -> {cache_path()}")
    for key, ent in res.items():
        print(f"  {key}: {ent['tiles']} ({ent['us']:.0f}us, "
              f"static {ent.get('static_us', ent['us']):.0f}us)")
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
