"""Builder orchestration + the exact host materialization.

``device_build`` runs the full §4 build pipeline with the heavy stages
on device — batched clustering sweeps, FFT pivot argmax sweeps,
pivot-distance columns through the ``pdist`` Pallas kernel, and every
rank/position model fit in one batched least-squares launch — and
returns a ``DeviceBuildResult``: the structural choices (clustering,
pivot ids), the device-fit models, and per-stage timings.

``LIMSIndex(backend="device")`` consumes the result and materializes
its host structures from it, recomputing exactly (f64, host
``dist_one_to_many``) everything exactness depends on: pivot-distance
columns, ring boundaries, TriPrune extents.  Device-fit models ride
along as-is — they are accelerators the host corrects with exponential
search, and snapshots re-certify their error bound E against the exact
columns (DESIGN.md §6).

``retrain_device`` is the single-cluster variant ``ServingEngine``
routes online retrains through.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from contextlib import nullcontext

import jax
import jax.numpy as jnp

from ..core.clustering import Clustering
from ..core.metrics import MetricSpace
from ..core.rankmodel import PolyRankModel
from .cluster import cluster_major, device_kcenter, device_kmeans
from .fit import batched_chebfit
from .pivots import fft_sweeps, pivot_columns

_PAD_LIMS = np.float32(2 ** 30)     # sorts after every real LIMS value


@dataclass
class DeviceBuildResult:
    """Everything the host materialization needs from the device pass."""
    clustering: Clustering
    pivot_gids: np.ndarray                  # (K, m) global pivot object ids
    rank_models: list                       # K lists of m PolyRankModels
    pos_models: list                        # K PolyRankModels
    device_rank_err: np.ndarray             # (K, m) device-certified E est.
    timings: dict                           # per-stage seconds

    @property
    def K(self) -> int:
        return self.clustering.k


# ------------------------------------------------------------------ fitting
def _ranks_to_lims(cols_raw, mask, counts, n_rings: int):
    """Device ring assignment from the (K, m, n_max) raw column matrix:
    ties-low ranks per (cluster, pivot), equal-count ring ids, LIMS
    values, and the per-cluster sorted LIMS column for position fits."""
    K, m, n_max = cols_raw.shape
    inf = jnp.asarray(jnp.inf, cols_raw.dtype)
    masked = jnp.where(mask[:, None, :], cols_raw, inf)
    order = jnp.argsort(masked, axis=-1)                     # stable
    cols_sorted = jnp.take_along_axis(masked, order, axis=-1)
    idx = jnp.arange(n_max)
    prev = jnp.concatenate(
        [jnp.full((K, m, 1), -jnp.inf, cols_sorted.dtype),
         cols_sorted[:, :, :-1]], axis=-1)
    r_sorted = jax.lax.cummax(
        jnp.where(cols_sorted != prev, idx[None, None, :], 0), axis=2)
    inv = jnp.argsort(order, axis=-1)
    rank_member = jnp.take_along_axis(r_sorted, inv, axis=-1)  # (K, m, n_max)
    width = jnp.maximum(1, -(-jnp.asarray(counts) // n_rings))[:, None, None]
    rid = jnp.clip(rank_member // width, 0, n_rings - 1)
    weights = jnp.asarray(
        [n_rings ** (m - 1 - j) for j in range(m)], jnp.int32)
    lims = jnp.sum(rid.astype(jnp.int32)
                   * weights[None, :, None], axis=1)           # (K, n_max)
    lims_col = jnp.sort(jnp.where(mask, lims.astype(jnp.float32),
                                  _PAD_LIMS), axis=-1)
    return cols_sorted, lims_col


def _fit_all_models(cols_raw, mask, counts, n_rings: int, deg_rank: int,
                    pos_degree: int):
    """ONE batched least-squares launch for the K·m rank models and the
    K position models; returns host ``PolyRankModel`` records plus the
    device-side certified error estimate per rank group."""
    K, m, n_max = cols_raw.shape
    cols_sorted, lims_col = _ranks_to_lims(cols_raw, mask, counts, n_rings)
    counts_j = jnp.asarray(counts, jnp.int32)
    cols_all = jnp.concatenate(
        [cols_sorted.reshape(K * m, n_max), lims_col], axis=0)
    counts_all = jnp.concatenate(
        [jnp.repeat(counts_j, m), counts_j], axis=0)
    deg_req = jnp.concatenate(
        [jnp.full((K * m,), deg_rank, jnp.int32),
         jnp.full((K,), pos_degree, jnp.int32)], axis=0)
    coef, lo, hi, n, dg, err = batched_chebfit(
        cols_all, counts_all, deg_req, max(deg_rank, pos_degree))
    coef = np.asarray(coef, np.float64)
    lo = np.asarray(lo, np.float64)
    hi = np.asarray(hi, np.float64)
    dg = np.asarray(dg, np.int64)
    counts_all = np.asarray(counts_all, np.int64)

    def wrap(g: int) -> PolyRankModel:
        n_g = int(counts_all[g])
        if n_g == 0:
            return PolyRankModel(np.zeros(1), 0.0, 1.0, 0)
        c = coef[g, :int(dg[g]) + 1].copy()
        if not c.any():                      # constant / degenerate column
            c = np.zeros(1)
        return PolyRankModel(c, float(lo[g]), float(hi[g]), n_g)

    rank_models = [[wrap(k * m + j) for j in range(m)] for k in range(K)]
    pos_models = [wrap(K * m + k) for k in range(K)]
    dev_err = np.asarray(err, np.float64)[:K * m].reshape(K, m)
    return rank_models, pos_models, dev_err


# ------------------------------------------------------------- full build
def device_build(space: MetricSpace, n_clusters: int, m: int = 3,
                 n_rings: int = 20, degree: int = 8, pos_degree: int = 8,
                 seed: int = 0, clusterer: str = "kcenter",
                 learned: bool = True,
                 exact_sweeps: bool = True) -> DeviceBuildResult:
    """Run the device build pipeline and return its structural output.

    ``exact_sweeps`` runs the clustering / pivot argmax sweeps in f64
    (scoped ``jax.enable_x64``) for structural bit-parity with the host
    build; f32 sweeps are available for accelerators without fast f64
    and only risk picking different (equally valid) centers/pivots.
    """
    if space._custom is not None or not space.is_vector:
        raise ValueError(
            "device build backend requires a built-in vector metric "
            f"(got {space.metric!r})")
    timings: dict = {}
    t0 = time.perf_counter()
    if clusterer == "kcenter":
        clustering = device_kcenter(space, n_clusters, seed=seed,
                                    exact_sweeps=exact_sweeps)
    elif clusterer == "kmeans":
        clustering = device_kmeans(space, n_clusters, seed=seed)
    else:
        raise ValueError(clusterer)
    timings["cluster_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    member_idx, mask, counts, _ = cluster_major(clustering.members)
    X = space.data
    dtype = np.float64 if exact_sweeps else np.float32
    ctx = jax.enable_x64(True) if exact_sweeps else nullcontext()
    with ctx:
        rows_sw = jnp.asarray(X[member_idx].astype(dtype))
        mask_dev = jnp.asarray(mask)
        gids_dev = jnp.asarray(np.where(mask, member_idx, -1))
        d1_dev = jnp.asarray(
            (clustering.dist_to_center[member_idx] * mask).astype(dtype))
        cent_rows = jnp.asarray(X[clustering.center_idx].astype(dtype))
        cent_gids = jnp.asarray(clustering.center_idx)
        piv_gids = np.asarray(fft_sweeps(
            rows_sw, mask_dev, gids_dev, d1_dev, cent_rows, cent_gids,
            m, space.metric), dtype=np.int64)
    space.dist_count += int(counts.sum()) * (m - 1)
    # (empty clusters need no patching: fft_sweeps latches them onto the
    # centroid gid from round one — the host's centroid-only semantics)
    timings["pivot_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    rows_f32 = jnp.asarray(X[member_idx].astype(np.float32))
    pivot_rows = jnp.asarray(X[piv_gids].astype(np.float32))   # (K, m, d)
    cols_raw = pivot_columns(rows_f32, pivot_rows, space.metric)
    deg_rank = degree if learned else 1
    rank_models, pos_models, dev_err = _fit_all_models(
        cols_raw, jnp.asarray(mask), counts, n_rings, deg_rank, pos_degree)
    timings["fit_s"] = time.perf_counter() - t0
    timings["device_s"] = sum(timings.values())
    return DeviceBuildResult(
        clustering=clustering, pivot_gids=piv_gids,
        rank_models=rank_models, pos_models=pos_models,
        device_rank_err=dev_err, timings=timings)


# ------------------------------------------------------ index / snapshot API
def build_index(space: MetricSpace, n_clusters: int | None = None, **kw):
    """Build a host ``LIMSIndex`` through the device builder
    (``LIMSIndex(backend="device")`` convenience wrapper)."""
    from ..core.index import LIMSIndex
    return LIMSIndex(space, n_clusters=n_clusters, backend="device", **kw)


def build_snapshot(space: MetricSpace, n_clusters: int | None = None, *,
                   spill_path: str | None = None,
                   page_bytes: int | None = None,
                   store: bool = False, **kw):
    """Device-build an index and emit its serving ``LIMSSnapshot``.

    Returns ``(snapshot, index)`` — the snapshot serves through
    ``QueryExecutor``/``ShardedExecutor``; the index remains the §5.3
    update target, exactly as with a host build.

    ``spill_path`` additionally emits the paged disk layout as part of
    the build (DESIGN.md §7): rows land in learned-position page extents
    the moment they exist, so a freshly built corpus is cold-start
    servable without a second pass.  ``store=True`` returns the
    store-backed snapshot view instead of the resident one.
    """
    from ..core.snapshot import LIMSSnapshot
    index = build_index(space, n_clusters=n_clusters, **kw)
    snap = LIMSSnapshot.build(index)
    if spill_path is not None:
        from ..storage import DEFAULT_PAGE_BYTES, PagedStore
        snap.spill(spill_path,
                   page_bytes=page_bytes or DEFAULT_PAGE_BYTES)
        if store:
            snap = snap.with_store(PagedStore(spill_path))
    elif store:
        raise ValueError("store=True requires spill_path")
    return snap, index


# ------------------------------------------------------------------ retrain
def retrain_device(sub: MetricSpace, cent_row: np.ndarray, m: int,
                   n_rings: int, degree: int, pos_degree: int,
                   exact_sweeps: bool = True):
    """Single-cluster device rebuild for ``retrain_cluster`` (§5.3).

    Pivot selection + every model fit run on device (one cluster is one
    row of the padded layout); the pivot-distance matrix handed back is
    recomputed exactly on the host, so the caller's mapping/extents are
    bit-exact.  Returns ``(piv_rows (m, d) f64, pivot_d (n, m) f64,
    rank_models, pos_model)``.
    """
    if sub._custom is not None or not sub.is_vector:
        raise ValueError(
            "device retrain backend requires a built-in vector metric "
            f"(got {sub.metric!r})")
    n = sub.n
    mem = np.arange(n)
    d1 = sub.dist(cent_row, mem)                     # exact f64
    # bucket the padded length so retrains over drifting cluster sizes
    # reuse compiled kernels (same policy as cluster_major)
    n_pad = -(-n // 128) * 128
    dim = sub.data.shape[1]
    rows_np = np.zeros((1, n_pad, dim), np.float64)
    rows_np[0, :n] = sub.data
    mask_np = np.zeros((1, n_pad), bool)
    mask_np[0, :n] = True
    gids_np = np.where(mask_np, np.arange(n_pad)[None], -1)
    d1_np = np.zeros((1, n_pad), np.float64)
    d1_np[0, :n] = d1
    dtype = np.float64 if exact_sweeps else np.float32
    ctx = jax.enable_x64(True) if exact_sweeps else nullcontext()
    with ctx:
        piv_gids = np.asarray(fft_sweeps(
            jnp.asarray(rows_np.astype(dtype)), jnp.asarray(mask_np),
            jnp.asarray(gids_np), jnp.asarray(d1_np.astype(dtype)),
            jnp.asarray(cent_row[None].astype(dtype)),
            jnp.asarray(np.asarray([-1])),           # centroid ∉ members
            m, sub.metric), dtype=np.int64)[0]
    sub.dist_count += n * (m - 1)

    piv_rows = np.empty((m, sub.data.shape[1]), np.float64)
    pivot_d = np.empty((n, m), np.float64)
    piv_rows[0] = cent_row
    pivot_d[:, 0] = d1
    for j in range(1, m):
        g = int(piv_gids[j])
        if g < 0:                                    # latched onto centroid
            piv_rows[j] = cent_row
            pivot_d[:, j] = d1
        else:
            piv_rows[j] = sub.data[g]
            pivot_d[:, j] = sub.dist(sub.data[g], mem)

    rows_f32 = jnp.asarray(rows_np.astype(np.float32))
    prow_f32 = jnp.asarray(piv_rows[None].astype(np.float32))
    cols_raw = pivot_columns(rows_f32, prow_f32, sub.metric)
    rank_models, pos_models, _ = _fit_all_models(
        cols_raw, jnp.asarray(mask_np), np.asarray([n]), n_rings,
        degree, pos_degree)
    return piv_rows, pivot_d, rank_models[0], pos_models[0]


__all__ = ["DeviceBuildResult", "device_build", "build_index",
           "build_snapshot", "retrain_device"]
