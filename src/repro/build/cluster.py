"""Batched device clustering for the LIMS builder.

``device_kcenter`` mirrors the host Gonzalez farthest-first traversal
(``repro.core.clustering.kcenter``) as a single ``lax.scan`` of K-1
argmax sweeps on device; ``device_kmeans`` runs Lloyd iterations with
``core.metrics.cdist`` + segment means.  Both return the same host
``Clustering`` record the numpy path produces.

Structural parity with the host build: the sweeps use the *direct*
(diff) distance formulation — the same math as the host's
``dist_one_to_many`` — and by default run in f64 via the scoped
``jax.enable_x64`` context, so every argmax sees values
within ~1 ulp of the host's and picks the same centers except on exact
ties.  ``exact_sweeps=False`` drops to f32 for accelerators without
fast f64; the resulting index is still exact (any partition is — the
materialization recomputes all bounds exactly, DESIGN.md §6), only
structural bit-parity with the host build is given up.

``dist_to_center`` is always recomputed on the host in f64 after the
sweeps: it becomes pivot column #1 of every cluster, and exactness
requires columns consistent with query-time host distances.
"""
from __future__ import annotations

import functools
from contextlib import nullcontext

import numpy as np

import jax
import jax.numpy as jnp

from ..core.clustering import Clustering
from ..core.metrics import MetricSpace, cdist


def one_to_all(X: jax.Array, row: jax.Array, metric: str) -> jax.Array:
    """(n,) distances row→X in the direct (diff) formulation — the same
    math as the host ``dist_one_to_many``, so f64 sweeps agree with the
    host to ~1 ulp (no Gram-trick cancellation)."""
    if metric == "l2":
        diff = X - row
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    if metric == "l1":
        return jnp.sum(jnp.abs(X - row), axis=-1)
    if metric == "linf":
        return jnp.max(jnp.abs(X - row), axis=-1)
    if metric == "cosine":
        xn = X / jnp.maximum(jnp.linalg.norm(X, axis=-1, keepdims=True),
                             1e-12)
        rn = row / jnp.maximum(jnp.linalg.norm(row), 1e-12)
        return 1.0 - xn @ rn
    raise ValueError(f"device clustering: unsupported metric {metric!r}")


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _kcenter_sweeps(X: jax.Array, first: jax.Array, k: int, metric: str):
    """K-1 farthest-first sweeps as one ``lax.scan``; each step is one
    argmax + one one-vs-all distance pass (O(nd)), all on device."""
    n = X.shape[0]
    d0 = one_to_all(X, X[first], metric)
    centers0 = jnp.zeros((k,), jnp.int32).at[0].set(first.astype(jnp.int32))

    def step(carry, c):
        d_near, assign, centers = carry
        nxt = jnp.argmax(d_near).astype(jnp.int32)
        d_new = one_to_all(X, X[nxt], metric)
        closer = d_new < d_near
        assign = jnp.where(closer, c, assign)
        d_near = jnp.where(closer, d_new, d_near)
        centers = centers.at[c].set(nxt)
        return (d_near, assign, centers), None

    init = (d0, jnp.zeros(n, jnp.int32), centers0)
    (d_near, assign, centers), _ = jax.lax.scan(
        step, init, jnp.arange(1, k, dtype=jnp.int32))
    return centers, assign, d_near


def _exact_dist_to_center(space: MetricSpace, center_idx: np.ndarray,
                          members: list) -> np.ndarray:
    """Host-exact f64 distance to the own centroid, per object.  This is
    pivot column #1 downstream — it must be bit-consistent with the
    query-time ``dist_one_to_many`` (DESIGN.md §6)."""
    d_own = np.zeros(space.n, dtype=np.float64)
    for c, mem in enumerate(members):
        if len(mem):
            d_own[mem] = space.dist(space.data[int(center_idx[c])], mem)
    return d_own


def device_kcenter(space: MetricSpace, k: int, seed: int = 0,
                   exact_sweeps: bool = True) -> Clustering:
    """Device mirror of ``clustering.kcenter`` (same seed → same first
    center; f64 sweeps → same argmax picks up to ~1-ulp ties)."""
    n = space.n
    k = min(k, n)
    rng = np.random.default_rng(seed)
    first = int(rng.integers(n))
    dtype = np.float64 if exact_sweeps else np.float32
    ctx = jax.enable_x64(True) if exact_sweeps else nullcontext()
    with ctx:
        X = jnp.asarray(space.data.astype(dtype))
        centers, assign, _ = _kcenter_sweeps(
            X, jnp.asarray(first), k, space.metric)
        centers = np.asarray(centers, dtype=np.int64)
        assign = np.asarray(assign, dtype=np.int64)
    space.dist_count += n * k        # the sweeps' distance passes
    members = [np.where(assign == c)[0] for c in range(k)]
    d_own = _exact_dist_to_center(space, centers, members)
    return Clustering(centers, assign, d_own, members)


@functools.partial(jax.jit, static_argnames=("k", "iters", "metric"))
def _kmeans_sweeps(X: jax.Array, cent0: jax.Array, k: int, iters: int,
                   metric: str):
    m = "l2" if metric == "cosine" else metric       # host `_cd` parity

    def body(_, cent):
        d = cdist(X, cent, m)
        assign = jnp.argmin(d, axis=1)
        sums = jnp.zeros_like(cent).at[assign].add(X.astype(cent.dtype))
        cnt = jnp.zeros((k,), cent.dtype).at[assign].add(1.0)
        return jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt, 1.0)[:, None],
                         cent)

    cent = jax.lax.fori_loop(0, iters, body, cent0)
    d = cdist(X, cent, m)
    assign = jnp.argmin(d, axis=1)
    # snap centers to the nearest member (empty cluster → global argmin)
    d_member = jnp.where(assign[:, None] == jnp.arange(k)[None], d, jnp.inf)
    has = jnp.any(assign[:, None] == jnp.arange(k)[None], axis=0)
    center_idx = jnp.where(has, jnp.argmin(d_member, axis=0),
                           jnp.argmin(d, axis=0))
    return center_idx, assign


def device_kmeans(space: MetricSpace, k: int, iters: int = 15,
                  seed: int = 0) -> Clustering:
    """Lloyd's kMeans on device (vector metrics): ``cdist`` assignment +
    segment-sum means, centers snapped to real objects at the end.  The
    final assignment is recomputed against the snapped centers so it is
    consistent with the returned ``center_idx``."""
    if not space.is_vector:
        raise ValueError("kmeans requires a vector metric")
    n = space.n
    k = min(k, n)
    rng = np.random.default_rng(seed)
    cent0 = space.data[rng.choice(n, size=k, replace=False)]
    X = jnp.asarray(space.data, jnp.float32)
    center_idx, _ = _kmeans_sweeps(
        X, jnp.asarray(cent0, jnp.float32), k, iters, space.metric)
    center_idx = np.asarray(center_idx, dtype=np.int64)
    space.dist_count += n * k * (iters + 1)
    # final assignment against the *snapped* centers, on the host in f64
    # (cluster membership must agree with the exact dist_to_center below)
    d = np.stack([space.dist(space.data[int(c)]) for c in center_idx], axis=1)
    assign = np.argmin(d, axis=1).astype(np.int64)
    members = [np.where(assign == c)[0] for c in range(k)]
    d_own = _exact_dist_to_center(space, center_idx, members)
    return Clustering(center_idx, assign, d_own, members)


def cluster_major(members: list, pad_mult: int = 128):
    """Pack per-cluster member index lists into the padded cluster-major
    layout every builder stage runs over.

    Returns ``(member_idx (K, n_max) int64, mask (K, n_max) bool,
    counts (K,) int64, n_max)``; padding slots hold index 0 and a False
    mask.  Member order inside a cluster is the host order (ascending
    global id, from ``np.where``) so device argmaxes tie-break exactly
    like the host's.  ``n_max`` rounds up to a multiple of ``pad_mult``
    so repeated builds/retrains over drifting cluster sizes bucket onto
    the same shapes and reuse their compiled kernels.
    """
    K = len(members)
    counts = np.asarray([len(mm) for mm in members], dtype=np.int64)
    n_max = max(int(counts.max()) if K else 1, 1)
    n_max = -(-n_max // max(pad_mult, 1)) * max(pad_mult, 1)
    member_idx = np.zeros((K, n_max), dtype=np.int64)
    mask = np.zeros((K, n_max), dtype=bool)
    for c, mm in enumerate(members):
        member_idx[c, :len(mm)] = mm
        mask[c, :len(mm)] = True
    return member_idx, mask, counts, n_max


__all__ = ["device_kcenter", "device_kmeans", "cluster_major", "one_to_all"]
