"""Layer 1 of the serving stack: the immutable device snapshot.

``LIMSSnapshot`` is a pure pytree of padded, cluster-major arrays built
from a host ``LIMSIndex`` — no query logic lives here (that is layer 2,
``repro.core.executor``; the mutable serving frontend is layer 3,
``repro.core.serving``; see DESIGN.md §1 for the stack).

Everything a query needs is laid out per cluster, padded to a common
``n_max`` so the whole corpus is one rectangular block.  ``n_max`` is a
multiple of 128, the TPU's lane width: flattening ``(…, K, n_max)`` to
the ``K·n_max`` candidate axis is then free on the chip.  With a ragged
``n_max`` the chip's compiler lowers that flattening of a
``(B, K, n_max)`` mask as a long unrolled relayout: at 1M rows and
K = 256 (n_max 6844) a planning program of over 60 MB that takes
minutes to compile.

  rows    (K, n_max, d)  f32   ring-ordered store rows, then §5.3 insert-
                               buffer rows, then invalid padding slots
  rids    (K, n_max, m)  i32   ring id per (row, pivot); -1 on non-ring slots
  pivots  (K, m, d)      f32   pivot payloads
  dmin/dmax (K, m)       f32   per-pivot distance extents (TriPrune)
  width   (K,)           i32   ring width ceil(n/N)
  ns      (K,)           i32   stored-row count per cluster
  valid / in_ring / always (K, n_max) bool
                               live slots / ring-structured slots / slots
                               that bypass the ring box (insert buffers)
  coef    (K, m, C)      f32   Chebyshev rank-model tables (one row per
                               (cluster, pivot) group)
  model_lo/hi/n (K, m)   f32   per-group domain + train count
  rank_err (K, m)        f32   certified rank-error bound E (DESIGN.md §3)

The cluster-major (K-leading) layout is what makes cluster-granular
sharding free: a ``ShardedExecutor`` splits every device array on axis 0
and each shard is a self-contained snapshot of K/ndev clusters (pivot
tables stay valid under partition — pruning and rank models are purely
per-cluster, so exactness survives sharding; DESIGN.md §4).

Host-side refinement data (``gids_np``, ``rows_np`` in f64, ``valid_np``)
rides along as aux so the final exact-distance refinement never round-trips
through f32 device memory.

Two-plane row layout (DESIGN.md §13): next to the f32 ``rows`` plane the
snapshot can carry an optional reduced-precision copy ``rows_lp``
(bf16/f16, ``REPRO_ROWS_DTYPE``, default off) used *only* for first-pass
distance filtering.  Its certified companion ``lp_eps`` is the exact
quantization margin max_x ‖x_f32 − x_lp‖ (computed in f64 at build): by
the triangle inequality every low-precision distance satisfies
|d_lp(q, x) − d(q, x)| ≤ lp_eps, so a filter radius widened by lp_eps
admits every true result and a kNN certification radius tightened by
lp_eps never certifies early — the same certified-superset pattern as
the rank-error bound E below, with the exact f32/f64 refinement keeping
final results bit-identical.  With the plane off, ``lp_eps = 0.0`` and
every threshold expression reduces to today's bitwise-identical form.

Exactness with learned models on device: the host corrects model error
with exponential search; fixed-shape device code cannot branch per value,
so the snapshot instead *certifies* a per-(cluster, pivot) rank-error
bound E and widens the predicted ring box by it.  E is computed at build
by running the actual ``rankeval`` kernel over the group's own sorted
column (max observed error at the data points) plus a Chebyshev
derivative bound ``D = Σ k²|c_k|`` times the largest inter-point gap in
normalized t-space (the polynomial cannot wiggle more than that between
samples), plus slack for rint/f32.  The widened box is therefore a
guaranteed superset of the host's exact rid box, and the final f64
refinement removes every extra candidate — results are bit-identical to
``LIMSIndex``.  The full argument is DESIGN.md §3.
"""
from __future__ import annotations

import shutil
import tempfile
import weakref
from dataclasses import dataclass, fields, replace

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..kernels.dispatch import rows_dtype
from ..storage import (DEFAULT_CACHE_PAGES, DEFAULT_PAGE_BYTES, PagedStore,
                       StoreView, load_meta, spill_rows, storage_mode)
from .index import LIMSIndex

_E_SLACK = 2.0      # ranks: rint (±0.5 twice) + f32 eval slop

# device-array fields, in flatten order (pytree children)
_DEVICE_FIELDS = (
    "rows", "rids", "pivots", "dmin", "dmax", "width", "ns",
    "valid", "in_ring", "always",
    "coef", "model_lo", "model_hi", "model_n", "rank_err",
)
# n_max is padded to a multiple of this (the TPU lane width)
_SLOT_ALIGN = 128
# static / host-side fields (pytree aux; the optional low-precision
# plane rides as aux, not a child — its presence must not change the
# pytree structure the sharded executor's cached shard_map builders key
# on, and the sharded/paged paths never read it)
_AUX_FIELDS = ("K", "m", "n_rings", "n_max", "live",
               "gids_np", "rows_np", "valid_np", "store",
               "rows_lp", "lp_eps")
# everything spilled to the store's metadata file (rows go to pages.bin)
_SPILL_FIELDS = tuple(f for f in _DEVICE_FIELDS if f != "rows")


@dataclass(frozen=True)
class LIMSSnapshot:
    """Immutable snapshot of one ``LIMSIndex`` (vector metrics, L2)."""

    # static metadata
    K: int
    m: int
    n_rings: int
    n_max: int
    live: int
    # device arrays (cluster-major; see module docstring for shapes)
    rows: jax.Array
    rids: jax.Array
    pivots: jax.Array
    dmin: jax.Array
    dmax: jax.Array
    width: jax.Array
    ns: jax.Array
    valid: jax.Array
    in_ring: jax.Array
    always: jax.Array
    coef: jax.Array
    model_lo: jax.Array
    model_hi: jax.Array
    model_n: jax.Array
    rank_err: jax.Array
    # host-side refinement data (f64 / int64, flat (K·n_max, …))
    gids_np: np.ndarray
    rows_np: np.ndarray
    valid_np: np.ndarray
    # paged storage tier (DESIGN.md §7): when set, row payloads live on
    # disk — ``rows``/``rows_np`` are empty placeholders and the executor
    # fetches candidate pages through this store view (the shared reader
    # bound to THIS snapshot's generation layout, so a later writeback
    # can never remap an in-flight batch's slots)
    store: StoreView | None = None
    # reduced-precision filter plane (DESIGN.md §13): bf16/f16 copy of
    # ``rows`` plus its certified quantization margin; None/0.0 when
    # disabled (``REPRO_ROWS_DTYPE``, the default)
    rows_lp: jax.Array | None = None
    lp_eps: float = 0.0

    # ------------------------------------------------------------- pytree
    def tree_flatten(self):
        children = tuple(getattr(self, f) for f in _DEVICE_FIELDS)
        aux = tuple(getattr(self, f) for f in _AUX_FIELDS)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(**dict(zip(_AUX_FIELDS, aux)),
                   **dict(zip(_DEVICE_FIELDS, children)))

    @property
    def n_slots(self) -> int:
        """Total padded slot count P = K · n_max (the candidate axis)."""
        return self.K * self.n_max

    @property
    def d(self) -> int:
        return self.rows.shape[-1]

    def filter_rows(self) -> tuple[jax.Array, float]:
        """(row plane, certified margin) for first-pass distance
        filtering: the low-precision plane with its quantization margin
        when present, else the f32 plane with margin 0.0 — callers add
        the margin to filter radii unconditionally (+0.0 is an f32/f64
        identity, so the disabled path stays bitwise identical)."""
        if self.rows_lp is not None:
            return self.rows_lp, self.lp_eps
        return self.rows, 0.0

    # -------------------------------------------------------------- build
    @classmethod
    def build(cls, index: LIMSIndex) -> "LIMSSnapshot":
        assert index.space.metric == "l2", "device path: L2 (MXU kernel)"
        K, m = index.K, index.m
        d = index.space.data.shape[1]
        dead = index.tombstones

        n_slots = [ci.n + len(ci.buf_ids) for ci in index.clusters]
        n_max = -(-max(max(n_slots), 1) // _SLOT_ALIGN) * _SLOT_ALIGN
        rows = np.zeros((K, n_max, d), np.float32)
        rows64 = np.zeros((K, n_max, d), np.float64)
        rids = np.full((K, n_max, m), -1, np.int32)
        pivots = np.zeros((K, m, d), np.float32)
        dmin = np.zeros((K, m), np.float32)
        dmax = np.zeros((K, m), np.float32)
        width = np.ones((K,), np.int32)
        gids = np.full((K, n_max), -1, np.int64)
        valid = np.zeros((K, n_max), bool)
        in_ring = np.zeros((K, n_max), bool)
        for ci in index.clusters:
            k, n, nb = ci.cid, ci.n, len(ci.buf_ids)
            pivots[k] = ci.pivot_rows
            if n:
                rows[k, :n] = ci.store.rows
                rows64[k, :n] = ci.store.rows
                rids[k, :n] = ci.mapping.rids[ci.mapping.order]
                dmin[k] = ci.mapping.dist_min
                dmax[k] = ci.mapping.dist_max
                width[k] = max(1, -(-n // index.n_rings))
                gids[k, :n] = ci.store_ids
                in_ring[k, :n] = True
                valid[k, :n] = ci.live_mask
            if nb:
                buf = np.stack(ci.buf_rows)
                rows[k, n:n + nb] = buf
                rows64[k, n:n + nb] = buf
                gids[k, n:n + nb] = ci.buf_ids
                valid[k, n:n + nb] = [g not in dead for g in ci.buf_ids]
        coef, lo, hi, n_model, err = _certified_rank_table(index)
        rows_dev = jnp.asarray(rows)
        rows_lp, lp_eps = _lp_plane(rows_dev)
        return cls(
            K=K, m=m, n_rings=index.n_rings, n_max=n_max,
            live=int(valid.sum()),
            rows_lp=rows_lp, lp_eps=lp_eps,
            rows=rows_dev,
            rids=jnp.asarray(rids),
            pivots=jnp.asarray(pivots),
            dmin=jnp.asarray(dmin),
            dmax=jnp.asarray(dmax),
            width=jnp.asarray(width),
            ns=jnp.asarray(
                np.array([ci.n for ci in index.clusters], np.int32)),
            valid=jnp.asarray(valid),
            in_ring=jnp.asarray(in_ring),
            always=jnp.asarray(valid & ~in_ring),
            coef=jnp.asarray(coef.reshape(K, m, -1)),
            model_lo=jnp.asarray(lo.reshape(K, m)),
            model_hi=jnp.asarray(hi.reshape(K, m)),
            model_n=jnp.asarray(n_model.reshape(K, m)),
            rank_err=jnp.asarray(err.reshape(K, m), jnp.float32),
            gids_np=gids.reshape(-1),
            rows_np=rows64.reshape(K * n_max, d),
            valid_np=valid.reshape(-1),
        )

    # ------------------------------------------------------- shard padding
    def pad_clusters(self, K_new: int) -> "LIMSSnapshot":
        """Pad with inert clusters so K divides a shard count.

        Padding clusters have ``ns = 0`` (TriPrune never wakes them) and
        all-False validity masks, so they contribute no candidates; the
        host-side arrays get matching -1-id / dead slots so the flat
        candidate axis stays aligned with the device mask.  Pure — returns
        a new snapshot, ``self`` is untouched.
        """
        if K_new == self.K:
            return self
        assert K_new > self.K
        pk = K_new - self.K

        def dev(name, fill):
            a = getattr(self, name)
            widths = [(0, pk)] + [(0, 0)] * (a.ndim - 1)
            return jnp.pad(a, widths, constant_values=fill)

        nm = self.n_max
        lp = self.rows_lp
        if lp is not None:
            # zero padding quantizes exactly, so the margin is unchanged
            lp = jnp.pad(lp, [(0, pk), (0, 0), (0, 0)])
        return replace(
            self, K=K_new, rows_lp=lp,
            rows=dev("rows", 0.0), rids=dev("rids", -1),
            pivots=dev("pivots", 0.0),
            dmin=dev("dmin", 0.0), dmax=dev("dmax", 0.0),
            # width 1 / model_hi 1 keep the (masked-out) padded groups'
            # arithmetic finite — no /0 inside the kernels
            width=dev("width", 1), ns=dev("ns", 0),
            valid=dev("valid", False), in_ring=dev("in_ring", False),
            always=dev("always", False),
            coef=dev("coef", 0.0), model_lo=dev("model_lo", 0.0),
            model_hi=dev("model_hi", 1.0), model_n=dev("model_n", 0.0),
            rank_err=dev("rank_err", 0.0),
            gids_np=np.concatenate(
                [self.gids_np, np.full(pk * nm, -1, np.int64)]),
            rows_np=np.concatenate(
                [self.rows_np, np.zeros((pk * nm, self.d), np.float64)]),
            valid_np=np.concatenate(
                [self.valid_np, np.zeros(pk * nm, bool)]),
        )

    # ------------------------------------------------------ paged storage
    def spill(self, path: str, page_bytes: int = DEFAULT_PAGE_BYTES):
        """Spill to a paged store directory (DESIGN.md §7): rows land in
        cluster-major page extents (mapped-value order), every other
        array in the generation's metadata file, published by one atomic
        manifest swap.  Incremental over an existing store — clusters
        with unchanged row bytes keep their extents.  Returns the new
        manifest; ``self`` is untouched.
        """
        K, n_max, d = self.K, self.n_max, self.d
        assert self.rows_np.shape == (K * n_max, d), \
            "spill needs a resident snapshot (store-backed rows are on disk)"
        meta = {f: np.asarray(getattr(self, f)) for f in _SPILL_FIELDS}
        meta.update(
            gids_np=self.gids_np, valid_np=self.valid_np,
            scalars=np.asarray(
                [self.K, self.m, self.n_rings, self.n_max, self.live],
                np.int64))
        return spill_rows(path, self.rows_np.reshape(K, n_max, d),
                          page_bytes=page_bytes, meta_arrays=meta)

    def with_store(self, store: "PagedStore | StoreView") -> "LIMSSnapshot":
        """Store-backed view of this snapshot: row payloads dropped (the
        executor fetches them from ``store`` page-wise), all query
        metadata kept resident.  A raw ``PagedStore`` is bound through a
        ``StoreView`` freezing its *current* generation's layout — call
        this right after :meth:`spill` so snapshot and layout match.
        Pure — returns a new snapshot."""
        if isinstance(store, PagedStore):
            store = store.view()
        return replace(
            self, rows=jnp.zeros((self.K, 0, self.d), jnp.float32),
            rows_np=np.zeros((0, self.d), np.float64), store=store,
            rows_lp=None, lp_eps=0.0)

    @classmethod
    def load(cls, path: str, store: "bool | PagedStore | None" = None,
             cache_pages: int | None = DEFAULT_CACHE_PAGES):
        """Load a spilled snapshot.

        ``store=None/False``: resident — rows read back from the page
        file; bit-identical round trip with :meth:`spill`.
        ``store=True``: cold-start — metadata loads (fast), rows stay on
        disk behind a fresh ``PagedStore`` with ``cache_pages`` capacity.
        ``store=<PagedStore>``: serve through an existing reader (keeps
        its warm page cache; refreshed to the latest manifest).
        """
        meta, man = load_meta(path)
        K, m, n_rings, n_max, live = (int(v) for v in meta["scalars"])
        d = man.d
        kw = {f: jnp.asarray(meta[f]) for f in _SPILL_FIELDS}
        if isinstance(store, StoreView):
            store = store.base
        # the view's (layout, pages file) pair comes from the SAME
        # manifest read as the metadata above — a writeback (or
        # compaction) landing between the two reads would otherwise pair
        # generation-G arrays with G+1 extents
        if isinstance(store, PagedStore):
            ps = store.refresh().view(man.layout(), man.pages_file)
        elif store:
            ps = PagedStore(path, cache_pages=cache_pages).view(
                man.layout(), man.pages_file)
        else:
            ps = None
        if ps is not None:
            rows = jnp.zeros((K, 0, d), jnp.float32)
            rows_np = np.zeros((0, d), np.float64)
            rows_lp, lp_eps = None, 0.0
        else:
            reader = PagedStore(path, cache_pages=0)
            rows64 = np.stack([reader.read_cluster(k) for k in range(K)])
            rows = jnp.asarray(rows64.astype(np.float32))
            rows_np = rows64.reshape(K * n_max, d)
            rows_lp, lp_eps = _lp_plane(rows)
        return cls(K=K, m=m, n_rings=n_rings, n_max=n_max, live=live,
                   rows=rows, rows_np=rows_np,
                   rows_lp=rows_lp, lp_eps=lp_eps,
                   gids_np=np.asarray(meta["gids_np"], np.int64),
                   valid_np=np.asarray(meta["valid_np"], bool),
                   store=ps, **kw)


def maybe_paged(snap: "LIMSSnapshot", path: str | None = None,
                page_bytes: int = DEFAULT_PAGE_BYTES,
                cache_pages: int | None = DEFAULT_CACHE_PAGES
                ) -> "LIMSSnapshot":
    """Apply the process-wide ``REPRO_STORAGE`` policy to a fresh
    snapshot: under ``paged``, spill it (to ``path``, or a self-cleaning
    temp directory) and return the store-backed view, so the default
    serving surfaces exercise the storage tier suite-wide; otherwise
    return ``snap`` unchanged."""
    if storage_mode() != "paged" or snap.store is not None:
        return snap
    cleanup = path is None
    if path is None:
        path = tempfile.mkdtemp(prefix="lims-paged-")
    snap.spill(path, page_bytes=page_bytes)
    store = PagedStore(path, cache_pages=cache_pages)
    if cleanup:
        weakref.finalize(store, shutil.rmtree, path, ignore_errors=True)
    return snap.with_store(store)


jax.tree_util.register_pytree_node(
    LIMSSnapshot, LIMSSnapshot.tree_flatten, LIMSSnapshot.tree_unflatten)


_LP_DTYPES = {"bf16": jnp.bfloat16, "f16": jnp.float16}


def lp_quant_eps(rows, lp, metric: str = "l2") -> float:
    """Certified quantization margin of a low-precision row plane.

    ``max_x ‖x − x̃‖`` over rows, computed exactly in f64 — by the
    triangle inequality ``|d(q, x̃) − d(q, x)| ≤ ‖x − x̃‖`` for every
    query ``q`` under any norm-induced metric, so widening a filter
    radius by this margin makes the low-precision ball test a certified
    superset of the exact one (the ε analogue of the rank bound E:
    DESIGN.md §13 vs §3)."""
    delta = np.abs(np.asarray(rows).astype(np.float64)
                   - np.asarray(lp).astype(np.float64))
    if delta.size == 0:
        return 0.0
    delta = delta.reshape(-1, delta.shape[-1])
    if metric in ("l2", "sql2"):
        per = np.sqrt(np.sum(delta * delta, axis=-1))
    elif metric == "l1":
        per = np.sum(delta, axis=-1)
    elif metric == "linf":
        per = np.max(delta, axis=-1)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return float(per.max())


def _lp_plane(rows: jax.Array) -> tuple[jax.Array | None, float]:
    """(rows_lp, lp_eps) under the ``REPRO_ROWS_DTYPE`` policy — None /
    0.0 when the plane is off (the default)."""
    dt = rows_dtype()
    if dt is None or rows.size == 0:
        return None, 0.0
    lp = rows.astype(_LP_DTYPES[dt])
    return lp, lp_quant_eps(rows, lp, "l2")


def _certified_rank_table(index: LIMSIndex):
    """(G, C) Chebyshev table for one-launch ``rankeval`` + the certified
    per-group rank-error bound E (module docstring / DESIGN.md §3)."""
    m = index.m
    G = index.K * m
    models = [ci.rank_models[j] for ci in index.clusters for j in range(m)]
    C = max(len(mo.coef) for mo in models)
    coef = np.zeros((G, C), np.float32)
    lo = np.zeros(G, np.float32)
    hi = np.ones(G, np.float32)
    n_model = np.zeros(G, np.float32)
    for g, mo in enumerate(models):
        coef[g, :len(mo.coef)] = mo.coef
        lo[g], hi[g], n_model[g] = mo.lo, mo.hi, mo.n

    # certify E: kernel error at the data points + derivative bound for
    # the gaps between them
    n_col = max(int(ci.n) for ci in index.clusters)
    err = np.zeros(G)
    if n_col > 0:
        xcols = np.zeros((G, n_col), np.float32)
        for gi, (ci, j) in enumerate(
                (ci, j) for ci in index.clusters for j in range(m)):
            n = ci.n
            col = ci.mapping.d_sorted[j]
            xcols[gi, :n] = col
            if n:
                xcols[gi, n:] = col[-1]       # pad with hi (ignored)
        pred = np.asarray(ops.rankeval(
            xcols, coef, lo, hi, n_model, n_rings=index.n_rings)[0])
        for gi, mo in enumerate(models):
            n = mo.n
            if n == 0:
                continue
            err_pt = np.abs(pred[gi, :n] -
                            np.arange(n, dtype=np.float64)).max()
            deriv = float(np.sum(
                np.arange(len(mo.coef)) ** 2 * np.abs(mo.coef)))
            span = mo.hi - mo.lo
            col = index.clusters[gi // m].mapping.d_sorted[gi % m]
            gap = float(np.diff(col).max()) * 2.0 / span \
                if (n > 1 and span > 0) else 0.0
            # ranks live in [0, n-1] and predictions are clipped to the
            # same interval, so n always bounds the error — keeps a
            # degenerate fit from inflating E past "whole cluster"
            err[gi] = min(err_pt + deriv * gap + _E_SLACK, float(n))
    return coef, lo, hi, n_model, err


__all__ = ["LIMSSnapshot", "maybe_paged", "lp_quant_eps"]
