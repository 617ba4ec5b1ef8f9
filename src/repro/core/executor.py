"""Layer 2 of the serving stack: plan *execution* over a snapshot.

The query path is split plan/execute (DESIGN.md §8): ``repro.core.planner``
builds one :class:`~repro.core.planner.CandidatePlan` per query batch —
certified candidate masks, cluster routing and the growing-radius
schedule, derived purely from snapshot metadata — and this module
executes it through one of two backends:

  * ``_ResidentBackend`` — the in-memory kernel pipeline.  Range applies
    the fused L2-ball prefilter to the plan's device mask — by default
    (``REPRO_COMPACT=on``) over the plan's *compacted candidate gather*:
    the union certified candidate rows, gathered once into a
    power-of-two bucket, so filter bytes scale with TriPrune survivors
    instead of padded slots (DESIGN.md §13).  kNN runs the *entire*
    growing-radius schedule inside one compiled ``lax.while_loop`` with
    per-query done flags, so a batch costs O(1) host syncs no matter how
    many rounds it takes (the counter is recorded in ``last_knn`` and
    asserted in tests).  Both filters read the snapshot's *filter plane*
    (``REPRO_ROWS_DTYPE``: optionally bf16/f16 rows whose certified
    quantization margin widens ball tests and tightens certifications),
    and the exact host refinement keeps results bitwise identical either
    way.
  * ``_PagedBackend`` — the storage tier.  The plan's masks become
    IO-batched page runs; because round t+1's radius is known from the
    schedule before round t's refinement finishes, the backend can hand
    the next round's IOPlan to an async prefetcher
    (``REPRO_PREFETCH=async``) that overlaps page IO with kernel
    refinement.

Either backend hands refinement and profiling a batch's candidates as
per-query slot lists (``CandidateSets``).  A mask computed on the device
leaves it bit-packed (``_pack_mask``: an eighth of the bool bytes) and is
decoded on the host without expanding the full slot plane.

``QueryExecutor`` owns the single-device pipeline; ``ShardedExecutor``
runs the same plan math cluster-sharded with ``shard_map`` over a mesh
from ``repro.sharding.logical``: each device holds a contiguous shard of
clusters, TriPrune routes every query per shard, and the kNN loop keeps
its per-round reductions on device — candidate counts via ``psum`` and
the k-th distance via a shard-local ``top_k`` merged with
``all_gather`` over (B, k)-sized blocks, never the full distance
matrix.  Cluster-granular sharding preserves exactness for free — pivot
tables, rank models and the certified error bound are all strictly
per-cluster state (DESIGN.md §4).

With one visible device ``ShardedExecutor`` degrades to the plain
single-device path, so CPU-interpret tests exercise the same class; a
second CI job forces 4 host devices (``--xla_force_host_platform_device_count``)
to run the real ``shard_map`` path.

Exactness contract: both executors return results bit-identical to the
host ``LIMSIndex`` — the plan's masks are a certified *superset* of
candidates (error-widened ring box, inflated f32 guard bands), kNN
rounds only certify once the k-th ball provably fits inside the queried
radius minus the guard band, and the final refinement recomputes true
f64 distances on the host (DESIGN.md §3, §8).
"""
from __future__ import annotations

import functools
import time
from types import SimpleNamespace

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import env
from ..kernels import ops
from ..kernels.dispatch import compact_enabled
from ..obs import registry as _obs
from ..obs.profile import QueryProfile, record_profile
from ..obs.trace import span
from ..sharding.logical import default_rules, serving_mesh, spec_for
from ..storage import (PagePrefetcher, cache_pin_mode, plan_batch,
                       prefetch_mode)
from .metrics import dist_one_to_many
from .planner import (_BALL_ABS, _R_REL, _SEED_REL, CandidatePlan, Planner,
                      plan_arrays)
from .snapshot import _DEVICE_FIELDS, LIMSSnapshot

# padding rows for bucketed store-mode kernel launches: far outside any
# ball, large but finite so f32 arithmetic stays NaN-free
_FAR = np.float32(1e30)


def _bucket_size(n: int, min_rows: int = 128) -> int:
    """Next power-of-two row bucket (≥ ``min_rows``) for ``n`` rows —
    the one bucketing policy both gather paths (paged IO and the
    resident compacted gather) launch kernels at, capping the number of
    executable shapes at log₂(P)."""
    return max(min_rows, 1 << max(n - 1, 1).bit_length())


def _pad_bucket(rows32: np.ndarray, min_rows: int = 128) -> np.ndarray:
    """Pad gathered rows to the next power-of-two bucket (≥ ``min_rows``).

    Store-mode launches run over candidate sets whose size varies per
    batch and per kNN round; without bucketing every distinct row count
    is a fresh jit compile on compiled backends.  Buckets cap the number
    of executable shapes at log₂(P); padding rows sit at distance ~1e30
    so they can never enter any ball, and callers slice kernel outputs
    back to the true count (per-pair math is unaffected by padding)."""
    n = rows32.shape[0]
    bucket = _bucket_size(n, min_rows)
    if bucket <= n:
        return rows32
    pad = np.full((bucket - n, rows32.shape[1]), _FAR, np.float32)
    return np.concatenate([rows32, pad])


# ---------------------------------------------------------------------------
# device-resident kNN rounds: the whole growing-radius schedule is one
# compiled loop — seed, rounds, certification and the exact-fallback all
# trace into a single executable, so a batch syncs to host exactly once
# ---------------------------------------------------------------------------
def _smallest_k(dm, k: int):
    """(B, k) smallest values per row, ascending — exact.

    Inside jit, XLA CPU lowers ``lax.top_k`` through a generic sort path
    roughly 40× slower than its eager dispatch (measured: 1.2s vs 31ms
    on a (64, 92k) f32 operand), which would dominate the compiled kNN
    loop.  For the small k the loop certifies with, k successive masked
    argmin sweeps are exact (ties consume one occurrence per sweep) and
    lower to plain fast reductions; large k (the k≈corpus clamp cases,
    where selection is a minor cost anyway) falls back to ``top_k``."""
    if k > 64:
        return -jax.lax.top_k(-dm, k)[0]
    rows = jnp.arange(dm.shape[0])

    def step(dm, _):
        i = jnp.argmin(dm, axis=1)
        v = dm[rows, i]
        return dm.at[rows, i].set(jnp.inf), v

    _, vs = jax.lax.scan(step, dm, None, length=k)
    return vs.T                                 # (B, k) ascending



def _knn_rounds(qf, d2, kth0, r0, eps, snap, n_rings, k_eff, max_rounds,
                count_sum, kth_select):
    """The entire certified growing-radius schedule as one
    ``lax.while_loop`` — the ONE copy of the loop both the single-device
    and the per-shard caller trace, parameterized only by the two global
    reductions (``count_sum``: (B, P_local) candidate mask → (B) global
    counts; ``kth_select``: (B, P_local) masked sq-distances → (B)
    global k-th smallest).  ``kth0`` is the f32 k-th distance (global —
    the sharded caller merges shard-local top-k first), ``r0`` the
    plan's (B,) f32 pivot-seeded schedule base.

    The start radius skips ahead on the schedule to the first round
    whose radius covers the k-th distance estimate (``r0·2^t ≥ kth``):
    executed radii stay on the deterministic schedule the plan
    advertises, but a well-seeded batch certifies in one round, exactly
    like the pre-refactor k-th-distance seeding.  Certification is the
    same guard-band test as ever — enough candidates AND the k-th ball
    strictly inside the round radius minus the f32 bands — so the
    certified set is a superset of the closed k-th ball at any radius
    the schedule visits, and exactness never depends on the seed.
    Anything the schedule never certifies falls back to the exact full
    scan of (locally) valid slots.

    The mask returned is that set ANDed with the k-th-distance band
    (``_knn_band``, ``ball(r_t)``), so refinement gets the ~k rows that
    can be answers, not the whole certified ball.  ``r_t`` is the
    smallest f32 radius at which the certification inequality above
    holds for ``kth0``, the f32 k-th distance over every valid slot.
    Sound by the argument that makes ``ball(r)`` sound (DESIGN.md §8):
    k valid slots have filter distance ≤ ``kth0``, the inequality at
    ``r_t`` is the guard band that puts their true distances at ≤
    ``r_t``, so the true k-th distance D_k ≤ ``r_t``, and every slot
    whose true distance is ≤ D_k passes ``ball(r_t)``.  The certified
    set, or the fallback's valid set, holds that closed k-th ball too,
    so their intersection does, and the stable f64 sort picks the same
    ids and distances from it.  Under ``shard_map`` each shard bands
    its slots with the global ``kth0``.

    Returns (banded mask, rounds used, (B,) rows and (B,) clusters of
    the certified set before the band) — the counts are global (reduced
    with ``count_sum``), the mask shard-local under ``shard_map``.

    ``eps`` is the certified quantization margin of whatever row plane
    produced ``d2`` (``snap.filter_rows()``; 0.0 for the exact f32
    rows): per-pair filter distances satisfy |d_lp − d| ≤ eps, so the
    ball test widens by +eps (no true candidate can be cut) and the
    k-th-ball certification tightens by −eps (the true k-th distance is
    at most the filtered one plus eps).  At eps = 0.0 both adjustments
    are the f32 identity x ± 0.0 — bit-for-bit the pre-lp loop.
    """
    valid = snap.valid.reshape(-1)
    B = qf.shape[0]
    seed = kth0 * (1.0 + _SEED_REL) + _BALL_ABS
    t0 = jnp.ceil(jnp.log2(jnp.maximum(seed, 1e-30) / r0))
    r_start = r0 * jnp.exp2(jnp.maximum(t0, 0.0))

    def cond(st):
        done, r, rounds, final = st
        return jnp.logical_and(~jnp.all(done), rounds < max_rounds)

    def body(st):
        done, r, rounds, final = st
        cand = plan_arrays(qf, r, snap, n_rings)[0]
        ball = d2 <= ((r * (1.0 + _R_REL) + _BALL_ABS + eps) ** 2)[:, None]
        candb = cand & ball
        cnt = count_sum(candb)
        dm = jnp.where(candb, d2, jnp.inf)
        kth = jnp.sqrt(jnp.maximum(kth_select(dm), 0.0))
        ok = (cnt >= k_eff) & (kth <= r * (1.0 - _R_REL) - _BALL_ABS - eps)
        newly = ok & ~done
        final = jnp.where(newly[:, None], candb, final)
        done = done | newly
        r = jnp.where(done, r, r * 2.0)
        return done, r, rounds + 1, final

    st0 = (jnp.zeros(B, bool), r_start, jnp.int32(0),
           jnp.zeros((B, valid.shape[0]), bool))
    done, _, rounds, final = jax.lax.while_loop(cond, body, st0)
    final = jnp.where(done[:, None], final, valid[None])
    cnt = count_sum(final)
    clusters = count_sum(final.reshape((B,) + snap.valid.shape).any(-1))
    return final & _knn_band(d2, kth0, eps), rounds, cnt, clusters


@jax.jit
def _knn_band(d2, kth0, eps):
    """(B, P) bool: the kNN loop's k-th-distance band (soundness in
    ``_knn_rounds``) — the slots whose filter distance passes the ball
    test at ``r_t``, the smallest f32 radius at which the loop's
    certification inequality ``kth0 <= r_t·(1 − _R_REL) − _BALL_ABS −
    eps`` holds.  ``r_t`` is ``(kth0 + _BALL_ABS + eps) / (1 − _R_REL)``
    rounded up in f32 one ulp at a time until the inequality holds as
    the loop evaluates it (two steps at most, measured over 2·10⁷ values
    of ``kth0`` from 0 to 300; a third is spare).  A row where it still
    fails, or where ``kth0`` is not finite, is left unbanded."""
    def cert(r):
        return kth0 <= r * (1.0 - _R_REL) - _BALL_ABS - eps

    r = (kth0 + _BALL_ABS + eps) * (1.0 / (1.0 - _R_REL))
    for _ in range(3):
        r = jnp.where(cert(r), r, jnp.nextafter(r, jnp.inf))
    ball = d2 <= ((r * (1.0 + _R_REL) + _BALL_ABS + eps) ** 2)[:, None]
    return ball | ~cert(r)[:, None]


@functools.partial(jax.jit,
                   static_argnames=("n_rings", "k_eff", "max_rounds"))
def _knn_loop_single(qf, d2, kth0, r0, eps, *arrays, n_rings, k_eff,
                     max_rounds):
    """Single-device compiled kNN rounds: (banded mask, rounds used,
    certified rows, certified clusters) — see ``_knn_rounds``.

    ``d2``/``kth0`` (the full valid-masked filter-plane distance matrix
    and the f32 k-th distance) arrive precomputed from the *eager*
    kernel path — XLA CPU's eager TopK dispatch is ~40× its jitted
    lowering, and the seed is loop-invariant anyway, so only per-round
    work compiles.  ``eps`` is the plane's certified margin (see
    ``_knn_rounds``; 0.0 on the exact f32 plane)."""
    snap = SimpleNamespace(**dict(zip(_DEVICE_FIELDS, arrays)))
    return _knn_rounds(
        qf, d2, kth0, r0, eps, snap, n_rings, k_eff, max_rounds,
        count_sum=lambda candb: jnp.sum(candb, axis=1),
        kth_select=lambda dm: _smallest_k(dm, k_eff)[:, -1])


@jax.jit
def _knn_round_masks(d2, cand, rf, eps):
    """The fused wide part of one host-rounds round: certified ball
    mask, candidate count, and the masked distance matrix in a single
    launch.

    The eager spelling streams the (B, n_slots) matrix once per op —
    ball, candb, cnt, dm are four separate passes; fused, XLA reads
    ``d2``/``cand`` once and writes ``candb``/``dm`` once.  The k-th
    selection stays *outside*: XLA-CPU's jitted TopK lowering is an
    order of magnitude slower than its eager dispatch (the same cliff
    that routes REPRO_KNN_DRIVER=auto to this driver), so the round
    fuses everything except TopK.  Same jnp graph as the eager version
    (elementwise math, exact bool-sum reduction) so the outputs are
    bit-identical — a bytes-moved optimization, not a math change."""
    ball = d2 <= ((rf * (1.0 + _R_REL) + _BALL_ABS + eps) ** 2)[:, None]
    candb = cand & ball
    cnt = jnp.sum(candb, axis=1)
    dm = jnp.where(candb, d2, jnp.inf)
    return candb, cnt, dm


# ---------------------------------------------------------------------------
# candidate sets to the host: a (B, P) bool mask leaves the device as
# (B, W) uint32 words and becomes per-query slot lists on the host
# ---------------------------------------------------------------------------
_PACK_BITS = 32
_PACK_LANES = 128


def _pack_width(n_slots: int) -> int:
    """Words per query: P padded to a multiple of 32·128 slots, so W is
    a multiple of the TPU's 128 lanes."""
    step = _PACK_BITS * _PACK_LANES
    return -(-n_slots // step) * _PACK_LANES


@jax.jit
def _pack_mask(mask):
    """(B, P) bool → (B, W) uint32, bit i of word j = slot i·W + j.

    Strided so that each of the 32 bit planes is a lane-aligned column
    slice of the mask: the pack is one elementwise pass that reads the
    mask once, with no relayout.  Padded slots read False."""
    B, P = mask.shape
    W = _pack_width(P)
    words = None
    for i in range(_PACK_BITS):
        lo, hi = i * W, min((i + 1) * W, P)
        if hi <= lo:
            break
        plane = mask[:, lo:hi].astype(jnp.uint32)
        if hi - lo < W:
            plane = jnp.pad(plane, ((0, 0), (0, W - (hi - lo))))
        plane = plane << i
        words = plane if words is None else words | plane
    return words


class CandidateSets:
    """A batch's candidate slots as per-query lists (CSR): query ``b``'s
    slots are ``slots[offsets[b]:offsets[b + 1]]``, ascending — the
    order ``np.nonzero(mask[b])`` gives, which refinement's stable
    distance sort and the range answers' id order depend on.  The one
    host form every backend hands refinement and profiling."""

    __slots__ = ("offsets", "slots")

    def __init__(self, offsets: np.ndarray, slots: np.ndarray):
        self.offsets = offsets      # (B + 1,) int64
        self.slots = slots          # (nnz,) int64

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def __getitem__(self, b: int) -> np.ndarray:
        return self.slots[self.offsets[b]:self.offsets[b + 1]]

    @property
    def counts(self) -> np.ndarray:
        """(B,) candidates per query."""
        return np.diff(self.offsets)

    def head(self, n: int) -> "CandidateSets":
        """The first ``n`` queries' lists: a padded batch's real rows."""
        if n == len(self):
            return self
        return CandidateSets(self.offsets[:n + 1],
                             self.slots[:self.offsets[n]])

    def clusters(self, n_max: int) -> np.ndarray:
        """(B,) distinct clusters (``slot // n_max``) per query."""
        B = len(self)
        rows = np.repeat(np.arange(B), self.counts)
        c = self.slots // n_max
        new = np.ones(c.size, bool)
        new[1:] = (c[1:] != c[:-1]) | (rows[1:] != rows[:-1])
        return np.bincount(rows[new], minlength=B)

    def union(self, n_slots: int) -> np.ndarray:
        """(n_slots,) bool: slots that are a candidate of any query."""
        u = np.zeros(n_slots, bool)
        u[self.slots] = True
        return u

    @classmethod
    def from_mask(cls, mask: np.ndarray,
                  cols: np.ndarray | None = None) -> "CandidateSets":
        """From a (B, n) host bool mask; ``cols`` (ascending) names the
        slot of each column when the mask covers a gathered subset.
        Counts ``executor.dense_batches``."""
        B, n = mask.shape
        flat = np.flatnonzero(mask)
        rows, c = np.divmod(flat, max(n, 1))
        slots = c if cols is None else np.asarray(cols, np.int64)[c]
        _obs.count("executor.dense_batches")
        return cls(np.searchsorted(rows, np.arange(B + 1)), slots)

    @classmethod
    def from_packed(cls, words: np.ndarray) -> "CandidateSets":
        """From :func:`_pack_mask` words, expanding only the non-zero
        ones.  Counts ``executor.packed_batches``."""
        B, W = words.shape
        flat = words.reshape(-1)
        nz = np.flatnonzero(flat != 0)
        bits = np.unpackbits(
            flat[nz].astype("<u4", copy=False).view(np.uint8),
            bitorder="little")
        e = np.flatnonzero(bits.view(bool))
        i = e & (_PACK_BITS - 1)
        rows, col = np.divmod(nz[e >> 5], W)
        # found in (query, word, bit) order; slot i·W + j ascends in
        # (bit, word), so a stable sort on (query, bit) orders each list
        key = rows * _PACK_BITS + i
        if B * _PACK_BITS <= 1 << 16:
            key = key.astype(np.uint16)      # numpy's radix sort
        order = np.argsort(key, kind="stable")
        offsets = np.zeros(B + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=B), out=offsets[1:])
        _obs.count("executor.packed_batches")
        return cls(offsets, (i * W + col)[order])


# ---------------------------------------------------------------------------
# execution backends (both consume the same CandidatePlan)
# ---------------------------------------------------------------------------
def _knn_driver(ex) -> str:
    """Which resident kNN driver executes the schedule, resolved per
    call so ``REPRO_KNN_DRIVER`` monkeypatching works on long-lived
    executors.  ``loop`` is the compiled ``lax.while_loop``; ``rounds``
    is the host-driven vectorized-round driver.  ``auto`` (default)
    picks ``rounds`` on single-device XLA-CPU — the while_loop/TopK
    cliff is a property of XLA's CPU lowerings (notably ``top_k``, ~40×
    its eager dispatch; the PR-5 ~433 → ~181 q/s regression), not of
    interpret mode, so the compiled xla lane takes the same exit — and
    ``loop`` everywhere else: real accelerators keep O(1) host syncs,
    and the sharded loop's per-round collectives have no eager
    equivalent."""
    mode = env.get("REPRO_KNN_DRIVER")
    if mode in ("loop", "rounds"):
        return mode
    if jax.default_backend() == "cpu" and getattr(ex, "n_shards", 1) <= 1:
        return "rounds"
    return "loop"


class _ResidentBackend:
    """In-memory execution: kernels over the snapshot's device rows."""

    name = "resident"

    def __init__(self, ex: "QueryExecutor"):
        self.ex = ex
        self.prefetcher = None          # nothing to prefetch in memory

    def release(self, plan: CandidatePlan) -> None:
        """No storage, nothing pinned."""

    def range_hits(self, plan: CandidatePlan) -> CandidateSets:
        """Plan mask ∧ ball filter; over every slot, the hits leave the
        device bit-packed."""
        ex = self.ex
        rf = jnp.asarray(plan.radii, jnp.float32)
        if compact_enabled() and getattr(ex, "n_shards", 1) <= 1:
            slots = plan.compact_slots()
            if slots is not None:
                return self._range_hits_compact(plan, rf, slots)
        ex.last_compact = None
        hits = plan.mask_dev & ex._ball_filter(plan.qf, rf)
        return CandidateSets.from_packed(plan.cost.to_host(_pack_mask(hits)))

    def _range_hits_compact(self, plan: CandidatePlan, rf,
                            slots: np.ndarray) -> CandidateSets:
        """Ball prefilter over the plan's compacted candidate gather
        (DESIGN.md §13): the union candidate rows are gathered from the
        filter plane once into a power-of-two bucket and only the dense
        array streams through ``range_filter`` — filter bytes scale
        with surviving candidates, not padded slots.

        Bit-identical to the full-array path: the gathered rows are the
        very device rows the full filter would stream, per-pair kernel
        math is independent of which rows share a launch, bucket
        padding sits at ~1e30 outside every ball, and slots outside the
        union are non-candidates for the whole batch in both paths
        (pinned by tests)."""
        ex = self.ex
        s = ex.snap
        cand = plan.mask
        hits = np.zeros((plan.B, 0), bool)
        bucket = 0
        if slots.size:
            frows, eps = s.filter_rows()
            sub = frows.reshape(s.n_slots, s.d)[jnp.asarray(slots)]
            bucket = _bucket_size(int(slots.size))
            if bucket > slots.size:
                sub = jnp.pad(sub, ((0, bucket - slots.size), (0, 0)),
                              constant_values=_FAR)
            ball = ops.range_filter(
                plan.qf, sub, rf * (1.0 + _R_REL) + _BALL_ABS + eps)
            ball = np.asarray(plan.cost.to_host(ball), bool)[:, :slots.size]
            hits = cand[:, slots] & ball
        ex.last_compact = {"slots": int(slots.size), "bucket": int(bucket),
                           "n_slots": int(s.n_slots)}
        _obs.count("executor.compact_batches")
        if s.n_slots:
            _obs.observe("executor.compact_frac",
                         slots.size / float(s.n_slots))
        return CandidateSets.from_mask(hits, slots)

    def knn_candidates(self, plan: CandidatePlan):
        """(banded candidate sets, rounds, (certified rows, certified
        clusters) per query): the sets refinement scans, and the
        pruning power of the LIMS-certified sets they were cut from."""
        ex = self.ex
        if _knn_driver(ex) == "rounds":
            return self._knn_host_rounds(plan)
        ex.last_driver = "loop"
        r0 = jnp.asarray(plan.radii, jnp.float32)
        final, rounds, cnt, clusters = ex._knn_device_loop(
            plan.qf, r0, plan.k, plan.max_rounds)
        words, rounds, cnt, clusters = plan.cost.to_host(
            (_pack_mask(final), rounds, cnt, clusters))
        return CandidateSets.from_packed(words), int(rounds), \
            (cnt, clusters)

    def _knn_host_rounds(self, plan: CandidatePlan):
        """The same certified schedule as ``_knn_rounds``, driven from
        the host with eager per-round kernel dispatches: identical seed
        skip-ahead, identical guard-band certification, identical exact
        fallback — only the loop control moves to Python, trading O(1)
        host syncs for XLA-CPU's fast eager lowerings.  The certified
        set is a superset of the closed k-th ball at whatever schedule
        radius certifies, so refinement returns bit-identical results
        whichever driver ran (pinned by tests); the k-th-distance band
        is the loop's own ``_knn_band``, so the sets are identical too."""
        ex = self.ex
        s = ex.snap
        qf = plan.qf
        k_eff = plan.k
        cost = plan.cost
        d2, eps = ex._filter_dists(qf)
        kth0 = jnp.sqrt(jnp.maximum(
            -jax.lax.top_k(-d2, k_eff)[0][:, -1], 0.0))
        r0 = jnp.asarray(plan.radii, jnp.float32)
        seed = kth0 * (1.0 + _SEED_REL) + _BALL_ABS
        t0 = jnp.ceil(jnp.log2(jnp.maximum(seed, 1e-30) / r0))
        r = cost.to_host(r0 * jnp.exp2(jnp.maximum(t0, 0.0)))
        B = plan.B
        done = np.zeros(B, bool)
        final = np.zeros((B, s.n_slots), bool)
        rounds = 0
        for t in range(plan.max_rounds):
            rounds = t + 1
            rf = jnp.asarray(r, jnp.float32)
            cand = ex._candidate_mask(qf, rf)
            # same ±eps adjustments as _knn_rounds: widen the ball so
            # the lp plane can't cut a true candidate, tighten the
            # certification by the margin the filtered k-th may be off
            # (fused wide passes; TopK stays eager — see _knn_round_masks)
            candb, cnt, dm = _knn_round_masks(d2, cand, rf,
                                              jnp.float32(eps))
            kth = jnp.sqrt(jnp.maximum(
                -jax.lax.top_k(-dm, k_eff)[0][:, -1], 0.0))
            ok = cost.to_host((cnt >= k_eff) &
                              (kth <= rf * (1.0 - _R_REL) - _BALL_ABS - eps))
            newly = ok & ~done
            if newly.any():
                final[newly] = cost.to_host(candb)[newly]
                done |= newly
            if done.all():
                break
            r = np.where(done, r, r * 2.0)
        else:
            final[~done] = s.valid_np[None]
        ex.last_driver = "rounds"
        cnt = final.sum(axis=1)
        clusters = final.reshape((B,) + s.valid.shape).any(-1).sum(-1)
        final &= cost.to_host(_knn_band(d2, kth0, jnp.float32(eps)))
        return CandidateSets.from_mask(final), rounds, (cnt, clusters)


class _PagedBackend:
    """Storage-tier execution: the plan's masks drive page IO.

    Round t's certified mask becomes a deduplicated, run-coalesced
    ``IOPlan``; rows are gathered through the snapshot's generation-bound
    ``StoreView`` and refined with the same kernels (power-of-two row
    bucketing keeps compile churn bounded).  With a prefetcher attached
    (``REPRO_PREFETCH=async``), round t+1's IOPlan — known from the
    schedule before round t's refinement starts — is fetched on a
    background thread while the kernels run, so the next round's fetch
    finds its pages already resident (DESIGN.md §8).
    """

    name = "paged"

    def __init__(self, ex: "QueryExecutor", prefetch: str | None = None):
        self.ex = ex
        mode = prefetch_mode() if prefetch is None else str(prefetch).lower()
        self.prefetcher = PagePrefetcher(ex.snap.store) \
            if mode == "async" else None

    # ----------------------------------------------------- schedule pins
    def _pin(self, plan: CandidatePlan, pages: np.ndarray) -> None:
        """Pin one round's planned pages for the plan's lifetime
        (``REPRO_CACHE_PIN=off`` reverts to blind LRU).  The ledger
        lives on the plan so ``release`` can drain it even when the
        executor errors mid-batch."""
        if len(pages) and cache_pin_mode():
            self.ex.snap.store.pin_pages(pages)
            plan._pins.append(pages)

    def release(self, plan: CandidatePlan) -> None:
        """Drop every page hold this plan's execution took (idempotent:
        the ledger drains)."""
        store = self.ex.snap.store
        pins, plan._pins = plan._pins, []
        for pages in pins:
            store.unpin_pages(pages)

    # ------------------------------------------------------------- range
    def range_hits(self, plan: CandidatePlan) -> CandidateSets:
        """Same candidate mask as the resident path, ball prefilter on
        gathered pages.  Per-pair kernel math is independent of which
        other rows share a launch and the gathered f32 rows are the same
        downcast the resident snapshot holds, so the mask is identical
        to the in-memory path (DESIGN.md §7)."""
        ex = self.ex
        store = ex.snap.store
        cand = plan.mask
        io = plan_batch(cand, store.layout)
        # schedule-aware eviction: the batch's planned pages stay pinned
        # until execute_*'s finally releases the plan — a squeezed cache
        # can't evict them between fetch, gather and exact refinement
        self._pin(plan, io.pages)
        store.fetch(io)
        rf = jnp.asarray(plan.radii, jnp.float32)
        hits = np.zeros((plan.B, 0), bool)
        if len(io.slots):
            rows64 = store.gather(io.slots)
            ball = ops.range_filter(
                plan.qf, jnp.asarray(_pad_bucket(rows64.astype(np.float32))),
                rf * (1.0 + _R_REL) + _BALL_ABS)
            ball = np.asarray(plan.cost.to_host(ball),
                              bool)[:, :len(io.slots)]
            hits = cand[:, io.slots] & ball
        store.record_queries(io.pages_per_query, io.cand_per_query)
        ex.last_io = io.summary()
        ex.last_io["pinned_pages"] = sum(len(p) for p in plan._pins)
        return CandidateSets.from_mask(hits, io.slots)

    # --------------------------------------------------------------- kNN
    def knn_candidates(self, plan: CandidatePlan):
        """Growing-radius rounds whose IO is the candidate pages.

        Each round evaluates the plan's schedule mask for the whole
        batch, fetches only pages not yet resident (the scheduler
        dedupes; earlier rounds' pages are cache hits — Alg. 2's
        never-re-read-a-page contract), computes f32 distances on the
        newly gathered rows with the same ``pdist`` kernel, and
        certifies per query with the resident loop's exact guard-band
        test.  The certified set is a superset of the closed k-th ball
        — ``_refine_topk`` therefore returns results bit-identical to
        the in-memory executor (DESIGN.md §7)."""
        ex = self.ex
        ex.last_driver = "paged"
        s = ex.snap
        store = s.store
        pf = self.prefetcher
        qf = plan.qf
        B, k_eff = plan.B, plan.k
        r = plan.radii.copy()
        done = np.zeros(B, bool)
        final = np.zeros((B, s.n_slots), bool)
        pos = np.full(s.n_slots, -1, np.int64)   # slot → gathered column
        d2g = np.empty((B, 0), np.float32)       # sq dists, gathered slots
        pages_seen = [set() for _ in range(B)]   # per-query IO metric
        seen = np.zeros((B, s.n_slots), bool)    # per-query fetched cands
        cand_next = plan.mask                    # round-0 schedule mask
        ticket = None
        rounds = 0
        for t in range(plan.max_rounds):
            rounds = t + 1
            cand = cand_next.copy()
            cand_next = None
            cand[done] = False        # frozen queries stop driving IO
            # per_query=False: the pages_seen sets below are this
            # driver's cross-round page accounting
            io = plan_batch(cand, store.layout, per_query=False)
            if pf is not None:
                pf.note_demand(io.pages, ticket)
                ticket = None
            # pin before the fetch: earlier rounds' pages a later round
            # re-demands (growing radii are supersets) stay resident
            # until execute_knn's finally releases the plan
            self._pin(plan, io.pages)
            store.fetch(io)
            # pages(∪ rounds) = ∪ pages(new slots per round): only map
            # slots not already charged to the query
            newly = cand & ~seen
            seen |= cand
            for b in np.nonzero(newly.any(axis=1))[0]:
                pages_seen[b].update(store.layout.slot_pages(
                    np.nonzero(newly[b])[0]).tolist())
            new = io.slots[pos[io.slots] < 0]
            if len(new):
                rows64 = store.gather(new)
                pos[new] = d2g.shape[1] + np.arange(len(new))
            # the schedule fixes round t+1's radius before round t's
            # refinement runs — evaluate its mask now and hand the page
            # IO of the genuinely new slots (``exclude``: everything
            # this or an earlier round gathered) to the background
            # prefetcher, overlapping the kernel work below
            if pf is not None and t + 1 < plan.max_rounds:
                spec_r = np.where(done, r, r * 2.0)
                cand_next = ex.planner.eval_mask(qf, spec_r, plan.cost)
                spec = cand_next.copy()
                spec[done] = False
                pio = plan_batch(spec, store.layout, per_query=False,
                                 exclude=pos >= 0)
                self._pin(plan, pio.pages)   # speculative pages too
                ticket = pf.submit(pio.pages)
            if len(new):
                d2_new = plan.cost.to_host(ops.pdist(
                    qf, jnp.asarray(_pad_bucket(
                        rows64.astype(np.float32)))))[:, :len(new)]
                d2g = np.concatenate([d2g, d2_new], axis=1)
            r32 = np.asarray(r, np.float32)
            thr = (r32 * np.float32(1.0 + _R_REL) +
                   np.float32(_BALL_ABS)) ** 2    # f32 guard-band ball
            cert = r32 * np.float32(1.0 - _R_REL) - np.float32(_BALL_ABS)
            for b in np.nonzero(~done)[0]:
                sl = np.nonzero(cand[b])[0]
                if len(sl) < k_eff:
                    continue
                db = d2g[b, pos[sl]]
                inball = db <= thr[b]
                if int(inball.sum()) < k_eff:
                    continue
                kth = np.sqrt(np.float32(max(
                    np.partition(db[inball], k_eff - 1)[k_eff - 1], 0.0)))
                # same certification as the resident loop: the k-th ball
                # fits strictly inside the round radius minus the f32
                # guard band
                if kth <= cert[b]:
                    final[b, sl[inball]] = True
                    done[b] = True
            if done.all():
                break
            r = np.where(done, r, r * 2.0)
            if cand_next is None and t + 1 < plan.max_rounds:
                cand_next = ex.planner.eval_mask(qf, r, plan.cost)
        else:
            final[~done] = s.valid_np[None]       # exact fallback: scan
            seen[~done] = s.valid_np[None]
        ppq = [len(p) for p in pages_seen]
        # candidates = rows fetched for the query across every round
        # (the union of its candidate sets), matching the range path's
        # accounting — NOT the smaller certified final set
        cpq = seen.sum(axis=1)
        store.record_queries(ppq, cpq)
        ex.last_io = {"pages": len(set().union(*pages_seen)),
                      "pages_per_query": ppq,
                      "candidates_per_query": [int(c) for c in cpq],
                      "pinned_pages": sum(len(p) for p in plan._pins)}
        if pf is not None:
            ex.last_io["prefetch"] = pf.snapshot()
        return CandidateSets.from_mask(final), rounds, None


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------
class QueryExecutor:
    """Single-device plan execution + exact host refinement.

    A snapshot carrying a paged store (``snap.store``, DESIGN.md §7)
    selects the paged backend: candidate masks are computed from
    resident metadata exactly as in memory, then executed as page-
    granular IO — bit-identical results, the paper's cost model driven
    by the learned positions."""

    def __init__(self, snapshot: LIMSSnapshot, prefetch: str | None = None):
        self.snap = snapshot
        self.planner = Planner(self)
        self.backend = _PagedBackend(self, prefetch) \
            if snapshot.store is not None else _ResidentBackend(self)
        # IO summary of the most recent store-mode batch (None otherwise)
        self.last_io: dict | None = None
        # {slots, bucket, n_slots} of the most recent resident range
        # batch that took the compacted-gather path (None when the full
        # padded array streamed; last-writer-wins like last_io)
        self.last_compact: dict | None = None
        # {backend, rounds, host_syncs, driver} of the most recent kNN
        # batch (last-writer-wins under concurrent batches, like last_io;
        # host_syncs is the batch's own count, from its plan's BatchCost)
        self.last_knn: dict | None = None
        self.last_driver: str | None = None
        # QueryProfile of the most recent batch (None until one runs,
        # or with REPRO_OBS=off; last-writer-wins like last_io/last_knn)
        self.last_profile = None
        # host mirrors of the model/ring fields for the observed
        # rank-error health stat; materialized once on first profiled
        # batch (never on the off path), see _health_arrays
        self._health: SimpleNamespace | None = None

    @property
    def live(self) -> int:
        return self.snap.live

    @property
    def prefetcher(self):
        """The backend's async page prefetcher (None unless paged and
        ``REPRO_PREFETCH=async``)."""
        return self.backend.prefetcher

    # ------------------------------------------------------ device stages
    # (the three methods a sharding strategy overrides)
    def _plan_arrays(self, qf: jax.Array, rf: jax.Array):
        """((B, P) candidate mask, (B, K) routing) — the plan math."""
        return plan_arrays(qf, rf, self.snap, self.snap.n_rings)

    def _candidate_mask(self, qf: jax.Array, rf: jax.Array) -> jax.Array:
        """(B, P) bool — error-widened ring box ∧ TriPrune ∧ validity."""
        return self._plan_arrays(qf, rf)[0]

    def _seed_dists(self, qf: jax.Array) -> jax.Array:
        """(B, K, m) device array of query→pivot L2 distances — the kNN
        plan's seed (pivots are data rows, so the nearest live one bounds
        every query's k-th ball from a known row)."""
        s = self.snap
        K, _, m = s.rids.shape
        d2 = ops.pdist(qf, s.pivots.reshape(K * m, s.d))
        return jnp.sqrt(jnp.maximum(d2, 0.0)).reshape(-1, K, m)

    def _ball_filter(self, qf: jax.Array, rf: jax.Array) -> jax.Array:
        """(B, P) bool — fused L2-ball prefilter over the snapshot's
        *filter plane*: the reduced-precision row copy when
        ``REPRO_ROWS_DTYPE`` enables one (radius widened by its
        certified eps so quantization can never cut a true result), the
        exact f32 rows with eps 0.0 — then bit-for-bit the pre-lp
        filter — otherwise."""
        s = self.snap
        frows, eps = s.filter_rows()
        ball = ops.range_filter(qf, frows.reshape(s.n_slots, s.d),
                                   rf * (1.0 + _R_REL) + _BALL_ABS + eps)
        return ball.astype(bool)

    def _sq_dists(self, qf: jax.Array) -> jax.Array:
        """(B, P) f32 squared distances to every slot, inf where invalid."""
        s = self.snap
        if s.store is not None:
            raise RuntimeError(
                "store-backed executor never scans every slot; the kNN "
                "driver routes through the paged backend")
        d2 = ops.pdist(qf, s.rows.reshape(s.n_slots, s.d))
        return jnp.where(s.valid.reshape(-1)[None], d2, jnp.inf)

    def _filter_dists(self, qf: jax.Array) -> tuple[jax.Array, float]:
        """(B, P) f32 squared distances on the filter plane, plus the
        plane's certified quantization margin eps.

        With the lp plane off this is :meth:`_sq_dists` bit-for-bit
        (eps 0.0).  With it on, per-pair distances satisfy
        |d_lp − d| ≤ eps (metric-norm bound on the row rounding,
        computed at snapshot build), so callers widen ball tests by
        +eps and tighten certifications by −eps; the exact host
        refinement then keeps final results bitwise identical."""
        s = self.snap
        if s.store is not None:
            raise RuntimeError(
                "store-backed executor never scans every slot; the kNN "
                "driver routes through the paged backend")
        frows, eps = s.filter_rows()
        d2 = ops.pdist(qf, frows.reshape(s.n_slots, s.d))
        return jnp.where(s.valid.reshape(-1)[None], d2, jnp.inf), eps

    def _knn_device_loop(self, qf, r0, k_eff: int, max_rounds: int):
        """(banded mask, rounds, certified rows, certified clusters) —
        the kNN schedule as one executable (``_knn_rounds``).

        The loop-invariant pieces (filter-plane distance matrix, seed
        k-th distance) run on the eager kernel path first; only the
        rounds themselves compile.  No extra host syncs — eager results
        stay device-resident and feed the jitted loop directly."""
        d2, eps = self._filter_dists(qf)
        kth0 = jnp.sqrt(jnp.maximum(
            -jax.lax.top_k(-d2, k_eff)[0][:, -1], 0.0))
        return _knn_loop_single(
            qf, d2, kth0, r0, jnp.float32(eps),
            *(getattr(self.snap, f) for f in _DEVICE_FIELDS),
            n_rings=self.snap.n_rings, k_eff=k_eff, max_rounds=max_rounds)

    # -------------------------------------------------------- observability
    def _emit_profile(self, plan: CandidatePlan, final: CandidateSets,
                      rounds: int, stages: dict, t0: float,
                      certified=None) -> None:
        """Build and record one batch's :class:`QueryProfile`.

        Everything derives from state already on the host — the final
        candidate sets the backend returned, ``last_io``, the plan's
        :class:`~repro.core.planner.BatchCost` — so profiling adds
        *zero* device syncs (the planner's O(1)-syncs-per-batch contract
        is pinned by tests and must survive instrumentation).
        Candidates are the LIMS-certified rows per query and clusters
        how many of the K clusters those rows span (TriPrune's pruning
        power): ``certified`` gives their (B,) counts where refinement
        got a tighter set (the kNN band), else ``final`` is that set.
        ``refined_per_query`` counts the rows refinement scanned.  The
        time spent here before the record is filed is its ``profile``
        stage."""
        if not _obs.enabled():
            return
        tp = time.perf_counter()
        with span("obs.profile"):
            s = self.snap
            B = plan.rows
            K, n_max, _ = s.rids.shape
            cost = plan.cost
            stages.update(route=cost.route_s, device_wait=cost.device_wait_s,
                          d2h=cost.d2h_s)
            if certified is None:
                cand, clusters = final.counts, final.clusters(n_max)
            else:
                cand, clusters = (c[:B] for c in certified)
            if self.backend.name == "paged" and self.last_io is not None:
                pages = int(self.last_io["pages"])
                ppq = float(np.mean(self.last_io["pages_per_query"]))
            else:
                pages, ppq = 0, 0.0
            prof = QueryProfile(
                kind=plan.kind, batch=B, k=plan.k,
                backend=self.backend.name,
                driver=self.last_driver if plan.kind == "knn" else None,
                storage="paged" if s.store is not None else "resident",
                n_shards=int(getattr(self, "n_shards", 1)),
                rounds=int(rounds), host_syncs=cost.syncs,
                pages=pages, pages_per_query=ppq,
                candidates_per_query=float(cand.mean()),
                clusters_per_query=float(clusters.mean()),
                refined_per_query=float(final.counts.mean()),
                n_clusters=int(K), stages=stages,
                total_s=time.perf_counter() - t0 + plan.plan_s,
                rank_err_ratio=self._observed_rank_err(final),
                d2h_bytes=cost.d2h_bytes, compiles=cost.compiles)
            stages["profile"] = time.perf_counter() - tp
        self.last_profile = prof
        record_profile(prof)

    # how many certified candidates the rank-health stat replays per
    # batch (host f32 math over cache-hot rows — bounded, not per-row)
    _HEALTH_SAMPLE = 32

    def _health_arrays(self) -> SimpleNamespace:
        """Host mirrors of the model/ring fields, materialized once per
        executor so the per-batch health stat adds no device work."""
        h = self._health
        if h is None:
            s = self.snap
            h = SimpleNamespace(
                rids=np.asarray(s.rids),                     # (K, n_max, m)
                pivots=np.asarray(s.pivots, np.float32),     # (K, m, d)
                coef=np.asarray(s.coef, np.float32),         # (K, m, C)
                lo=np.asarray(s.model_lo, np.float32),       # (K, m)
                hi=np.asarray(s.model_hi, np.float32),
                n=np.asarray(s.model_n, np.float32),
                err=np.asarray(s.rank_err, np.float32),      # (K, m)
                in_ring=np.asarray(s.in_ring).reshape(-1),   # (K*n_max,)
            )
            self._health = h
        return h

    def _observed_rank_err(self, final: CandidateSets) -> float | None:
        """Observed rank-model error over this batch, as a fraction of
        the certified bound E (DESIGN.md §12).

        Samples up to ``_HEALTH_SAMPLE`` in-ring candidate slots from
        the union of the sets refinement scanned (deterministic stride —
        no RNG on the query path), recomputes their pivot distances from
        the rows refinement just gathered (cache-hot), replays the kernel's
        ``rank_math`` arithmetic in host f32 numpy, and compares the
        predicted ring id against the one the build stored.  Ratio 1.0
        means predictions are off by as much as the ring-widening
        budget E assumes; the rank-drift detector watches the
        per-cluster gauges this emits.  Returns the sample-mean ratio,
        or None when the batch certified no in-ring rows.  Buffer rows
        (``in_ring`` False) bypass the model and are skipped."""
        s = self.snap
        K, n_max, m = s.rids.shape
        h = self._health_arrays()
        slots = np.flatnonzero(final.union(h.in_ring.size) & h.in_ring)
        if slots.size == 0:
            return None
        if slots.size > self._HEALTH_SAMPLE:
            step = slots.size // self._HEALTH_SAMPLE
            slots = slots[::step][:self._HEALTH_SAMPLE]
        rows = np.asarray(self._refine_rows(slots), np.float32)  # (S, d)
        kk = slots // n_max
        jj = slots % n_max
        x = np.sqrt(((rows[:, None, :] - h.pivots[kk]) ** 2).sum(-1))
        # replay rank_math (kernels/rankeval.py) in f32: normalize,
        # Clenshaw high→low, rank → ring id
        lo, hi, nn = h.lo[kk], h.hi[kk], h.n[kk]                 # (S, m)
        t = np.clip((x - lo) / np.maximum(hi - lo, np.float32(1e-30))
                    * 2.0 - 1.0, -1.0, 1.0).astype(np.float32)
        coef = h.coef[kk]                                        # (S, m, C)
        b1 = np.zeros_like(t)
        b2 = np.zeros_like(t)
        t2 = 2.0 * t
        for c in range(coef.shape[-1] - 1, 0, -1):
            b1, b2 = coef[..., c] + t2 * b1 - b2, b1
        r = coef[..., 0] + t * b1 - b2
        rank = np.clip(np.rint(r), 0.0, np.maximum(nn - 1.0, 0.0))
        width = np.ceil(nn / np.float32(s.n_rings))
        pred = np.clip(np.floor(rank / np.maximum(width, 1.0)), 0.0,
                       np.float32(s.n_rings - 1))
        act = h.rids[kk, jj]                                     # (S, m)
        ok = act >= 0
        if not ok.any():
            return None
        ratio = np.where(
            ok, np.abs(pred - act) * width / np.maximum(h.err[kk], 1.0),
            0.0)
        for k in np.unique(kk):
            _obs.set_gauge(f"executor.rank_err_ratio.c{int(k)}",
                           float(ratio[kk == k].max()))
        mean = float(ratio.sum() / ok.sum())
        _obs.observe("executor.rank_err_ratio", mean)
        return mean

    # ----------------------------------------------------- refinement data
    def _refine_rows(self, idx: np.ndarray) -> np.ndarray:
        """f64 rows for flat slot ids: resident matrix or page gather
        (cache-hot — the prefilter just fetched these pages)."""
        if self.snap.store is not None:
            return self.snap.store.gather(idx)
        return self.snap.rows_np[idx]

    # ------------------------------------------------------- range queries
    def range_query_batch(self, Q, r):
        """Exact batched L2 range query.

        ``Q``: (B, d) queries; ``r``: scalar or (B,) per-query radii.
        Returns a list of B ``(ids, dists)`` pairs (int64 / float64), the
        same results as ``LIMSIndex.range_query`` per query.
        """
        Q = np.atleast_2d(np.asarray(Q, np.float64))
        B = Q.shape[0]
        r_arr = np.broadcast_to(np.asarray(r, np.float64), (B,))
        plan = self.planner.plan_range(Q, r_arr)
        return self.execute_range(Q, plan)

    def execute_range(self, Q, plan: CandidatePlan):
        """Execute a prebuilt range plan — the router's entry point: a
        replica runs a ``plan.subset`` built by another executor's
        planner without constructing a second plan.  ``Q`` must be the
        (B, d) f64 queries the plan was built for (the plan carries only
        their f32 device copy; exact refinement needs f64)."""
        s = self.snap
        Q = np.atleast_2d(np.asarray(Q, np.float64))
        t0 = time.perf_counter()
        stages = {"plan": plan.plan_s}
        try:
            with plan.cost.charge():
                with span("executor.range_execute",
                          {"B": plan.B, "backend": self.backend.name}):
                    hit = self.backend.range_hits(plan).head(plan.rows)
                t1 = time.perf_counter()
                stages["execute"] = t1 - t0
                out = []
                with span("executor.refine", {"B": plan.rows}):
                    for b in range(Q.shape[0]):
                        idx = hit[b]
                        ids = s.gids_np[idx]
                        d_true = dist_one_to_many(
                            Q[b], self._refine_rows(idx), "l2")
                        keep = d_true <= plan.radii[b]
                        out.append((ids[keep], d_true[keep]))
                stages["refine"] = time.perf_counter() - t1
            self._emit_profile(plan, hit, 1, stages, t0)
        finally:
            self.backend.release(plan)
        return out

    def range_query(self, q, r: float):
        """Single-query convenience wrapper over the batch engine."""
        return self.range_query_batch(np.asarray(q)[None], float(r))[0]

    # --------------------------------------------------------- kNN queries
    def knn_query_batch(self, Q, k: int, max_rounds: int = 64):
        """Exact batched kNN: one plan, one backend execution.

        ``k`` is clamped to the number of live objects. Returns
        ``(ids (B, k'), dists (B, k'))`` with ``k' = min(k, live)``.
        """
        s = self.snap
        Q = np.atleast_2d(np.asarray(Q, np.float64))
        B = Q.shape[0]
        k_eff = min(int(k), s.live)
        if k_eff <= 0:
            return (np.empty((B, 0), np.int64), np.empty((B, 0)))
        plan = self.planner.plan_knn(Q, k_eff, max_rounds)
        return self.execute_knn(Q, plan)

    def execute_knn(self, Q, plan: CandidatePlan):
        """Execute a prebuilt kNN plan (see :meth:`execute_range`).
        Syncs, copies and compiles accumulate on the plan's own
        :class:`~repro.core.planner.BatchCost`, which already holds what
        planning (and routing) charged — wherever the plan was built."""
        Q = np.atleast_2d(np.asarray(Q, np.float64))
        t0 = time.perf_counter()
        stages = {"plan": plan.plan_s}
        try:
            with plan.cost.charge():
                with span("executor.knn_execute",
                          {"B": plan.B, "k": plan.k,
                           "backend": self.backend.name}):
                    final, rounds, certified = \
                        self.backend.knn_candidates(plan)
                final = final.head(plan.rows)
                t1 = time.perf_counter()
                stages["execute"] = t1 - t0
                self.last_knn = {"backend": self.backend.name, "k": plan.k,
                                 "rounds": rounds,
                                 "host_syncs": plan.cost.syncs,
                                 "driver": self.last_driver}
                with span("executor.refine", {"B": plan.rows}):
                    out = self._refine_topk(Q, final, plan.k)
                stages["refine"] = time.perf_counter() - t1
            self._emit_profile(plan, final, rounds, stages, t0, certified)
            return out
        finally:
            self.backend.release(plan)

    def _refine_topk(self, Q, final: CandidateSets, k_eff: int):
        """Exact f64 refinement of the candidate sets: the shared tail
        of both kNN backends.  ``final`` is a superset of the closed
        k-th ball per query (certified, and on the resident backend
        banded), so the stable distance sort (ties in ascending slot
        order) selects the same k results whichever backend produced
        it."""
        s = self.snap
        B = Q.shape[0]
        ids_out = np.empty((B, k_eff), np.int64)
        d_out = np.empty((B, k_eff))
        for b in range(B):
            idx = final[b]
            d_true = dist_one_to_many(Q[b], self._refine_rows(idx), "l2")
            sel = np.argsort(d_true, kind="stable")[:k_eff]
            ids_out[b] = s.gids_np[idx[sel]]
            d_out[b] = d_true[sel]
        return ids_out, d_out

    def knn_query(self, q, k: int):
        """Single-query convenience wrapper over the batch engine."""
        ids, dists = self.knn_query_batch(np.asarray(q)[None], k)
        return ids[0], dists[0]


class ShardedExecutor(QueryExecutor):
    """Cluster-sharded executor: ``shard_map`` over a device mesh.

    The snapshot's K clusters are padded to a multiple of the mesh's
    ``data`` extent and split on the cluster axis; every device traces
    the *same* ``plan_arrays`` body over its shard-local snapshot.
    Queries are replicated (in-spec ``P()``); per-shard plan masks come
    back sharded on the candidate axis (out-spec ``P(None, 'data')``),
    and the compiled kNN loop runs *inside* ``shard_map`` — per-round
    candidate counts merge with ``psum`` and the k-th distance with a
    shard-local ``top_k`` + ``all_gather`` over (B, k) blocks, so
    neither seeding nor rounds ever gather the full distance matrix.

    With one device (plain tier-1 CI) no mesh is built and the class
    behaves exactly like ``QueryExecutor``.
    """

    def __init__(self, snapshot: LIMSSnapshot, mesh: Mesh | None = None,
                 axis: str = "data", prefetch: str | None = None):
        if mesh is None:
            mesh = serving_mesh()
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.shape[axis]) if axis in mesh.axis_names \
            else 1
        if self.n_shards <= 1:
            super().__init__(snapshot, prefetch=prefetch)
            return
        # the unpadded single-device pivot plane (K·m·d floats) for the
        # kNN seed: a Pallas kernel cannot take operands sharded over
        # the mesh outside shard_map, and the 1-device shapes keep the
        # seed bit-identical to the unsharded executor's
        self._pivots = snapshot.pivots
        K_pad = -(-snapshot.K // self.n_shards) * self.n_shards
        snapshot = snapshot.pad_clusters(K_pad)
        # cluster-major arrays shard on axis 0 (logical axis "clusters");
        # place each on its shard now so repeated calls never re-transfer
        rules = default_rules()
        leaves, treedef = jax.tree_util.tree_flatten(snapshot)
        specs = tuple(
            spec_for(("clusters",) + (None,) * (a.ndim - 1),
                     rules, mesh, a.shape) for a in leaves)
        snapshot = jax.tree_util.tree_unflatten(
            treedef, [jax.device_put(a, NamedSharding(mesh, sp))
                      for a, sp in zip(leaves, specs)])
        super().__init__(snapshot, prefetch=prefetch)
        self._dev_arrays = tuple(
            getattr(snapshot, f) for f in _DEVICE_FIELDS)
        self._specs = specs
        self._plan_fn, self._ball_fn = _sharded_pipeline(
            mesh, axis, snapshot.n_rings, specs)

    # sharded device stages (same host drivers as the base class).  In
    # store mode only the plan math runs sharded — the ball prefilter
    # and refinement happen on host-gathered pages, so those stages
    # never dispatch here (the paged backend only asks for plan masks).
    def _plan_arrays(self, qf, rf):
        if self.n_shards <= 1:
            return super()._plan_arrays(qf, rf)
        return self._plan_fn(qf, rf, *self._dev_arrays)

    def _ball_filter(self, qf, rf):
        if self.n_shards <= 1 or self.snap.store is not None:
            return super()._ball_filter(qf, rf)
        return self._ball_fn(qf, rf, *self._dev_arrays)

    def _seed_dists(self, qf):
        if self.n_shards <= 1:
            return super()._seed_dists(qf)
        K, m, d = self._pivots.shape
        dq = jnp.sqrt(jnp.maximum(
            ops.pdist(qf, self._pivots.reshape(K * m, d)), 0.0))
        # padding clusters hold no live slot: the planner masks them
        return jnp.pad(dq.reshape(-1, K, m),
                       ((0, 0), (0, self.snap.K - K), (0, 0)),
                       constant_values=np.inf)

    # NOTE: no _sq_dists override — the full (B, P) distance matrix is
    # only ever needed by the single-device loop's eager seeding; the
    # sharded kNN loop replaced PR-2's all_gather of it with in-loop
    # shard-local top-k merges (the base method's eager jnp still
    # assembles the matrix correctly from the sharded rows if some
    # residual caller asks).

    def _knn_device_loop(self, qf, r0, k_eff: int, max_rounds: int):
        if self.n_shards <= 1:
            return super()._knn_device_loop(qf, r0, k_eff, max_rounds)
        fn = _sharded_knn_loop(self.mesh, self.axis, self.snap.n_rings,
                               self._specs, k_eff, max_rounds)
        return fn(qf, r0, *self._dev_arrays)


def _local_view(arrays) -> SimpleNamespace:
    """Attribute view of the snapshot's device arrays (flatten order =
    ``_DEVICE_FIELDS``): inside ``shard_map`` every leading extent is
    shard-local, and ``plan_arrays`` derives all shapes from the arrays
    themselves."""
    return SimpleNamespace(**dict(zip(_DEVICE_FIELDS, arrays)))


@functools.lru_cache(maxsize=32)
def _sharded_pipeline(mesh: Mesh, axis: str, n_rings: int, specs: tuple):
    """Build the (plan, ball) jitted ``shard_map`` pipeline.

    Cached on (mesh, axis, n_rings, specs) — all hashable — so a
    ``ServingEngine`` refresh that swaps in a same-shaped snapshot reuses
    the previous generation's compiled pipeline instead of retracing on
    the first post-swap batch (``jax.jit`` then keys on array shapes as
    usual; only a snapshot whose padded shapes actually changed pays a
    retrace).
    """
    rep = P()                        # queries/radii: replicated per shard

    def plan_body(qf, rf, *arrays):
        # shard-local TriPrune routing: this device evaluates only its
        # own clusters' ring boxes for every query in the batch
        return plan_arrays(qf, rf, _local_view(arrays), n_rings)

    def ball_body(qf, rf, *arrays):
        snap = _local_view(arrays)
        # the ops wrappers trace with shard-local shapes here, so their
        # tile policy sizes blocks to the per-device slice automatically
        ball = ops.range_filter(
            qf, snap.rows.reshape(-1, snap.rows.shape[-1]),
            rf * (1.0 + _R_REL) + _BALL_ABS)
        return ball.astype(bool)

    out_sharded = P(None, axis)
    return (
        jax.jit(jax.shard_map(plan_body, mesh=mesh,
                              in_specs=(rep, rep) + specs,
                              out_specs=(out_sharded, out_sharded),
                              check_vma=False)),
        jax.jit(jax.shard_map(ball_body, mesh=mesh,
                              in_specs=(rep, rep) + specs,
                              out_specs=out_sharded, check_vma=False)),
    )


@functools.lru_cache(maxsize=64)
def _sharded_knn_loop(mesh: Mesh, axis: str, n_rings: int, specs: tuple,
                      k_eff: int, max_rounds: int):
    """Compiled cluster-sharded kNN rounds: the whole growing-radius
    schedule inside one ``shard_map``.

    Every per-round reduction stays a collective: candidate counts via
    ``psum``, the k-th distance via shard-local ``top_k`` merged with an
    ``all_gather`` of (B, min(k, P_local)·n_shards) blocks — the full
    (B, P) distance matrix is never gathered, for seeding or rounds
    (PR-2's seeding all-gathered it).  ``done``/radii stay replicated
    because every shard computes identical global reductions, so the
    loop needs no host round-trips at all; the certified masks come
    back cluster-sharded and reassemble through the out-spec.
    """
    rep = P()

    def body(qf, r0, *arrays):
        snap = _local_view(arrays)
        valid_l = snap.valid.reshape(-1)
        n_local = valid_l.shape[0]
        kl = min(k_eff, n_local)     # shard-local top-k width
        d2 = ops.pdist(qf, snap.rows.reshape(n_local, -1))
        d2 = jnp.where(valid_l[None], d2, jnp.inf)

        def merged_kth(dm):
            """Global k-th smallest of (B, P_local) per-shard values:
            local top-k, gather the (B, kl) blocks, re-select.  Unlike
            the single-device loop, ``lax.top_k`` is the fast selection
            here — XLA lowers it well on the shard-local operands, and
            the ``_smallest_k`` sweeps measure ~4× slower in this
            position (both were benchmarked; keep whichever wins)."""
            loc = -jax.lax.top_k(-dm, kl)[0]                 # (B, kl)
            allk = jax.lax.all_gather(loc, axis, axis=1,
                                      tiled=True)            # (B, kl·S)
            return -jax.lax.top_k(-allk, k_eff)[0][:, -1]

        kth0 = jnp.sqrt(jnp.maximum(merged_kth(d2), 0.0))
        # the sharded loop always filters on the exact f32 rows — the
        # lp plane is aux state the shard_map pipeline never ships, and
        # cross-shard reductions must agree on one plane — so eps is 0
        return _knn_rounds(
            qf, d2, kth0, r0, jnp.float32(0.0), snap, n_rings, k_eff,
            max_rounds,
            count_sum=lambda candb: jax.lax.psum(
                jnp.sum(candb, axis=1), axis),
            kth_select=merged_kth)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(rep, rep) + specs,
                                 out_specs=(P(None, axis), P(), P(), P()),
                                 check_vma=False))


def make_executor(snapshot: LIMSSnapshot, *, sharded: bool | None = None,
                  mesh: Mesh | None = None,
                  prefetch: str | None = None) -> QueryExecutor:
    """Executor factory: ``sharded=None`` auto-shards when the process
    sees more than one device (or a mesh is given), else stays on the
    plain single-device pipeline.  ``prefetch`` pins the paged backend's
    prefetch mode ("async"/"off"; None → ``REPRO_PREFETCH``)."""
    if sharded is None:
        sharded = mesh is not None or jax.device_count() > 1
    if sharded:
        return ShardedExecutor(snapshot, mesh=mesh, prefetch=prefetch)
    return QueryExecutor(snapshot, prefetch=prefetch)


__all__ = ["QueryExecutor", "ShardedExecutor", "make_executor"]
