"""Layer 2a of the serving stack: query planning (the *plan* half of the
plan/execute query path).

The paper's cost model is positional: rank models certify, per query, an
interval of learned positions, and the query cost is how many of those
positions (pages, on disk) get touched.  Everything about that decision
is a function of the snapshot's *metadata* — pivot distances, Chebyshev
rank tables, the certified per-group rank-error bound E — and never of
the row payloads.  This module makes that boundary explicit:

  * :class:`CandidatePlan` — one query batch's certified plan: per-query
    radii (plus the growing-radius schedule kNN rounds walk), the
    error-widened per-query candidate masks, and per-query cluster
    routing (TriPrune).  Built exactly once per batch.
  * :class:`Planner` — builds plans from a bound executor's device
    pipeline and evaluates schedule rounds on demand.

Both execution backends (the resident kernel pipeline and the paged
store, ``repro.core.executor``) consume the same plan object, so the
candidate math exists in one place and is provably identical however
the batch executes:

  * the plan never reads rows, so a resident snapshot and its spilled
    store-backed twin plan identically (and a store writeback/manifest
    swap cannot change an existing snapshot's plans);
  * masks and routing are evaluated through the executor's device hook,
    so the ``shard_map``-sharded pipeline produces the same bits as the
    single-device one (cluster padding only appends always-False slots);
  * the kNN radius schedule is deterministic doubling from a
    pivot-distance seed: round t's radius is ``radii · 2^t``, which is
    what lets the paged backend construct round t+1's IOPlan *before*
    round t's refinement finishes (``repro.storage.prefetch``) and the
    resident backend run the whole schedule inside one compiled
    ``lax.while_loop`` (DESIGN.md §8).

Guard-band constants live here because they are plan semantics: the
plan's masks must be a certified superset of the host's exact candidate
sets (DESIGN.md §3), and every consumer widens/narrows by the same
bands.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..obs import registry as _obs
from ..obs.trace import span, thread_compiles

# f32 guard bands: rank math and distances run in f64 on the host; the
# device path inflates radii so rounding can never exclude a true result
# (the final f64 refinement removes the extras).
_R_REL = 1e-5       # relative radius inflation for the ring box
_R_ABS = 1e-4       # absolute radius inflation for the ring box
_BALL_ABS = 1e-3    # absolute inflation for the distance-ball prefilter
# seed-radius inflation: pivot/k-th distances are f32, the schedule base
# is f64 — the same margin both pre-refactor kNN drivers applied
_SEED_REL = 1e-3
# compacted-gather payoff bound: when the union candidate set exceeds
# this fraction of the slot array, gathering survivors moves more bytes
# than the full-array filter saves (the power-of-two bucket would cover
# most of the slots anyway) and the plan reports "don't compact"
_COMPACT_MAX_FRAC = 0.5


def plan_arrays(qf, rf, snap, n_rings: int, fused: bool | None = None):
    """The pure device plan math: (B, K·n_max) candidate mask + (B, K)
    cluster routing, written against a (possibly shard-local) snapshot
    pytree so the single-device executor and every ``shard_map`` shard
    run literally the same code.

    Staged path: one ``pdist`` launch gives query→pivot distances
    (TriPrune + AreaLocate inputs); one ``rankeval`` launch evaluates all
    K·m rank models on the lo/hi annulus boundaries of the whole batch,
    laid out (G, 2B).  On the compiled lanes (``fused=None`` defers to
    ``dispatch.fused_plan_enabled``) both collapse into the single
    ``ops.pdist_rankeval`` launch — bit-identical within a lane, pinned
    by tests.  Either way the predicted ring box is widened by the
    certified per-group rank-error bound so it is a guaranteed superset
    of the host's box.
    """
    B = qf.shape[0]
    K, n_max, m = snap.rids.shape
    d = snap.rows.shape[-1]
    N = n_rings
    r_g = rf * (1.0 + _R_REL) + _R_ABS                      # (B,)
    if fused is None:
        fused = ops.fused_plan_enabled()
    G = K * m
    if fused:
        dq, rank_lo, rank_hi = ops.pdist_rankeval(
            qf, snap.pivots.reshape(G, d), snap.coef.reshape(G, -1),
            snap.model_lo.reshape(-1), snap.model_hi.reshape(-1),
            snap.model_n.reshape(-1), r_g, n_rings=N)
    else:
        dq = jnp.sqrt(jnp.maximum(
            ops.pdist(qf, snap.pivots.reshape(G, d)), 0.0))
        # one rankeval launch: G groups × (lo | hi) boundaries of all B
        x = jnp.concatenate([(dq - r_g[:, None]).T,
                             (dq + r_g[:, None]).T], axis=1)  # (G, 2B)
        rank, _ = ops.rankeval(
            x, snap.coef.reshape(G, -1), snap.model_lo.reshape(-1),
            snap.model_hi.reshape(-1), snap.model_n.reshape(-1),
            n_rings=N)
        rank_lo, rank_hi = rank[:, :B], rank[:, B:]
    dqr = dq.reshape(B, K, m)
    # TriPrune, per query per (local) cluster
    alive = jnp.all((dqr <= snap.dmax[None] + r_g[:, None, None]) &
                    (dqr >= snap.dmin[None] - r_g[:, None, None]),
                    axis=-1) & (snap.ns[None] > 0)          # (B, K)
    err = snap.rank_err.reshape(-1)[:, None]                # (G, 1)
    lo_rank = jnp.maximum(rank_lo.astype(jnp.float32) - err, 0.0)
    hi_rank = rank_hi.astype(jnp.float32) + err
    w = snap.width[None, :, None].astype(jnp.float32)
    rid_lo = jnp.clip(jnp.floor(lo_rank.T.reshape(B, K, m) / w),
                      0, N - 1).astype(jnp.int32)
    rid_hi = jnp.clip(jnp.floor(hi_rank.T.reshape(B, K, m) / w),
                      0, N - 1).astype(jnp.int32)
    box = jnp.all((snap.rids[None] >= rid_lo[:, :, None, :]) &
                  (snap.rids[None] <= rid_hi[:, :, None, :]),
                  axis=-1)                                  # (B, K, n_max)
    cand = (box & alive[:, :, None] & snap.in_ring[None]) | \
        snap.always[None]
    cand = cand & snap.valid[None]
    return cand.reshape(B, K * n_max), alive


@dataclass
class BatchCost:
    """What one query batch spent outside its kernels, charged where it
    happens and carried by the batch's :class:`CandidatePlan` — so the
    counts belong to the batch, whichever executor, replica or thread
    does the work (``QueryProfile`` reads them).

    Every device→host copy on the query path goes through
    :meth:`to_host`.  ``route_s`` is the router's assignment time and
    ``compiles`` the backend compiles of the batch's planning, routing
    and execution, each charged through :meth:`charge`.

    A batch the router splits across replicas charges its planning and
    routing once, to its first sub-batch; the others start from a fresh
    record (:meth:`CandidatePlan.subset`), so a sum over a batch's
    profiles counts each copy once."""

    syncs: int = 0               # device→host materializations
    d2h_bytes: int = 0           # bytes those copies moved
    device_wait_s: float = 0.0   # host blocked until the value was ready
    d2h_s: float = 0.0           # the copies themselves
    route_s: float = 0.0
    compiles: int = 0

    @contextlib.contextmanager
    def charge(self, route: bool = False):
        """Charge the backend compiles this thread makes inside the
        block to the batch; with ``route`` also the block's time, as
        ``route_s``."""
        t0 = time.perf_counter()
        c0 = thread_compiles()
        yield self
        self.compiles += thread_compiles() - c0
        if route:
            self.route_s += time.perf_counter() - t0

    def to_host(self, x):
        """Host copy of ``x`` (an array or a pytree of arrays): block
        until it is ready (timed as ``device_wait_s``), copy it (timed
        as ``d2h_s``, its bytes counted), and count one sync.  With
        observability off only the sync is counted."""
        self.syncs += 1
        if not _obs.enabled():
            return jax.device_get(x)
        n = sum(a.nbytes for a in jax.tree_util.tree_leaves(x))
        with span("executor.d2h", {"bytes": n}):
            t0 = time.perf_counter()
            jax.block_until_ready(x)
            t1 = time.perf_counter()
            out = jax.device_get(x)
            t2 = time.perf_counter()
        self.device_wait_s += t1 - t0
        self.d2h_s += t2 - t1
        self.d2h_bytes += n
        return out


@dataclass(eq=False)
class CandidatePlan:
    """One query batch's certified plan, built once and consumed by
    whichever execution backend runs the batch.

    ``radii`` are the round-0 radii (a range query's actual radii; a kNN
    batch's pivot-distance seeds) and ``growth`` the deterministic
    per-round multiplier (1 for range — there is only round 0).  The
    candidate mask and cluster routing are evaluated lazily through the
    owning planner's device pipeline and cached, so a backend that never
    needs the host copy (the resident kNN loop keeps everything on
    device) never pays the transfer — while two backends sharing the
    plan still share one evaluation.
    """

    kind: str                    # "range" | "knn"
    B: int                       # batch size
    k: int | None                # kNN k (clamped to live); None for range
    max_rounds: int              # schedule length
    growth: float                # radius multiplier per round
    radii: np.ndarray            # (B,) f64 round-0 radii
    _planner: "Planner" = field(repr=False, default=None)
    _qf: jax.Array = field(repr=False, default=None)
    _dev: tuple | None = field(repr=False, default=None)
    _mask_np: np.ndarray | None = field(repr=False, default=None)
    _routing_np: np.ndarray | None = field(repr=False, default=None)
    # cached compacted-gather decision: None = not evaluated yet,
    # (slots,) = dense gather indices, (None,) = union too large to pay
    _compact: tuple | None = field(repr=False, default=None)
    # page arrays the paged backend pinned for this plan's execution;
    # drained by the executor's release (finally) — never shared across
    # plans, so a router subset starts with its own empty ledger
    _pins: list = field(repr=False, default_factory=list)
    # wall seconds the planner spent constructing this plan — travels
    # with the plan so whichever executor runs it can charge the plan
    # stage in its QueryProfile (a router subset inherits it: the
    # replica executes a slice of the same single construction)
    plan_s: float = 0.0
    # transfers, routing and compiles charged to this batch so far (a
    # router subset: see BatchCost and subset)
    cost: BatchCost = field(default_factory=BatchCost)
    # trailing rows that copy the batch's first query so a router
    # sub-batch keeps its replica's one device shape (``subset``'s
    # ``pad_to``); the executor drops their results
    pad: int = 0

    @property
    def rows(self) -> int:
        """The batch's real queries: ``B`` less the padding rows."""
        return self.B - self.pad

    @property
    def qf(self) -> jax.Array:
        """(B, d) f32 device queries (shared by every plan consumer)."""
        return self._qf

    def radius_at(self, t: int) -> np.ndarray:
        """(B,) f64 schedule radii for round ``t`` — known for every
        round the moment the plan exists (what prefetch relies on)."""
        return self.radii * (self.growth ** t)

    def _device(self) -> tuple:
        if self._dev is None:
            rf = jnp.asarray(self.radii, jnp.float32)
            self._dev = self._planner.ex._plan_arrays(self._qf, rf)
        return self._dev

    @property
    def mask_dev(self) -> jax.Array:
        """(B, P) bool device candidate mask at round 0."""
        return self._device()[0]

    @property
    def routing_dev(self) -> jax.Array:
        """(B, K) bool device TriPrune cluster routing at round 0."""
        return self._device()[1]

    @property
    def mask(self) -> np.ndarray:
        """Host copy of :attr:`mask_dev` (materialized once)."""
        if self._mask_np is None:
            self._mask_np = self.cost.to_host(self.mask_dev)
        return self._mask_np

    @property
    def routing(self) -> np.ndarray:
        """Host copy of :attr:`routing_dev` (materialized once)."""
        if self._routing_np is None:
            self._routing_np = self.cost.to_host(self.routing_dev)
        return self._routing_np

    def compact_slots(self) -> np.ndarray | None:
        """The plan's compacted row-index gather: sorted flat slot ids
        of the *union* certified candidate set at round-0 radii, or
        None when compaction cannot pay (union > ``_COMPACT_MAX_FRAC``
        of the slots — streaming the full padded array is cheaper than
        gather + dense filter would save).

        This is the memory-roofline half of the plan (DESIGN.md §13):
        the resident backend gathers exactly these rows once into a
        power-of-two bucket (the paged path's compile-churn bucketing)
        and runs the ball prefilter over the dense array, so filter
        bytes scale with TriPrune's surviving candidates instead of
        with the padded slot count.  Certification is untouched — the
        union is read off the already-certified mask, every
        non-listed slot is a non-candidate for every query in the
        batch, and per-pair kernel math is independent of which rows
        share a launch.  Cached with the host mask it derives from.
        """
        if self._compact is None:
            mask = self.mask
            slots = np.nonzero(mask.any(axis=0))[0]
            limit = int(mask.shape[1] * _COMPACT_MAX_FRAC)
            self._compact = (None,) if slots.size > limit else (slots,)
        return self._compact[0]

    def subset(self, idx: np.ndarray, planner: "Planner | None" = None,
               device=None, shared: bool = True,
               pad_to: int | None = None) -> "CandidatePlan":
        """The plan restricted to queries ``idx`` — what the router
        dispatches to a replica (one plan construction per batch still
        holds: a subset is a view, not a rebuild, and does not bump the
        planner's ``built`` counter).

        Per-query plan rows are independent of batchmates (every mask /
        routing / schedule row is a function of that query alone), so
        slicing the batch axis preserves certification exactly.  Host
        copies already materialized slice for free; device arrays are
        NOT carried over — the receiving executor re-evaluates them
        through its own pipeline (same math, its own device), with
        ``device`` placing the sliced queries there first.  ``planner``
        rebinds the subset to the replica executor that will run it.
        The subset's cost record starts from a copy of this plan's when
        ``shared`` (it carries the batch's planning and routing) and
        from a fresh one otherwise.

        ``pad_to`` appends copies of the first query of ``idx`` until
        the subset has that many rows (``pad`` counts them): a copy
        certifies and finishes its kNN schedule exactly when its
        original does, so padding adds no round, and every sub-batch a
        replica receives has the one shape its programs compiled for.
        """
        cost = dataclasses.replace(self.cost) if shared else BatchCost()
        with cost.charge():
            idx = np.asarray(idx, np.int64)
            pad = max(int(pad_to) - len(idx), 0) if pad_to else 0
            if pad:
                idx = np.concatenate([idx, np.full(pad, idx[0])])
            qf = self._qf[jnp.asarray(idx)]
            if device is not None:
                qf = jax.device_put(qf, device)
        return CandidatePlan(
            kind=self.kind, B=len(idx), k=self.k,
            max_rounds=self.max_rounds, growth=self.growth,
            radii=self.radii[idx],
            _planner=planner if planner is not None else self._planner,
            _qf=qf,
            _mask_np=None if self._mask_np is None else self._mask_np[idx],
            _routing_np=None if self._routing_np is None
            else self._routing_np[idx],
            plan_s=self.plan_s, cost=cost, pad=pad)


class Planner:
    """Builds :class:`CandidatePlan`s for one executor.

    ``built`` counts plan constructions — the acceptance criterion is
    exactly one per query batch (tests assert it), with per-round
    schedule evaluations going through :meth:`eval_mask` instead of
    rebuilding anything.
    """

    def __init__(self, executor):
        self.ex = executor
        self.built = 0

    # ------------------------------------------------------------ plans
    def plan_range(self, Q64: np.ndarray, r64: np.ndarray) -> CandidatePlan:
        """Single-round plan at the queries' own radii."""
        self.built += 1
        t0 = time.perf_counter()
        cost = BatchCost()
        with cost.charge(), span("planner.plan_range",
                                 {"B": int(Q64.shape[0])}):
            plan = CandidatePlan(
                kind="range", B=Q64.shape[0], k=None, max_rounds=1,
                growth=1.0, radii=np.array(r64, np.float64),
                _planner=self, _qf=jnp.asarray(Q64, jnp.float32),
                cost=cost)
        plan.plan_s = time.perf_counter() - t0
        _obs.count("planner.plans_built")
        return plan

    def plan_knn(self, Q64: np.ndarray, k_eff: int,
                 max_rounds: int) -> CandidatePlan:
        """Growing-radius plan seeded at the nearest live pivot.

        Pivots are data rows, so the seed ball is non-empty and doubling
        reaches the k-th ball in O(log) rounds; the seed uses only
        resident metadata (pivot payloads + validity masks), so resident
        and store-backed snapshots plan identically.  Clusters with no
        live slots (deleted out, or the inert padding a sharded snapshot
        carries) hold zero/stale pivot rows — mask them so they can't
        collapse the seed below any real point's distance.
        """
        self.built += 1
        t0 = time.perf_counter()
        cost = BatchCost()
        with cost.charge(), span("planner.plan_knn",
                                 {"B": int(Q64.shape[0]), "k": int(k_eff)}):
            s = self.ex.snap
            qf = jnp.asarray(Q64, jnp.float32)
            K, n_max, m = s.rids.shape
            dq = cost.to_host(self.ex._seed_dists(qf))          # (B, K, m)
            live_k = s.valid_np.reshape(K, n_max).any(axis=1)       # (K,)
            dqm = np.where(live_k[None, :, None], dq, np.inf)
            r0 = dqm.reshape(dq.shape[0], K * m).min(axis=1).astype(
                np.float64) * (1.0 + _SEED_REL) + _BALL_ABS
            plan = CandidatePlan(
                kind="knn", B=Q64.shape[0], k=int(k_eff),
                max_rounds=int(max_rounds), growth=2.0, radii=r0,
                _planner=self, _qf=qf, cost=cost)
        plan.plan_s = time.perf_counter() - t0
        _obs.count("planner.plans_built")
        return plan

    # -------------------------------------------------- round evaluation
    def eval_mask(self, qf: jax.Array, radii: np.ndarray,
                  cost: BatchCost) -> np.ndarray:
        """(B, P) host candidate mask at explicit per-query radii — the
        paged backend's per-round schedule evaluation (the resident
        backend evaluates the same math on device, inside its loop);
        the copy is charged to ``cost``."""
        cand, _ = self.ex._plan_arrays(qf, jnp.asarray(radii, jnp.float32))
        _obs.count("planner.round_evals")
        return cost.to_host(cand)


__all__ = ["BatchCost", "CandidatePlan", "Planner", "plan_arrays"]
