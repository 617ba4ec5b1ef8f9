"""Plan-driven routing: one CandidatePlan, dispatched by cluster
ownership.

The planner already computes, per batch, everything a router needs: the
TriPrune cluster routing (which clusters each query can possibly touch)
and the full radius schedule.  ``PlanRouter`` builds that plan exactly
*once* — on its routing executor, preserving the one-plan-per-batch
acceptance property — then splits the batch into per-replica sub-batches
and executes each through ``plan.subset`` on its replica.

Assignment: each query's routed clusters vote for the replicas that
own them.

* Several resident replicas: every replica takes at most ``cap =
  ceil(max_batch / R)`` queries of a batch, rounded up to a multiple of
  8 (:func:`capacity_assign`): a query goes to its most-voted replica
  while that one has room, else to its next preference; ties, and
  queries whose TriPrune set is empty, fall to the replica with the
  least lifetime load.  The batch is planned at ``max_batch`` rows and
  each sub-batch is padded to exactly ``cap`` rows with copies of one of
  its own queries (``CandidatePlan.subset``), whose results the executor
  drops — so each device runs one compiled shape for full and tail
  batches alike.
* One replica, or paged replicas: the query goes to the replica with
  the most votes, ties broken toward the replica with the least load
  (already-assigned batchmates included, so one batch spreads under
  ties); a query whose TriPrune set is empty falls to round-robin.  No
  cap and no padding: the batch is planned as it came, ownership alone
  decides the spread (the page cache's placement loop relies on that),
  and paged kernels run on page-run buckets whatever the batch size.

Exactness argument (DESIGN.md §9): a plan row — mask, routing, schedule
radius — is a function of that query and the snapshot metadata alone,
never of batchmates; every execution stage preserves that independence
(kernel math is per-pair, padding rows are inert, certification and
refinement are per-query).  So executing any sub-batch of a plan on any
replica of the same snapshot returns, per query, exactly what the full
batch on one executor returns — routing is a pure performance decision,
pinned by the bit-identity tests.

Each routed batch's dispatch record — the threaded dispatch's wall
time, the sum of its sub-batches' own seconds, its real and padding
rows — lands on the ``QueryProfile`` of its first sub-batch, and the
rows in the ``router.rows`` / ``router.pad_rows`` counters.

Routed-cluster counts accumulate in ``routed_heat``;
:meth:`PlanRouter.rebalance` folds the page cache's per-cluster access
counters (falling back to ``routed_heat`` when resident) back into
replica ownership — the cache → placement feedback loop.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from ..obs import registry as _obs
from ..obs.trace import span
from .replicas import ReplicaSet

# sub-batch rows are a multiple of this (the TPU's sublane tile)
_SUB_ALIGN = 8


def capacity_assign(votes: np.ndarray, load: np.ndarray,
                    cap: int) -> np.ndarray:
    """(B,) replica per query: at most ``cap`` queries a replica.

    ``votes`` (B, R) are the ownership votes, ``load`` (R,) the
    replicas' lifetime query counts.  A query prefers replicas by most
    votes, then least load, then lowest id.  Round ``j`` offers every
    unplaced query its ``j``-th preference; a replica asked by more
    queries than it has room for takes those that vote for it most
    (batch order among equals).  A query turned away goes on to its
    next preference, so when ``R * cap >= B`` every query is placed
    within R rounds.  Array operations over R × R (round, replica)
    pairs: no loop over queries."""
    B, R = votes.shape
    load_rank = np.empty(R, np.int64)
    load_rank[np.argsort(load, kind="stable")] = np.arange(R)
    pref = np.argsort(load_rank[None, :] - votes * R, axis=1,
                      kind="stable")                     # (B, R)
    pick = np.full(B, -1, np.int64)
    room = np.full(R, int(cap), np.int64)
    for j in range(R):
        want = np.where(pick < 0, pref[:, j], -1)
        for r in np.flatnonzero(room):
            ask = np.flatnonzero(want == r)
            if ask.size:
                take = ask[np.argsort(-votes[ask, r],
                                      kind="stable")[:room[r]]]
                pick[take] = r
                room[r] -= take.size
    if (pick < 0).any():
        raise ValueError(f"{B} queries exceed {R} replicas x {cap} rows")
    return pick


def _pad(a: np.ndarray, n: int) -> np.ndarray:
    """``a`` with copies of its first row appended up to ``n`` rows."""
    if len(a) >= n:
        return a
    return np.concatenate([a, np.repeat(a[:1], n - len(a), axis=0)])


class PlanRouter:
    """Dispatch query batches across a :class:`ReplicaSet` by plan.

    ``max_batch`` is the largest batch the caller sends (the frontend's
    own); with several resident replicas it fixes the sub-batch shape."""

    def __init__(self, replicas: ReplicaSet, max_batch: int):
        self.replicas = replicas
        self.max_batch = int(max_batch)
        # the routing executor: builds the batch's single plan (and owns
        # the pivot-distance seeding); replica 0 doubles as it, so a
        # one-replica set routes with zero overhead
        self.routing_ex = replicas.members[0].ex
        self.routed_heat = np.zeros(replicas.K, np.int64)
        self._lock = threading.Lock()
        self._rr = 0            # round-robin cursor (vote path, no routing)

    def shapes(self, B: int) -> tuple[int, int]:
        """(rows a batch of ``B`` is planned at, rows each sub-batch is
        padded to).  One replica, or paged replicas: ``(B, 0)``, the
        batch as it came."""
        R = len(self.replicas)
        if R == 1 or self.replicas.snapshot.store is not None:
            return B, 0
        mb = max(B, self.max_batch)
        cap = -(-mb // R)
        return mb, -(-cap // _SUB_ALIGN) * _SUB_ALIGN

    # ------------------------------------------------------------ queries
    def range_query_batch(self, Q, r):
        Q = np.atleast_2d(np.asarray(Q, np.float64))
        B = Q.shape[0]
        r_arr = np.broadcast_to(np.asarray(r, np.float64), (B,))
        n_plan, cap = self.shapes(B)
        plan = self.routing_ex.planner.plan_range(_pad(Q, n_plan),
                                                  _pad(r_arr, n_plan))
        parts = self._dispatch(Q, plan, "execute_range", cap)
        out = [None] * B
        for idx, res in parts:
            for j, b in enumerate(idx):
                out[b] = res[j]
        return out

    def knn_query_batch(self, Q, k: int, max_rounds: int = 64):
        Q = np.atleast_2d(np.asarray(Q, np.float64))
        B = Q.shape[0]
        k_eff = min(int(k), self.replicas.snapshot.live)
        if k_eff <= 0:
            return (np.empty((B, 0), np.int64), np.empty((B, 0)))
        n_plan, cap = self.shapes(B)
        plan = self.routing_ex.planner.plan_knn(_pad(Q, n_plan), k_eff,
                                                max_rounds)
        parts = self._dispatch(Q, plan, "execute_knn", cap)
        ids = np.empty((B, k_eff), np.int64)
        ds = np.empty((B, k_eff))
        for idx, (ids_p, ds_p) in parts:
            ids[idx] = ids_p
            ds[idx] = ds_p
        return ids, ds

    # ----------------------------------------------------------- dispatch
    def _assign(self, plan, B: int, cap: int) -> np.ndarray:
        """(B,) replica id per real query of ``plan``: ownership votes
        over the plan's TriPrune routing, at most ``cap`` a replica
        (:func:`capacity_assign`), or with no cap (0) the most-voted
        replica.  Its time (the routing copy included) and compiles are
        charged to the plan's cost record."""
        with plan.cost.charge(route=True), span("router.assign",
                                                {"B": plan.B}):
            return self._assign_inner(plan, B, cap)

    def _assign_inner(self, plan, B: int, cap: int) -> np.ndarray:
        routing = plan.routing[:B]                   # (B, K) bool
        own = self.replicas.ownership()              # (R, K) bool
        votes = routing.astype(np.int64) @ own.T.astype(np.int64)  # (B, R)
        with self._lock:
            self.routed_heat += routing.sum(axis=0)
            load = np.array([m.queries for m in self.replicas.members],
                            np.float64)
            if cap:
                return capacity_assign(votes, load, cap)
            pick = np.empty(routing.shape[0], np.int64)
            for b in range(routing.shape[0]):
                v = votes[b]
                if v.max() == 0:
                    pick[b] = self._rr % len(self.replicas)
                    self._rr += 1
                else:
                    tied = np.nonzero(v == v.max())[0]
                    pick[b] = tied[int(np.argmin(load[tied]))]
                load[pick[b]] += 1.0    # spread batchmates under ties
        return pick

    def _dispatch(self, Q, plan, method: str, cap: int) -> list:
        """[(query idx, sub-result)] per replica group; groups with >1
        replica run on threads (each replica's device works its own
        sub-batch concurrently).  Sub-batches are padded to ``cap`` rows
        (none when 0)."""
        B = Q.shape[0]
        pick = self._assign(plan, B, cap)
        groups = []
        for rep in self.replicas.members:
            idx = np.nonzero(pick == rep.rid)[0]
            if len(idx):
                groups.append((rep, idx))
        pad_rows = sum(cap - len(idx) for _, idx in groups) if cap else 0
        if _obs.enabled():
            reg = _obs.REGISTRY
            reg.counter("router.batches").inc()
            reg.counter("router.queries").inc(B)
            reg.counter("router.subbatches").inc(len(groups))
            reg.counter("router.rows").inc(B)
            reg.counter("router.pad_rows").inc(pad_rows)
            # how widely one batch spreads across the replica set (1 =
            # everything landed on a single replica)
            reg.histogram("router.replica_spread").observe(len(groups))
        results = [None] * len(groups)
        errors = [None] * len(groups)
        busy = [0.0] * len(groups)
        first = [None]

        def run(g: int, rep, idx) -> None:
            # a sub-batch's own seconds: its thread's CPU time plus its
            # waits on its device — not the time it queued for the
            # interpreter behind the other replicas' host work
            c0 = time.thread_time()
            prof0 = rep.ex.last_profile
            try:
                with span("router.subbatch",
                          {"replica": rep.rid, "B": len(idx)}):
                    sub = plan.subset(idx, planner=rep.ex.planner,
                                      device=rep.device, shared=g == 0,
                                      pad_to=cap or None)
                    w0 = sub.cost.device_wait_s + sub.cost.d2h_s
                    results[g] = getattr(rep.ex, method)(Q[idx], sub)
                    busy[g] = sub.cost.device_wait_s + sub.cost.d2h_s - w0
                rep.record(len(idx))
            except BaseException as e:  # re-raised on the caller thread
                errors[g] = e
            busy[g] += time.thread_time() - c0
            if g == 0 and rep.ex.last_profile is not prof0:
                first[0] = rep.ex.last_profile

        t0 = time.perf_counter()
        with span("router.dispatch",
                  {"B": plan.B, "groups": len(groups)}):
            if len(groups) == 1:
                run(0, *groups[0])
            else:
                threads = [threading.Thread(target=run, args=(g, rep, idx),
                                            name=f"lims-route-r{rep.rid}")
                           for g, (rep, idx) in enumerate(groups)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
        dispatch_s = time.perf_counter() - t0
        for err in errors:
            if err is not None:
                raise err
        prof = first[0]
        if prof is not None:            # the first sub-batch's record
            prof.dispatch_s, prof.subbatch_s = dispatch_s, sum(busy)
            prof.rows, prof.pad_rows = B, pad_rows
        return [(idx, res) for (_, idx), res in zip(groups, results)]

    # ---------------------------------------------------------- placement
    def _heat(self) -> np.ndarray:
        """The current per-cluster heat signal: page-cache access
        counters when paged, routed-cluster counts when resident.
        Always length ``replicas.K``: sharded snapshots pad K to a
        device multiple while the store reports real clusters only, so
        the tail pads with zero heat (padding clusters hold no data)."""
        heat = self.replicas.cluster_heat()
        if heat is None or not heat.any():
            heat = self.routed_heat
        heat = np.asarray(heat, np.float64).reshape(-1)
        K = self.replicas.K
        if len(heat) < K:
            heat = np.pad(heat, (0, K - len(heat)))
        return heat[:K]

    def heat_skew(self) -> float:
        """How badly ownership mismatches heat: max per-replica owned
        heat over the per-replica mean (1.0 = balanced, R = one replica
        owns everything hot).  Published as the ``router.heat_skew``
        gauge — the heat-skew detector's input; the monitor daemon
        calls this as its per-tick probe."""
        heat = self._heat()
        own = self.replicas.ownership()              # (R, K) bool
        per = own.astype(np.float64) @ heat          # (R,)
        total = per.sum()
        if total <= 0 or len(per) <= 1:
            skew = 1.0
        else:
            skew = float(per.max() / (total / len(per)))
        _obs.set_gauge("router.heat_skew", skew)
        return skew

    def rebalance(self) -> np.ndarray:
        """Fold the current heat signal into replica ownership: the page
        cache's per-cluster access counters when paged, the router's own
        routed-cluster counts when resident."""
        with span("router.rebalance"):
            moved = self.replicas.rebalance(self._heat())
        _obs.count("router.rebalances")
        return moved

    def load_stats(self) -> dict:
        return {"replicas": self.replicas.load_stats(),
                "routed_heat": self.routed_heat.tolist()}


__all__ = ["PlanRouter"]
