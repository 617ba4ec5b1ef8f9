"""Replica set: snapshot placement + per-replica load accounting.

A :class:`ReplicaSet` places one executor per serving replica, each
bound to the *same* snapshot generation with its device arrays
``jax.device_put`` onto that replica's device.  The snapshot is a
frozen pytree whose leaves are exactly the device arrays
(``_DEVICE_FIELDS``) and whose aux data (ids, validity, the
generation-bound ``StoreView``) is shared by reference — so placement
is one pytree map, replicas can never disagree about generation
content, and every paged replica gathers through the same page cache
(one buffer pool, one set of access counters, one pin ledger).

In logical-axis terms (``repro.sharding.logical``) this is the
*replicated* placement of the "clusters" axis: where ``ShardedExecutor``
maps clusters → mesh ``data`` axis (each device holds a shard and
collectives merge per-round reductions), a replica set gives every
device the whole cluster axis and partitions the *request* stream
instead — the router sends each query sub-batch to one replica, chosen
by TriPrune cluster ownership; resident replicas each take at most a
fixed capacity, and their sub-batches are padded to it so each device
keeps one program shape (``serving/router.py``).  Both placements
preserve exactness for free (per-cluster state is self-contained;
per-query results are independent of batchmates); replication trades
memory for routing freedom and zero cross-device collectives on the
hot path.

Cluster *ownership* is the routing preference, not a data partition:
every replica can execute any query bit-identically; ownership decides
which replica a query's TriPrune cluster set votes for, and a query
goes to its most-voted replica (among resident replicas, while that one
has room).  The default is round-robin (cluster k → replica k mod R);
:meth:`ReplicaSet.rebalance` reassigns ownership greedily from a
cluster-heat signal — by default the page cache's access counters
folded per extent (``PagedStore.cluster_heat``), closing the storage →
placement feedback loop (DESIGN.md §9).
"""
from __future__ import annotations

import threading

import numpy as np

import jax

from ..core.executor import QueryExecutor
from ..core.snapshot import LIMSSnapshot
from ..obs import registry as _obs


class Replica:
    """One serving replica: an executor on a device + load counters."""

    def __init__(self, rid: int, device, ex: QueryExecutor):
        self.rid = rid
        self.device = device
        self.ex = ex
        self._lock = threading.Lock()
        self.batches = 0
        self.queries = 0

    def record(self, n_queries: int) -> None:
        with self._lock:
            self.batches += 1
            self.queries += n_queries
        _obs.count(f"replica.{self.rid}.batches")
        _obs.count(f"replica.{self.rid}.queries", n_queries)

    def stats(self) -> dict:
        with self._lock:
            return {"rid": self.rid, "device": str(self.device),
                    "batches": self.batches, "queries": self.queries}


class ReplicaSet:
    """Executors over one snapshot generation, one per device.

    ``n_replicas=None`` → one replica per visible device (devices cycle
    when asked for more — useful for exercising the routing logic on a
    single-device host).  All replicas share the snapshot's aux state,
    including its ``StoreView`` when paged.
    """

    def __init__(self, snapshot: LIMSSnapshot, n_replicas: int | None = None,
                 devices: list | None = None,
                 prefetch: str | None = None):
        devices = list(devices) if devices is not None else jax.devices()
        n = int(n_replicas) if n_replicas is not None else len(devices)
        if n < 1:
            raise ValueError("a replica set needs at least one replica")
        self.snapshot = snapshot
        self.K = snapshot.K
        self.members: list[Replica] = []
        for i in range(n):
            dev = devices[i % len(devices)]
            snap_i = jax.device_put(snapshot, dev)
            self.members.append(
                Replica(i, dev, QueryExecutor(snap_i, prefetch=prefetch)))
        # ownership[k] = the replica cluster k's routing votes go to
        self._owner = np.arange(self.K, dtype=np.int64) % n
        self._own_lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.members)

    @property
    def owner(self) -> np.ndarray:
        """(K,) replica id owning each cluster (routing preference)."""
        return self._owner.copy()

    def ownership(self) -> np.ndarray:
        """(R, K) bool ownership matrix (the router's vote weights)."""
        with self._own_lock:
            return self._owner[None, :] == \
                np.arange(len(self.members))[:, None]

    def cluster_heat(self) -> np.ndarray | None:
        """(K,) access heat from the page cache, or None when resident
        (no page counters to fold — the router falls back to its own
        routed-cluster counts)."""
        store = self.snapshot.store
        return store.cluster_heat() if store is not None else None

    def rebalance(self, heat: np.ndarray) -> np.ndarray:
        """Reassign cluster ownership from a heat signal: hottest
        cluster first, each to the replica with the least heat assigned
        so far — the greedy makespan balance.  Returns the new (K,)
        owner array.  Queries in flight are unaffected (ownership only
        biases future routing; results never depend on it)."""
        heat = np.asarray(heat, np.float64)
        if heat.shape != (self.K,):
            raise ValueError(f"heat must be shape ({self.K},)")
        R = len(self.members)
        owner = np.empty(self.K, np.int64)
        load = np.zeros(R, np.float64)
        for k in np.argsort(-heat, kind="stable"):
            r = int(np.argmin(load))
            owner[k] = r
            load[r] += heat[k]
        with self._own_lock:
            self._owner = owner
        return owner.copy()

    def set_ownership(self, owner: np.ndarray) -> None:
        """Install an explicit (K,) ownership map.  Exactness never
        depends on ownership, so any assignment is legal — this is how
        demos and tests inject placement drift (stale ownership vs live
        heat) for the monitor daemon to detect and repair."""
        owner = np.asarray(owner, np.int64)
        if owner.shape != (self.K,):
            raise ValueError(f"owner must be shape ({self.K},)")
        R = len(self.members)
        if owner.size and (owner.min() < 0 or owner.max() >= R):
            raise ValueError(f"owner ids must be in [0, {R})")
        with self._own_lock:
            self._owner = owner.copy()

    def load_stats(self) -> list:
        with self._own_lock:
            counts = np.bincount(self._owner, minlength=len(self.members))
        out = []
        for rep, c in zip(self.members, counts):
            st = rep.stats()
            st["owned_clusters"] = int(c)
            out.append(st)
        return out


__all__ = ["Replica", "ReplicaSet"]
