"""Frozen copies of the PR-4 query drivers — golden references.

These are the four hand-rolled drivers the plan/execute refactor
replaced (resident/store × range/kNN), copied verbatim from the
pre-refactor ``repro.core.executor`` and kept as executable golden
outputs: the unified CandidatePlan path must return results bit-identical
to every one of them, on every CI leg.  They run against a *new-style*
executor object, using only the stable hooks the refactor kept
(``_candidate_mask``, ``_sq_dists``, ``_refine_rows``, ``snap``) plus
the kernel wrappers and the IO-batch scheduler — so the masks and kernel
math they consume are the same ones the unified path consumes, and any
divergence is attributable to the driver logic itself.

Do not "improve" this file: it is a pin, not production code.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.metrics import dist_one_to_many
from repro.core.planner import _BALL_ABS, _R_REL
from repro.kernels import ops
from repro.storage import plan_batch

_FAR = np.float32(1e30)


def _pad_bucket(rows32: np.ndarray, min_rows: int = 128) -> np.ndarray:
    """Pre-refactor power-of-two bucketing for store-mode launches."""
    n = rows32.shape[0]
    bucket = max(min_rows, 1 << max(n - 1, 1).bit_length())
    if bucket <= n:
        return rows32
    pad = np.full((bucket - n, rows32.shape[1]), _FAR, np.float32)
    return np.concatenate([rows32, pad])


def _refine_topk(ex, Q, final: np.ndarray, k_eff: int):
    """The shared exact-refinement tail, as it was."""
    s = ex.snap
    B = Q.shape[0]
    ids_out = np.empty((B, k_eff), np.int64)
    d_out = np.empty((B, k_eff))
    for b in range(B):
        idx = np.nonzero(final[b])[0]
        d_true = dist_one_to_many(Q[b], ex._refine_rows(idx), "l2")
        sel = np.argsort(d_true, kind="stable")[:k_eff]
        ids_out[b] = s.gids_np[idx[sel]]
        d_out[b] = d_true[sel]
    return ids_out, d_out


# ------------------------------------------------------------- range drivers
def range_resident(ex, Q, r):
    """PR-4 ``QueryExecutor.range_query_batch`` on a resident snapshot."""
    s = ex.snap
    Q = np.atleast_2d(np.asarray(Q, np.float64))
    B = Q.shape[0]
    r_arr = np.broadcast_to(np.asarray(r, np.float64), (B,))
    qf = jnp.asarray(Q, jnp.float32)
    rf = jnp.asarray(r_arr, jnp.float32)
    cand = ex._candidate_mask(qf, rf)
    ball = ops.range_filter(qf, s.rows.reshape(s.n_slots, s.d),
                               rf * (1.0 + _R_REL) + _BALL_ABS)
    hit = np.asarray(cand & ball.astype(bool))
    out = []
    for b in range(B):
        idx = np.nonzero(hit[b])[0]
        ids = s.gids_np[idx]
        d_true = dist_one_to_many(Q[b], ex._refine_rows(idx), "l2")
        keep = d_true <= r_arr[b]
        out.append((ids[keep], d_true[keep]))
    return out


def range_store(ex, Q, r):
    """PR-4 ``QueryExecutor._hits_store`` + refinement on a paged snapshot."""
    s = ex.snap
    store = s.store
    Q = np.atleast_2d(np.asarray(Q, np.float64))
    B = Q.shape[0]
    r_arr = np.broadcast_to(np.asarray(r, np.float64), (B,))
    qf = jnp.asarray(Q, jnp.float32)
    rf = jnp.asarray(r_arr, jnp.float32)
    cand = np.asarray(ex._candidate_mask(qf, rf))
    plan = plan_batch(cand, store.layout)
    store.fetch(plan)
    hit = np.zeros_like(cand)
    if len(plan.slots):
        rows64 = store.gather(plan.slots)
        ball = ops.range_filter(
            qf, jnp.asarray(_pad_bucket(rows64.astype(np.float32))),
            rf * (1.0 + _R_REL) + _BALL_ABS)
        ball = np.asarray(ball, bool)[:, :len(plan.slots)]
        hit[:, plan.slots] = cand[:, plan.slots] & ball
    out = []
    for b in range(B):
        idx = np.nonzero(hit[b])[0]
        ids = s.gids_np[idx]
        d_true = dist_one_to_many(Q[b], ex._refine_rows(idx), "l2")
        keep = d_true <= r_arr[b]
        out.append((ids[keep], d_true[keep]))
    return out


# --------------------------------------------------------------- kNN drivers
def knn_resident(ex, Q, k: int, max_rounds: int = 64):
    """PR-4 host-driven growing-radius kNN over a resident snapshot
    (per-round host sync, f32 k-th-distance seeding)."""
    s = ex.snap
    Q = np.atleast_2d(np.asarray(Q, np.float64))
    B = Q.shape[0]
    k_eff = min(int(k), s.live)
    if k_eff <= 0:
        return (np.empty((B, 0), np.int64), np.empty((B, 0)))
    qf = jnp.asarray(Q, jnp.float32)
    d2 = ex._sq_dists(qf)
    kth0 = jnp.sqrt(jnp.maximum(
        -jax.lax.top_k(-d2, k_eff)[0][:, -1], 0.0))
    r = np.asarray(kth0, np.float64) * (1.0 + 1e-3) + _BALL_ABS
    done = np.zeros(B, bool)
    final = np.zeros((B, d2.shape[1]), bool)
    for _ in range(max_rounds):
        rf = jnp.asarray(r, jnp.float32)
        cand = ex._candidate_mask(qf, rf)
        ball = d2 <= ((rf * (1.0 + _R_REL) + _BALL_ABS) ** 2)[:, None]
        candb = cand & ball
        cnt = jnp.sum(candb, axis=1)
        dm = jnp.where(candb, d2, jnp.inf)
        kth = jnp.sqrt(jnp.maximum(
            -jax.lax.top_k(-dm, k_eff)[0][:, -1], 0.0))
        ok = np.asarray((cnt >= k_eff) &
                        (kth <= rf * (1.0 - _R_REL) - _BALL_ABS))
        newly = ok & ~done
        if newly.any():
            final[newly] = np.asarray(candb)[newly]
            done |= newly
        if done.all():
            break
        r = np.where(done, r, r * 2.0)
    else:
        final[~done] = s.valid_np[None]
    return _refine_topk(ex, Q, final, k_eff)


def knn_store(ex, Q, k: int, max_rounds: int = 64):
    """PR-4 ``QueryExecutor._knn_store``: host-driven growing-radius kNN
    whose IO is the candidate pages (pivot-distance seeding)."""
    s = ex.snap
    store = s.store
    Q = np.atleast_2d(np.asarray(Q, np.float64))
    B = Q.shape[0]
    k_eff = min(int(k), s.live)
    if k_eff <= 0:
        return (np.empty((B, 0), np.int64), np.empty((B, 0)))
    qf = jnp.asarray(Q, jnp.float32)
    K, n_max, m = s.rids.shape
    dq = np.asarray(jnp.sqrt(jnp.maximum(
        ops.pdist(qf, s.pivots.reshape(K * m, s.d)), 0.0)))
    live_k = s.valid_np.reshape(K, n_max).any(axis=1)
    dqm = np.where(np.repeat(live_k, m)[None], dq, np.inf)
    r = dqm.min(axis=1).astype(np.float64) * (1.0 + 1e-3) + _BALL_ABS
    done = np.zeros(B, bool)
    final = np.zeros((B, s.n_slots), bool)
    pos = np.full(s.n_slots, -1, np.int64)
    d2g = np.empty((B, 0), np.float32)
    pages_seen = [set() for _ in range(B)]
    seen = np.zeros((B, s.n_slots), bool)
    for _ in range(max_rounds):
        rf = jnp.asarray(r, jnp.float32)
        cand = np.array(ex._candidate_mask(qf, rf))
        cand[done] = False
        plan = plan_batch(cand, store.layout, per_query=False)
        store.fetch(plan)
        newly = cand & ~seen
        seen |= cand
        for b in np.nonzero(newly.any(axis=1))[0]:
            pages_seen[b].update(store.layout.slot_pages(
                np.nonzero(newly[b])[0]).tolist())
        new = plan.slots[pos[plan.slots] < 0]
        if len(new):
            rows64 = store.gather(new)
            d2_new = np.asarray(ops.pdist(
                qf, jnp.asarray(_pad_bucket(
                    rows64.astype(np.float32)))))[:, :len(new)]
            pos[new] = d2g.shape[1] + np.arange(len(new))
            d2g = np.concatenate([d2g, d2_new], axis=1)
        r32 = np.asarray(rf)
        thr = (r32 * np.float32(1.0 + _R_REL) +
               np.float32(_BALL_ABS)) ** 2
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
            if kth <= cert[b]:
                final[b, sl[inball]] = True
                done[b] = True
        if done.all():
            break
        r = np.where(done, r, r * 2.0)
    else:
        final[~done] = s.valid_np[None]
        seen[~done] = s.valid_np[None]
    return _refine_topk(ex, Q, final, k_eff)
