"""Compiled-XLA lowerings of the kernel pipeline — the CPU "compiled lane".

``pallas_call`` cannot compile on the CPU backend (it raises "Only
interpret mode is supported"), but *compiled* on XLA-CPU does not need
pallas: the same tile-blocked math lowers through ``jax.jit`` straight
to XLA's native CPU codegen (Eigen GEMMs, vectorized loops) with none of
the per-grid-cell interpreter overhead.  These functions mirror the
pallas kernel bodies operation-for-operation — the Gram-trick sql2
distance, the shared :func:`rankeval.rank_math` Clenshaw recurrence, the
fused distance+threshold range filter — so within this lane the fused
and staged pipelines are bit-identical (pinned in tests), and across
lanes results agree to f32 tolerance (accumulation order in the dot may
differ).

Tile sizes here are real tuning parameters, not grid geometry: a
``(bq, bp, qb)`` / ``(bg, bb)`` tuple becomes ``lax.map`` chunk sizes —
cache blocking — which is exactly what the autotuner searches per shape
bucket.  A chunk size >= the operand dimension means "no chunking": one
fused XLA computation over the whole operand (for the sql2 Gram path
that is usually the winner; for the broadcast l1/linf path chunking is
mandatory to bound the (bq, bp, d) intermediate).

Query blocking: the query×points kernels take a third chunk size ``qb``
(query *sub*-block).  The loop nest is query super-tiles (``bq``) →
point blocks (``bp``) → query sub-blocks (``qb``): each point block is
loaded once per super-tile and stays cache-resident while the ``qb``-row
sub-blocks stream over it, instead of the whole point array being
re-streamed per query tile.  ``qb >= bq`` (or 0) disables sub-blocking.
Every output cell is produced by the same per-pair arithmetic regardless
of the (bq, bp, qb) choice, so results are bit-identical across tilings
(pinned in tests) — tiles move bytes, not math.

Operands arrive padded to tile multiples (``ops.py`` does the padding,
same as for the pallas lane), so every ``reshape(n // b, b, ...)`` here
is exact by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .rankeval import rank_math


def _gram_sq(q: jax.Array, p: jax.Array) -> jax.Array:
    """Squared-L2 distance block via the Gram trick, clamped at 0.

    Identical operation sequence to ``pdist._pdist_l2_kernel`` /
    ``range_filter``'s distance half: f32 row norms + one
    ``dot_general`` with f32 accumulation.
    """
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    pn = jnp.sum(p * p, axis=-1, keepdims=True)
    g = jax.lax.dot_general(q, p, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    return jnp.maximum(qn + pn.T - 2.0 * g, 0.0)


def _pdist_block(qb: jax.Array, pb: jax.Array, metric: str) -> jax.Array:
    if metric == "sql2":
        return _gram_sq(qb, pb)
    diff = jnp.abs(qb[:, None, :] - pb[None, :, :])
    if metric == "l1":
        return jnp.sum(diff, axis=-1)
    if metric == "linf":
        return jnp.max(diff, axis=-1)
    raise ValueError(f"unknown metric {metric!r}")


def _map_pblocks(fn, p: jax.Array, bp: int):
    """Map ``fn`` over row-chunks of ``p`` and re-join on the *column*
    axis of fn's (nq, bp)-shaped output: (nP, nq, bp) → (nq, nP*bp)."""
    npts, d = p.shape
    out = jax.lax.map(fn, p.reshape(npts // bp, bp, d))
    return jnp.swapaxes(out, 0, 1).reshape(out.shape[1], npts)


def _map_qsub(fn, qs: jax.Array, qb: int):
    """Map ``fn`` over ``qb``-row sub-blocks of a query super-tile and
    re-join on the row axis: (nS, qb, cols) → (nS*qb, cols).  The point
    operand is closed over — loaded once, reused across sub-blocks."""
    gsz, d = qs.shape
    if qb <= 0 or qb >= gsz:
        return fn(qs)
    out = jax.lax.map(fn, qs.reshape(gsz // qb, qb, d))
    return out.reshape(gsz, out.shape[-1])


@functools.partial(jax.jit, static_argnames=("metric", "bq", "bp", "qb"))
def pdist_xla(q: jax.Array, p: jax.Array, metric: str = "sql2",
              bq: int = 128, bp: int = 128, qb: int = 0) -> jax.Array:
    """(nq, npts) f32 distance matrix; nq % bq == 0, npts % bp == 0,
    bq % qb == 0 when query sub-blocking is on (qb in (0, bq))."""
    q = q.astype(jnp.float32)
    p = p.astype(jnp.float32)
    nq, d = q.shape
    npts = p.shape[0]
    if 0 < qb < min(bq, nq):
        assert min(bq, nq) % qb == 0, (nq, bq, qb)

    def qblock(qs):
        def pblock(pb):
            return _map_qsub(lambda qsub: _pdist_block(qsub, pb, metric),
                             qs, qb)
        if bp >= npts:
            return pblock(p)
        return _map_pblocks(pblock, p, bp)

    if bq >= nq:
        return qblock(q)
    out = jax.lax.map(qblock, q.reshape(nq // bq, bq, d))
    return out.reshape(nq, npts)


@functools.partial(jax.jit, static_argnames=("n_rings", "bg", "bb"))
def rankeval_xla(x: jax.Array, coef: jax.Array, lo: jax.Array,
                 hi: jax.Array, n: jax.Array, n_rings: int = 20,
                 bg: int = 8, bb: int = 128):
    """Returns (rank, rid), both (G, B) int32 — same math as the pallas
    kernel via the shared ``rank_math``; (bg, bb) are chunk sizes."""
    g, b = x.shape
    n_coef = coef.shape[1]

    def gblock(args):
        xg, cg, log, hig, ng = args

        def bblock(xb):
            return rank_math(xb, cg, log, hig, ng, n_coef=n_coef,
                             n_rings=n_rings)

        if bb >= b:
            return bblock(xg)
        gsz = xg.shape[0]
        xbs = jnp.moveaxis(xg.reshape(gsz, b // bb, bb), 1, 0)
        rk, rid = jax.lax.map(bblock, xbs)          # (nB, gsz, bb) each
        return (jnp.moveaxis(rk, 0, 1).reshape(gsz, b),
                jnp.moveaxis(rid, 0, 1).reshape(gsz, b))

    args = (x, coef, lo, hi, n)
    if bg >= g:
        return gblock(args)
    chunked = tuple(a.reshape(g // bg, bg, *a.shape[1:]) for a in args)
    rk, rid = jax.lax.map(gblock, chunked)          # (nG, bg, b) each
    return rk.reshape(g, b), rid.reshape(g, b)


@functools.partial(jax.jit, static_argnames=("bq", "bp", "qb"))
def range_filter_xla(q: jax.Array, p: jax.Array, r: jax.Array,
                     bq: int = 128, bp: int = 128, qb: int = 0):
    """Fused sql2 distance + threshold: mask (nq, npts) uint8 — same
    contract as the pallas kernel (``r`` is the per-query radius,
    squared here).  Same query-blocked
    nest as :func:`pdist_xla`: each point block is loaded once per query
    super-tile and reused across the ``qb``-row sub-blocks."""
    q = q.astype(jnp.float32)
    p = p.astype(jnp.float32)
    r2 = (r * r).astype(jnp.float32)
    nq, d = q.shape
    npts = p.shape[0]
    if 0 < qb < min(bq, nq):
        assert min(bq, nq) % qb == 0, (nq, bq, qb)

    def qblock(args):
        qs, r2s = args
        gsz = qs.shape[0]

        def pblock(pb):
            def sub(a):
                qsub, r2sub = a
                return (_gram_sq(qsub, pb) <= r2sub[:, None]).astype(
                    jnp.uint8)
            if qb <= 0 or qb >= gsz:
                return sub((qs, r2s))
            m = jax.lax.map(sub, (qs.reshape(gsz // qb, qb, d),
                                  r2s.reshape(gsz // qb, qb)))
            return m.reshape(gsz, pb.shape[0])

        if bp >= npts:
            return pblock(p)
        return _map_pblocks(pblock, p, bp)

    if bq >= nq:
        return qblock((q, r2))
    m = jax.lax.map(qblock, (q.reshape(nq // bq, bq, d),
                             r2.reshape(nq // bq, bq)))
    return m.reshape(nq, npts)


@functools.partial(jax.jit, static_argnames=("n_rings", "bg", "bb"))
def pdist_rankeval_xla(q: jax.Array, piv: jax.Array, coef: jax.Array,
                       lo: jax.Array, hi: jax.Array, n: jax.Array,
                       rg: jax.Array, n_rings: int = 20, bg: int = 8,
                       bb: int = 128):
    """Fused plan stage: query→pivot distances + rank eval at the
    widened-radius boundaries, one compiled program, no (G, 2B) distance
    staging buffer.

    ``q`` (B, d) queries; ``piv`` (G, d) pivots; ``coef`` (G, C);
    ``lo``/``hi``/``n`` (G,); ``rg`` (B,) guard-widened radii.  Returns
    ``(dq (B, G) f32, rank_lo (G, B) i32, rank_hi (G, B) i32)`` where
    rank_lo/hi evaluate at dq∓rg — exactly the staged planner's
    ``rankeval(concat(dq-rg, dq+rg))`` split back into halves.  ``bb``
    chunks the query (B) axis: the pivot plane and model params are
    loaded once per query chunk and reused, bounding the live
    (bb, bg) distance/rank tiles — the same query-blocked nest as
    :func:`pdist_xla`.
    """
    q = q.astype(jnp.float32)
    B, d = q.shape
    g = piv.shape[0]
    n_coef = coef.shape[1]
    rg = rg.astype(jnp.float32)
    gargs = (piv.astype(jnp.float32), coef, lo, hi, n)

    def bchunk(qargs):
        qc, rgc = qargs                             # (bb, d), (bb,)

        def gblock(args):
            pg, cg, log, hig, ng = args
            dq = jnp.sqrt(_gram_sq(qc, pg))         # (bb, bg)
            xlo = dq.T - rgc[None, :]               # (bg, bb)
            xhi = dq.T + rgc[None, :]
            rk_lo, _ = rank_math(xlo, cg, log, hig, ng, n_coef=n_coef,
                                 n_rings=n_rings)
            rk_hi, _ = rank_math(xhi, cg, log, hig, ng, n_coef=n_coef,
                                 n_rings=n_rings)
            return dq, rk_lo, rk_hi

        if bg >= g:
            return gblock(gargs)
        chunked = tuple(a.reshape(g // bg, bg, *a.shape[1:])
                        for a in gargs)
        dq, rk_lo, rk_hi = jax.lax.map(gblock, chunked)
        bc = qc.shape[0]
        return (jnp.swapaxes(dq, 0, 1).reshape(bc, g),
                rk_lo.reshape(g, bc), rk_hi.reshape(g, bc))

    if bb >= B:
        return bchunk((q, rg))
    dq, rk_lo, rk_hi = jax.lax.map(
        bchunk, (q.reshape(B // bb, bb, d), rg.reshape(B // bb, bb)))
    return (dq.reshape(B, g),
            jnp.swapaxes(rk_lo, 0, 1).reshape(g, B),
            jnp.swapaxes(rk_hi, 0, 1).reshape(g, B))


__all__ = ["pdist_xla", "rankeval_xla", "range_filter_xla",
           "pdist_rankeval_xla"]
