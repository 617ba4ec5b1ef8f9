"""Fused pdist→rankeval Pallas kernel: the planner's hot stage in one launch.

The staged plan pipeline materializes a (B, G) pivot-distance block to
HBM, reshapes it into a (G, 2B) boundary matrix, and launches a second
kernel over it.  This kernel computes both in one grid cell: the Gram
sql2 distance tile, the sqrt, and the Clenshaw rank eval at the two
widened-radius boundaries dq∓rg — the distance tile lives only in VMEM.
Math is shared with the staged kernels (``xla._gram_sq`` mirrors
``pdist._pdist_l2_kernel``; ``rankeval.rank_math`` is literally the same
function), so fused-vs-staged bit-identity within a lane is structural,
not coincidental — and pinned by tests.

Grid: (B//bb, G//bg); each cell loads a (bb, d) query tile, a (bg, d)
pivot tile, a (1, bb) row of guard radii and (bg, 1) columns of model
params, and writes a (bb, bg) distance tile and two (bg, bb) rank
tiles.  Every block is 2-D with its last dim the full array dim or a
multiple of the 128-lane tile, so on TPU both ``bb`` and ``bg`` are
lane-aligned (``ops.py`` pads G to the group tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret
from .rankeval import rank_math


def _pdist_rankeval_kernel(q_ref, piv_ref, rg_ref, coef_ref, lo_ref,
                           hi_ref, n_ref, o_dq_ref, o_lo_ref, o_hi_ref,
                           *, n_coef: int, n_rings: int):
    qb = q_ref[...].astype(jnp.float32)                 # (bb, d)
    pv = piv_ref[...].astype(jnp.float32)               # (bg, d)
    qn = jnp.sum(qb * qb, axis=-1, keepdims=True)
    pn = jnp.sum(pv * pv, axis=-1, keepdims=True)
    g = jax.lax.dot_general(qb, pv, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qn + pn.T - 2.0 * g, 0.0)
    dq = jnp.sqrt(d2)                                   # (bb, bg)
    rg = rg_ref[...].astype(jnp.float32)                # (1, bb)
    xlo = dq.T - rg                                     # (bg, bb)
    xhi = dq.T + rg
    coef = coef_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]
    nn = n_ref[...]
    rk_lo, _ = rank_math(xlo, coef, lo, hi, nn, n_coef=n_coef,
                         n_rings=n_rings)
    rk_hi, _ = rank_math(xhi, coef, lo, hi, nn, n_coef=n_coef,
                         n_rings=n_rings)
    o_dq_ref[...] = dq
    o_lo_ref[...] = rk_lo
    o_hi_ref[...] = rk_hi


@functools.partial(
    jax.jit, static_argnames=("n_rings", "bg", "bb", "interpret"))
def pdist_rankeval_pallas(q: jax.Array, piv: jax.Array, coef: jax.Array,
                          lo: jax.Array, hi: jax.Array, n: jax.Array,
                          rg: jax.Array, n_rings: int = 20, bg: int = 8,
                          bb: int = 128, interpret: bool | None = None):
    """Returns (dq (B, G) f32, rank_lo (G, B) i32, rank_hi (G, B) i32).

    ``q`` (B, d) f32; ``piv`` (G, d); ``coef`` (G, C); ``lo``/``hi``/
    ``n`` (G,); ``rg`` (B,).  B % bb == 0 and G % bg == 0 (``ops.py``
    pads).  sql2/L2 only — the query path's metric.
    """
    interpret = resolve_interpret(interpret)
    B, d = q.shape
    G, n_coef = coef.shape
    assert piv.shape == (G, d) and B % bb == 0 and G % bg == 0, (
        q.shape, piv.shape, bg, bb)
    lo, hi, n = (a.reshape(G, 1) for a in (lo, hi, n))
    rg = rg.reshape(1, B)
    kern = functools.partial(_pdist_rankeval_kernel, n_coef=n_coef,
                             n_rings=n_rings)
    return pl.pallas_call(
        kern,
        grid=(B // bb, G // bg),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bg, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bb), lambda i, j: (0, i)),
            pl.BlockSpec((bg, n_coef), lambda i, j: (j, 0)),
            pl.BlockSpec((bg, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bg, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bg, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bg), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bb), lambda i, j: (j, i)),
            pl.BlockSpec((bg, bb), lambda i, j: (j, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, G), jnp.float32),
            jax.ShapeDtypeStruct((G, B), jnp.int32),
            jax.ShapeDtypeStruct((G, B), jnp.int32),
        ],
        interpret=interpret,
    )(q, piv, rg, coef, lo, hi, n)


__all__ = ["pdist_rankeval_pallas"]
