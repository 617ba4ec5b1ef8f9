"""Fused rank-model inference kernel: Clenshaw polynomial eval + ring ID.

The TPU analogue of LIMS's per-query model calls: for G (cluster, pivot)
groups at once, evaluate each group's degree-g Chebyshev rank model on a
(G, B) tile of distances and fuse the ring-ID transform
rid = clip(rank // ceil(n/N), 0, N-1) — one VMEM pass, VPU only.

Layout: x (G, B) distances; coef (G, C) low→high Chebyshev coefficients
(zero-padded to a common C); lo/hi/n (G,) per-group normalization, fed
to the kernel as (G, 1) columns so every block keeps the TPU's 2-D
(sublane, lane) tiling; a single pass produces both clipped ranks and
ring IDs.

:func:`rank_math` holds the arithmetic itself so the compiled-XLA lane
(``xla.py``) and the fused pdist→rankeval kernel (``fused.py``) execute
the exact same f32 operation sequence as this kernel — bit-identity
across call sites depends on sharing it, not on reimplementing the
recurrence (``ref.py`` intentionally uses a different one).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret


def rank_math(x, coef, lo, hi, n, *, n_coef: int, n_rings: int):
    """Clenshaw rank eval + ring id on a (g, b) tile; returns int32 pair.

    ``x`` (g, b) f32 distances; ``coef`` (g, C); ``lo``/``hi``/``n``
    (g,) or (g, 1).  Pure jnp — callable from a pallas kernel body (on
    materialized refs) and from jitted XLA code alike.
    """
    x = x.astype(jnp.float32)
    lo = lo.astype(jnp.float32).reshape(-1, 1)          # (g, 1)
    hi = hi.astype(jnp.float32).reshape(-1, 1)
    n = n.astype(jnp.float32).reshape(-1, 1)
    t = (x - lo) / jnp.maximum(hi - lo, 1e-30) * 2.0 - 1.0
    t = jnp.clip(t, -1.0, 1.0)
    # Clenshaw recurrence, coefficients high -> low (static unroll over C)
    b1 = jnp.zeros_like(t)
    b2 = jnp.zeros_like(t)
    t2 = 2.0 * t
    for k in range(n_coef - 1, 0, -1):
        c_k = coef[:, k:k + 1].astype(jnp.float32)
        b1, b2 = c_k + t2 * b1 - b2, b1
    c0 = coef[:, 0:1].astype(jnp.float32)
    r = c0 + t * b1 - b2
    rank = jnp.clip(jnp.rint(r), 0.0, jnp.maximum(n - 1.0, 0.0))
    width = jnp.ceil(n / float(n_rings))
    rid = jnp.clip(jnp.floor(rank / jnp.maximum(width, 1.0)), 0.0,
                   float(n_rings - 1))
    return rank.astype(jnp.int32), rid.astype(jnp.int32)


def _rankeval_kernel(x_ref, coef_ref, lo_ref, hi_ref, n_ref, o_rank_ref,
                     o_rid_ref, *, n_coef: int, n_rings: int):
    rank, rid = rank_math(x_ref[...], coef_ref[...], lo_ref[...],
                          hi_ref[...], n_ref[...], n_coef=n_coef,
                          n_rings=n_rings)
    o_rank_ref[...] = rank
    o_rid_ref[...] = rid


@functools.partial(
    jax.jit, static_argnames=("n_rings", "bg", "bb", "interpret"))
def rankeval_pallas(x: jax.Array, coef: jax.Array, lo: jax.Array,
                    hi: jax.Array, n: jax.Array, n_rings: int = 20,
                    bg: int = 8, bb: int = 128,
                    interpret: bool | None = None):
    """Returns (rank, rid), both (G, B) int32. ``interpret=None``
    auto-selects by backend (compiled on TPU/GPU, interpreted on CPU)."""
    interpret = resolve_interpret(interpret)
    g, b = x.shape
    g2, n_coef = coef.shape
    assert g == g2 and g % bg == 0 and b % bb == 0, (x.shape, coef.shape, bg, bb)
    lo, hi, n = (a.reshape(g, 1) for a in (lo, hi, n))
    kern = functools.partial(_rankeval_kernel, n_coef=n_coef,
                             n_rings=n_rings)
    return pl.pallas_call(
        kern,
        grid=(g // bg, b // bb),
        in_specs=[
            pl.BlockSpec((bg, bb), lambda i, j: (i, j)),
            pl.BlockSpec((bg, n_coef), lambda i, j: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bg, bb), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bb), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, b), jnp.int32),
            jax.ShapeDtypeStruct((g, b), jnp.int32),
        ],
        interpret=interpret,
    )(x, coef, lo, hi, n)
