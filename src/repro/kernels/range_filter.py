"""Fused refinement kernel: distance + threshold, no HBM distance matrix.

LIMS's refinement step (Alg. 1 line 30) computes exact distances for the
candidate pages and filters by radius. Fusing the compare into the distance
tile means only a uint8 mask ever leaves VMEM — 4× less HBM write traffic
than materializing fp32 distances, which is what makes the refinement
memory-bound term small on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret


def _range_filter_kernel(q_ref, p_ref, r2_ref, mask_ref):
    q = q_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    r2 = r2_ref[...].astype(jnp.float32)                   # (bq, 1) radius²
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    pn = jnp.sum(p * p, axis=-1, keepdims=True)
    g = jax.lax.dot_general(q, p, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qn + pn.T - 2.0 * g, 0.0)
    mask_ref[...] = (d2 <= r2).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("bq", "bp", "interpret"))
def range_filter_pallas(q: jax.Array, p: jax.Array, r: jax.Array,
                        bq: int = 128, bp: int = 128,
                        interpret: bool | None = None) -> jax.Array:
    """(nq, np) uint8 mask of the L2 ball test ``|q - p| <= r``.

    ``r`` is one radius per query row (nq,) — batched heterogeneous range
    queries in one launch.  It enters the kernel as an (nq, 1) column so
    every block keeps the TPU's 2-D (sublane, lane) tiling.
    ``interpret=None`` auto-selects by backend (compiled on TPU/GPU,
    interpreted on CPU).

    Point-major grid (query tiles iterate fastest), same as
    ``pdist_pallas``: each candidate tile is fetched once and reused
    across the query tiles; per-cell outputs are unchanged.
    """
    interpret = resolve_interpret(interpret)
    nq, d = q.shape
    npts, _ = p.shape
    assert nq % bq == 0 and npts % bp == 0
    r2 = (r * r).astype(jnp.float32).reshape(nq, 1)
    return pl.pallas_call(
        _range_filter_kernel,
        grid=(npts // bp, nq // bq),
        in_specs=[
            pl.BlockSpec((bq, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bp, d), lambda j, i: (j, 0)),
            pl.BlockSpec((bq, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bp), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, npts), jnp.uint8),
        interpret=interpret,
    )(q, p, r2)
