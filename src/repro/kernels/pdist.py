"""Blocked pairwise-distance Pallas kernel (the paper's dominant cost).

TPU mapping of the distance computations LIMS performs everywhere
(clustering passes, pivot columns, refinement): a 2-D grid over
(query tiles × point tiles). For L2 the Gram trick turns the inner loop
into an MXU matmul with fp32 accumulation at ``Precision.HIGHEST`` (a
TPU f32 matmul at default precision may round its operands to bf16, an
error far above the planner's guard bands); L1/Linf run on the VPU with the
feature dimension resident in VMEM.

Tile sizing: (bq, d) + (bp, d) + (bq, bp) in VMEM. With the default
bq = bp = 128 and d ≤ 4096 this is ≤ 2×128×4096×4B + 64KB ≈ 4.3 MB —
comfortably inside a v5e's 16 MB VMEM, and every matmul dim is a multiple
of the 128-lane MXU width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret


def _pdist_l2_kernel(q_ref, p_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)           # (bq, 1)
    pn = jnp.sum(p * p, axis=-1, keepdims=True)           # (bp, 1)
    # MXU: (bq, d) @ (d, bp) with fp32 accumulation
    g = jax.lax.dot_general(q, p, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    o_ref[...] = jnp.maximum(qn + pn.T - 2.0 * g, 0.0)


def _pdist_l1_kernel(q_ref, p_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)                    # (bq, d)
    p = p_ref[...].astype(jnp.float32)                    # (bp, d)
    # VPU: broadcast diff over a (bq, bp, d) tile kept in registers/VMEM
    o_ref[...] = jnp.sum(jnp.abs(q[:, None, :] - p[None, :, :]), axis=-1)


def _pdist_linf_kernel(q_ref, p_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.max(jnp.abs(q[:, None, :] - p[None, :, :]), axis=-1)


_KERNELS = {"sql2": _pdist_l2_kernel, "l1": _pdist_l1_kernel,
            "linf": _pdist_linf_kernel}


@functools.partial(jax.jit,
                   static_argnames=("metric", "bq", "bp", "interpret"))
def pdist_pallas(q: jax.Array, p: jax.Array, metric: str = "sql2",
                 bq: int = 128, bp: int = 128,
                 interpret: bool | None = None) -> jax.Array:
    """Pairwise distances, rows of q (nq, d) × rows of p (np, d).

    ``metric='sql2'`` returns *squared* L2 (callers square radii instead of
    paying an elementwise sqrt over the nq×np tile). nq/np must be multiples
    of bq/bp — ``repro.kernels.ops`` handles padding. ``interpret=None``
    auto-selects by backend (compiled on TPU/GPU, interpreted on CPU).

    The grid is point-major (point tiles outer, query tiles inner — the
    last grid dimension iterates fastest): each candidate-point tile is
    fetched into VMEM once and reused across every query tile, instead
    of the whole point array being re-streamed per query tile.  The
    point plane dominates the operand bytes on the refinement path, so
    this is the bandwidth-friendly orientation; per-cell outputs are
    unchanged, so results are bit-identical to the query-major grid.
    """
    interpret = resolve_interpret(interpret)
    nq, d = q.shape
    npts, d2 = p.shape
    assert d == d2, (d, d2)
    assert nq % bq == 0 and npts % bp == 0, (nq, npts, bq, bp)
    # L1/Linf tiles materialize (bq, bp, d); keep them small enough for VMEM
    if metric in ("l1", "linf"):
        bq = min(bq, 32)
        assert nq % bq == 0
    return pl.pallas_call(
        _KERNELS[metric],
        grid=(npts // bp, nq // bq),
        in_specs=[
            pl.BlockSpec((bq, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bp, d), lambda j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bp), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, npts), jnp.float32),
        interpret=interpret,
    )(q, p)
