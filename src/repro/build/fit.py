"""Batched rank-model fitting: one launch for every model in the index.

The host build fits K·m distance→rank models plus K LIMS-value→position
models one ``chebfit`` at a time.  Here all G = K·m + K groups solve in
a single jitted launch: a Chebyshev-Vandermonde basis over the padded
(G, n_max) column matrix, normal equations per group, and one batched
``linalg.solve`` on the (G, C, C) stack.

Numerical notes (f32 on device):

* the basis is Chebyshev on x normalized to [-1, 1] — the same model
  class as the host's ``PolyRankModel.fit`` (degree-g polynomials),
  same normalization, so device coefficients drop straight into
  ``PolyRankModel`` records;
* normal equations square the basis condition number, so each group
  gets a scale-aware Tikhonov jitter, the per-group degree is capped
  exactly like the hardened host fit (``min(degree, max(1, n//8),
  n_distinct - 1)``), and any group whose solve still goes non-finite
  falls back to the exact linear ramp rank ≈ (n-1)(t+1)/2;
* model quality never affects exactness (DESIGN.md §3/§6) — a worse
  fit only widens the certified error bound E.

The same pass certifies a device-side rank-error estimate per group
(max deviation at the data points + the Chebyshev derivative bound for
the gaps, §3's recipe).  Snapshots built from the materialized index
re-certify E against the exact f64 columns through the deployed
``rankeval`` kernel; the device estimate is for diagnostics and for
callers staying entirely on device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_E_SLACK = 2.0      # rint half-steps + f32 eval slop (mirrors snapshot)
# full-f32 contractions: the solve and the certified error estimate
# assume f32 products, not the TPU's default bf16 operand rounding
_HIGHEST = jax.lax.Precision.HIGHEST


def cheb_basis(t: jax.Array, degree: int) -> jax.Array:
    """(..., n) → (..., n, degree+1) Chebyshev-Vandermonde basis via the
    T_k recurrence (numerically benign on [-1, 1])."""
    cols = [jnp.ones_like(t), t]
    for _ in range(2, degree + 1):
        cols.append(2.0 * t * cols[-1] - cols[-2])
    return jnp.stack(cols[:degree + 1], axis=-1)


@functools.partial(jax.jit, static_argnames=("max_degree",))
def _fit_kernel(cols: jax.Array, counts: jax.Array, deg_req: jax.Array,
                max_degree: int):
    """The one-launch fit. ``cols`` (G, n_max) ascending per group with
    arbitrary padding past ``counts[g]``; ``deg_req`` (G,) per-group
    requested degree (rank vs position models differ)."""
    G, n_max = cols.shape
    C = max_degree + 1
    idx = jnp.arange(n_max)
    n = counts.astype(jnp.float32)                            # (G,)
    w = (idx[None, :] < counts[:, None]).astype(jnp.float32)  # (G, n_max)

    lo = cols[:, 0]
    last = jnp.clip(counts - 1, 0, n_max - 1)
    hi = jnp.take_along_axis(cols, last[:, None], axis=1)[:, 0]
    span = hi - lo
    degenerate = (span <= 0) | (counts <= 1)
    span_safe = jnp.where(span > 0, span, 1.0)
    t = jnp.clip((cols - lo[:, None]) / span_safe[:, None] * 2.0 - 1.0,
                 -1.0, 1.0)

    # ties-low ranks within each sorted column: the last index that
    # started a new value, propagated by a running max
    prev = jnp.concatenate(
        [jnp.full((G, 1), -jnp.inf, cols.dtype), cols[:, :-1]], axis=1)
    newv = cols != prev
    ranks = jax.lax.cummax(
        jnp.where(newv, idx[None, :], 0), axis=1).astype(jnp.float32)
    n_distinct = jnp.sum(newv.astype(jnp.int32) * (w > 0), axis=1)

    # hardened per-group degree: over-determined and tie-aware
    dg = jnp.minimum(jnp.minimum(deg_req, jnp.maximum(1, counts // 8)),
                     jnp.maximum(1, n_distinct - 1))
    c_idx = jnp.arange(C)
    cmask = (c_idx[None, :] <= dg[:, None]).astype(jnp.float32)   # (G, C)

    T = cheb_basis(t, max_degree)                                 # (G,n,C)
    Tw = T * w[:, :, None] * cmask[:, None, :]
    A = jnp.einsum("gnc,gnd->gcd", Tw, Tw, precision=_HIGHEST)
    b = jnp.einsum("gnc,gn->gc", Tw, ranks, precision=_HIGHEST)
    # identity rows pin masked coefficients to 0; live rows get a
    # scale-aware jitter (diag(A) ≈ n/2 per Chebyshev coefficient)
    jitter = 1e-6 * jnp.maximum(n, 1.0)
    diag = jnp.where(cmask > 0, jitter[:, None], 1.0)
    A = A + jnp.eye(C)[None] * diag[:, None, :]
    coef = jnp.linalg.solve(A, b[..., None])[..., 0] * cmask

    # exact linear-ramp fallback for any solve that went non-finite
    r_last = jnp.take_along_axis(ranks, last[:, None], axis=1)[:, 0]
    ramp = jnp.zeros((G, C), coef.dtype)
    ramp = ramp.at[:, 0].set(r_last / 2.0)
    if C > 1:
        ramp = ramp.at[:, 1].set(r_last / 2.0)
    bad = ~jnp.all(jnp.isfinite(coef), axis=1)
    coef = jnp.where(bad[:, None], ramp, coef)
    coef = jnp.where(degenerate[:, None], 0.0, coef)
    hi_out = jnp.where(span > 0, hi, lo + 1.0)
    lo_out = jnp.where(counts > 0, lo, 0.0)
    hi_out = jnp.where(counts > 0, hi_out, 1.0)

    # device-side certified error estimate (§3): deployed-polynomial
    # deviation at the data points + derivative bound × largest t-gap
    pred = jnp.clip(jnp.rint(jnp.einsum("gnc,gc->gn", T, coef,
                                        precision=_HIGHEST)),
                    0.0, jnp.maximum(n - 1.0, 0.0)[:, None])
    err_pt = jnp.max(jnp.abs(pred - ranks) * w, axis=1)
    deriv = jnp.sum((c_idx.astype(jnp.float32) ** 2)[None, :]
                    * jnp.abs(coef), axis=1)
    t_next = jnp.concatenate([t[:, 1:], t[:, -1:]], axis=1)
    pair_ok = (idx[None, :] + 1 < counts[:, None]).astype(jnp.float32)
    gap = jnp.max((t_next - t) * pair_ok, axis=1)
    err = jnp.minimum(err_pt + deriv * gap + _E_SLACK, n)
    err = jnp.where(counts > 0, err, 0.0)
    return coef, lo_out, hi_out, n, dg, err


def batched_chebfit(cols, counts, deg_req, max_degree: int):
    """Fit every group's rank model in one launch.

    ``cols`` (G, n_max) ascending (any padding), ``counts`` (G,) valid
    lengths, ``deg_req`` (G,) requested degree per group.  Returns
    ``(coef (G, max_degree+1), lo, hi, n, dg, err)`` — ``dg`` the
    per-group effective degree actually fit, ``err`` the device-side
    certified rank-error estimate.
    """
    return _fit_kernel(jnp.asarray(cols, jnp.float32),
                       jnp.asarray(counts, jnp.int32),
                       jnp.asarray(deg_req, jnp.int32), int(max_degree))


__all__ = ["batched_chebfit", "cheb_basis"]
