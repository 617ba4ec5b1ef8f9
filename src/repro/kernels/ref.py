"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function computes the same math as its kernel with no tiling — tests
sweep shapes/dtypes and assert allclose between kernel (interpret mode on
CPU, compiled on TPU) and these references.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def pdist_ref(q: jax.Array, p: jax.Array, metric: str = "sql2") -> jax.Array:
    q = q.astype(jnp.float32)
    p = p.astype(jnp.float32)
    if metric == "sql2":
        d = q[:, None, :] - p[None, :, :]
        return jnp.sum(d * d, axis=-1)
    if metric == "l1":
        return jnp.sum(jnp.abs(q[:, None, :] - p[None, :, :]), axis=-1)
    if metric == "linf":
        return jnp.max(jnp.abs(q[:, None, :] - p[None, :, :]), axis=-1)
    raise ValueError(metric)


def rankeval_ref(x: jax.Array, coef: jax.Array, lo: jax.Array,
                 hi: jax.Array, n: jax.Array, n_rings: int = 20):
    """(rank, rid) — vectorized Chebyshev eval + ring id, float32 math."""
    x = x.astype(jnp.float32)
    lo = lo.astype(jnp.float32)[:, None]
    hi = hi.astype(jnp.float32)[:, None]
    nn = n.astype(jnp.float32)[:, None]
    t = jnp.clip((x - lo) / jnp.maximum(hi - lo, 1e-30) * 2.0 - 1.0,
                 -1.0, 1.0)
    g, c = coef.shape
    # T_k recurrence accumulated explicitly
    acc = jnp.zeros_like(t)
    t_km1 = jnp.ones_like(t)
    t_k = t
    for k in range(c):
        term = coef[:, k].astype(jnp.float32)[:, None]
        if k == 0:
            acc = acc + term * t_km1
        elif k == 1:
            acc = acc + term * t_k
        else:
            t_kp1 = 2.0 * t * t_k - t_km1
            t_km1, t_k = t_k, t_kp1
            acc = acc + term * t_k
    rank = jnp.clip(jnp.rint(acc), 0.0, jnp.maximum(nn - 1.0, 0.0))
    width = jnp.maximum(jnp.ceil(nn / float(n_rings)), 1.0)
    rid = jnp.clip(jnp.floor(rank / width), 0.0, float(n_rings - 1))
    return rank.astype(jnp.int32), rid.astype(jnp.int32)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True) -> jax.Array:
    """Dense softmax attention with GQA head mapping; fp32 math."""
    b, hq, sq, d = q.shape
    _, hk, sk, _ = k.shape
    group = hq // hk
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) / (d ** 0.5)
    if causal:
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vf).astype(q.dtype)
