"""The plain reference: brute force over every corpus row, in f64.

Copied from the smoke run's ``Reference`` so that it imports nothing of
the program: the direct (difference) formulation of the L2 distance,
``sqrt(sum_d (x_d - q_d)^2)`` as one ``einsum`` per row, which is the
arithmetic the served path's exact refinement uses, so a correct answer
matches it to the bit.  kNN is the first k of a stable sort of the
distances (ties break by row id); range is every row with d <= r.
``dtype`` lowers the precision of the whole computation: float32 is the
control, the reference one step below the f64 that the configurations
state.  Rows stream in blocks so a 1M-row scan stays in cache-sized
pieces; a row's distance does not depend on its block.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_BLOCK = 1 << 16


def distances(X: np.ndarray, q: np.ndarray, dtype=np.float64) -> np.ndarray:
    """(n,) distances from ``q`` to every row of ``X``."""
    q = np.asarray(q, dtype)
    out = np.empty(len(X), dtype)
    for i in range(0, len(X), _BLOCK):
        diff = np.asarray(X[i:i + _BLOCK], dtype) - q
        out[i:i + _BLOCK] = np.sqrt(np.einsum("nd,nd->n", diff, diff))
    return out


def _map(fn, items, threads: int):
    with ThreadPoolExecutor(max(1, threads)) as pool:
        return list(pool.map(fn, items))


def kth_distances(X: np.ndarray, Q: np.ndarray, k: int,
                  threads: int = 8) -> np.ndarray:
    """Each query's distance to its k-th nearest row."""
    return np.array(_map(
        lambda q: np.partition(distances(X, q), k - 1)[k - 1], Q, threads))


def answer(X: np.ndarray, kind: str, q: np.ndarray, arg,
           dtype=np.float64) -> tuple[np.ndarray, np.ndarray]:
    """(ids, distances) of one query, ordered by (distance, id)."""
    d = distances(X, q, dtype)
    if kind == "knn":
        k = min(int(arg), len(d))
        near = np.argpartition(d, k - 1)[:k] if k < len(d) else \
            np.arange(len(d))
        reach = d[near].max()
        ids = np.nonzero(d <= reach)[0]
        ids = ids[np.lexsort((ids, d[ids]))][:k]
    else:
        ids = np.nonzero(d <= dtype(arg))[0]
        ids = ids[np.lexsort((ids, d[ids]))]
    return ids.astype(np.int64), d[ids].astype(np.float64)


def answers(X: np.ndarray, reqs: list, dtype=np.float64,
            threads: int = 8) -> list:
    """:func:`answer` for every request, on ``threads`` threads."""
    return _map(lambda r: answer(X, r.kind, r.q, r.arg, dtype), reqs,
                threads)
