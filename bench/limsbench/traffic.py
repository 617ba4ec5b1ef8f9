"""The one traffic generator: corpus, queries, radii and arrivals from a
configuration file, a traffic file and ``--seed``.

The corpus is the deployment's data set: its generator runs from the
configuration's own ``data_seed``, so every run of a configuration
serves the same corpus and hence the same snapshot shapes and compiled
programs.  ``--seed`` draws the traffic: which corpus rows the queries
perturb, their noise, the order of query kinds and the order of the
arrival gaps.  Every seed gets the same multiset of kinds and gaps in
another order, so a seed changes which queries run, not how much work
the window holds.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import reference
from .spec import ROOT, load_module


@dataclass
class Request:
    kind: str            # "knn" | "range"
    q: np.ndarray        # (d,) f64
    arg: float | int     # k for kNN, radius for range


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose, from any non-negative seed."""
    return np.random.default_rng([int(seed), int(stream)])


def corpus(config: dict, root: str = ROOT) -> np.ndarray:
    gen = config["generator"]
    mod = load_module("generators", gen["name"], root)
    return mod.generate(int(config["n"]), int(config["d"]),
                        seed=int(config["data_seed"]), **gen.get("args", {}))


def _kind_list(traffic: dict, n: int, seed: int) -> list:
    """``n`` query classes in the traffic's fixed shares, seeded order."""
    classes = traffic["queries"]
    counts = [int(round(c["share"] * n)) for c in classes]
    counts[-1] = n - sum(counts[:-1])
    out = [i for i, c in enumerate(counts) for _ in range(c)]
    rng(seed, 1).shuffle(out)
    return out


def radii(config: dict, traffic: dict, X: np.ndarray, seed: int) -> dict:
    """Selectivity → radius for every range class: the median, over a
    seeded sample of queries, of the distance to the ``round(s·n)``-th
    nearest row (the harness's own brute force)."""
    out = {}
    n_sample = int(traffic.get("radius_sample", 16))
    for c in traffic["queries"]:
        if c["kind"] != "range":
            continue
        Q = queries(X, n_sample, float(traffic["noise"]), seed, stream=2)
        kth = max(1, int(round(float(c["selectivity"]) * len(X))))
        out[float(c["selectivity"])] = float(np.median(
            reference.kth_distances(X, Q, kth)))
    return out


def queries(X: np.ndarray, n: int, noise: float, seed: int,
            stream: int = 3) -> np.ndarray:
    """``n`` queries: corpus rows drawn by the seed plus N(0, noise)."""
    g = rng(seed, stream)
    rows = X[g.integers(0, len(X), n)]
    return rows + g.normal(0.0, noise, rows.shape)


def requests(traffic: dict, X: np.ndarray, n: int, seed: int,
             radius: dict) -> list:
    """``n`` requests of the traffic's mix, in seeded order."""
    Q = queries(X, n, float(traffic["noise"]), seed)
    out = []
    for q, i in zip(Q, _kind_list(traffic, n, seed)):
        c = traffic["queries"][i]
        if c["kind"] == "knn":
            out.append(Request("knn", q, int(c["k"])))
        else:
            out.append(Request("range", q, radius[float(c["selectivity"])]))
    return out


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due offsets (s) of an open-loop Poisson stream at ``rate`` over
    ``seconds``: the ``round(rate·seconds)`` gaps are the exponential
    distribution's evenly spaced quantiles, in seeded order — every seed
    offers the same gaps, so the same load, in another order."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    rng(seed, 4).shuffle(gaps)
    return np.cumsum(gaps)
