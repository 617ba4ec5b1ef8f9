"""Stand-in for the Corel Image Features color histograms (UCI; the
paper's Color Histogram set): ``clusters`` Dirichlet centres, per-row
scale and gamma noise, rows normalised onto the simplex.  The same
recipe as the program's ``repro.data.datasets.color_histogram_like``,
kept here so the benchmark's data cannot move with the program."""
import numpy as np


def generate(n: int, d: int, seed: int, clusters: int = 40,
             alpha: float = 0.4) -> np.ndarray:
    rng = np.random.default_rng(seed)
    centers = rng.dirichlet(np.full(d, alpha), size=clusters)
    comp = rng.integers(0, clusters, size=n)
    noise = rng.gamma(0.8, 0.02, size=(n, d))
    x = centers[comp] * rng.uniform(0.5, 1.5, size=(n, 1)) + noise
    x /= x.sum(axis=1, keepdims=True)
    return x.astype(np.float64)
