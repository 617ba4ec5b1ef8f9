"""GaussMix corpus (arXiv:2204.10028 §6.1.1, iDistance-style): ``components``
normals with standard deviation ``std`` around uniform-random means in
[0, 1]^d, values clipped to [0, 1].  The same recipe as the program's
``repro.data.datasets.gauss_mix``, kept here so the benchmark's data
cannot move with the program."""
import numpy as np


def generate(n: int, d: int, seed: int, components: int = 150,
             std: float = 0.05) -> np.ndarray:
    rng = np.random.default_rng(seed)
    means = rng.uniform(0.0, 1.0, size=(components, d))
    comp = rng.integers(0, components, size=n)
    x = means[comp] + rng.normal(0.0, std, size=(n, d))
    return np.clip(x, 0.0, 1.0).astype(np.float64)
