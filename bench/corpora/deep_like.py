"""deep_like: unit-norm float32 descriptors (DEEP-like).  ``clusters``:
the number of Gaussian centres."""
import numpy as np

from bench.data import gmm


def make(n: int, dim: int, rng: np.random.Generator, clusters: int = 64) -> np.ndarray:
    x = gmm(n, dim, int(clusters), rng)
    x /= np.linalg.norm(x, axis=1, keepdims=True) + 1e-9
    return x.astype(np.float32)
