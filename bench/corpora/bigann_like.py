"""bigann_like: uint8-range clustered vectors (SIFT-like), stored as
float32.  ``clusters``: the number of Gaussian centres."""
import numpy as np

from bench.data import gmm


def make(n: int, dim: int, rng: np.random.Generator, clusters: int = 64) -> np.ndarray:
    x = gmm(n, dim, int(clusters), rng)
    x = x - x.min()
    x = x / x.max() * 255.0
    return np.round(x).astype(np.float32)
