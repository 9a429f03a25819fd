"""Corpora, labels and query streams of the benchmark, made from the run's seed.

The generators are copies of those in ``src/repro/data`` (``_gmm``,
``make_bigann_like``, ``make_deep_like``, ``uniform_labels``,
``zipf_labels``, ``make_queries``), taking a ``numpy.random.Generator``
instead of an int seed.  They live here so
that a change to the program cannot move the yardstick.

Every random draw comes from one named stream of a seed (``streams``),
so a new stream can be added without moving the others.  The corpus,
its labels and the index build come from the configuration's
``data_seed``: a deployment's data is fixed, and seeds that each drew a
corpus of their own were seen to change the work of a run by up to 30%.
The requests (query vectors and their labels) and the sample that is
checked come from the run's ``--seed``.  Seeds
may be any non-negative integer, larger than 32 bits included.
"""
from __future__ import annotations

import numpy as np

STREAMS = ("corpus", "labels", "queries", "requests", "sample", "build")


def streams(seed: int) -> dict[str, np.random.Generator]:
    """One independent generator per named stream of ``seed``."""
    children = np.random.SeedSequence(int(seed)).spawn(len(STREAMS))
    return {name: np.random.default_rng(ss)
            for name, ss in zip(STREAMS, children)}


def build_seed(seed: int) -> int:
    """The index build's seed: 31 bits drawn from the run's seed (the
    program's PRNG keys take 32-bit seeds)."""
    return int(streams(seed)["build"].integers(0, 2**31 - 1))


# -- corpora -----------------------------------------------------------------

def _gmm(n: int, dim: int, n_clusters: int, rng: np.random.Generator,
         spread: float = 0.35) -> np.ndarray:
    centers = rng.normal(0.0, 1.0, size=(n_clusters, dim))
    assign = rng.integers(0, n_clusters, size=n)
    x = centers[assign] + rng.normal(0.0, spread, size=(n, dim))
    return x.astype(np.float32)


def bigann_like(n: int, dim: int, rng: np.random.Generator,
                n_clusters: int = 64) -> np.ndarray:
    """uint8-range clustered vectors (SIFT-like), stored as float32."""
    x = _gmm(n, dim, n_clusters, rng)
    x = x - x.min()
    x = x / x.max() * 255.0
    return np.round(x).astype(np.float32)


def deep_like(n: int, dim: int, rng: np.random.Generator,
              n_clusters: int = 64) -> np.ndarray:
    """Unit-norm float32 descriptors (DEEP-like)."""
    x = _gmm(n, dim, n_clusters, rng)
    x /= np.linalg.norm(x, axis=1, keepdims=True) + 1e-9
    return x.astype(np.float32)


CORPORA = {"bigann_like": bigann_like, "deep_like": deep_like}


def corpus(config: dict, rng: np.random.Generator) -> np.ndarray:
    """The configuration's ``n_vectors`` records, by its ``corpus`` group."""
    spec = config["corpus"]
    return CORPORA[spec["generator"]](
        int(config["n_vectors"]), int(spec["dim"]), rng,
        n_clusters=int(spec["clusters"]))


# -- labels ------------------------------------------------------------------

def class_probs(spec: dict) -> np.ndarray:
    """Probability of each label class.  ``spec``: {"kind": "uniform" |
    "zipf", "classes": C, "alpha": a}; Zipf gives class c mass
    1/(c+1)^alpha (alpha 1.0 over 10 classes: top 34%, rarest 3.4%)."""
    c = int(spec["classes"])
    if spec["kind"] == "uniform":
        return np.full(c, 1.0 / c)
    if spec["kind"] == "zipf":
        w = 1.0 / np.arange(1, c + 1) ** float(spec["alpha"])
        return w / w.sum()
    raise ValueError(f"unknown label kind {spec['kind']!r}")


def labels(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """One label per record, drawn from ``class_probs(spec)``."""
    if spec["kind"] == "uniform":
        return rng.integers(0, int(spec["classes"]), size=n).astype(np.int32)
    return rng.choice(int(spec["classes"]), size=n,
                      p=class_probs(spec)).astype(np.int32)


# -- requests ----------------------------------------------------------------

def queries(spec: dict, corpus_: np.ndarray, n: int,
            rng: np.random.Generator) -> np.ndarray:
    """``n`` query vectors.  ``spec``: {"kind": "near_corpus", "noise"}
    draws distinct corpus points plus Gaussian noise of ``noise`` times
    the corpus's mean absolute value."""
    if spec["kind"] != "near_corpus":
        raise ValueError(f"unknown query kind {spec['kind']!r}")
    scale = np.abs(corpus_).mean() * float(spec["noise"])
    picks = rng.choice(corpus_.shape[0], size=n, replace=False)
    q = corpus_[picks] + rng.normal(0.0, scale, size=(n, corpus_.shape[1]))
    return q.astype(np.float32)


def request_labels(spec: dict, n_classes: int, n: int,
                   rng: np.random.Generator) -> np.ndarray:
    """The label each request filters on.  ``spec``: {"kind": "uniform"}
    over the configuration's classes."""
    if spec["kind"] != "uniform":
        raise ValueError(f"unknown request-label kind {spec['kind']!r}")
    return rng.integers(0, n_classes, size=n).astype(np.int32)
