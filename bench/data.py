"""Corpora and query streams of the benchmark, made from the run's seed.

A configuration's corpus comes from the generator its ``corpus`` group
names, ``bench/corpora/<generator>.py``; its records' attributes and
each request's predicate come from its filter schema,
``bench/filters/<filter>.py``.  The generators are copies of those in
``src/repro/data`` (``_gmm``, ``make_bigann_like``, ``make_deep_like``,
``uniform_labels``, ``zipf_labels``, ``make_queries``), taking a
``numpy.random.Generator`` instead of an int seed.  They live here so
that a change to the program cannot move the yardstick.

Every random draw comes from one named stream of a seed (``streams``),
so a new stream can be added at the end without moving the others.  The
corpus, its attributes and the index build come from the configuration's
``data_seed``: a deployment's data is fixed, and seeds that each drew a
corpus of their own were seen to change the work of a run by up to 30%.
The requests (query vectors and their predicates), the arrival times of
an open loop and the sample that is checked come from the run's
``--seed``.  Seeds may be any non-negative integer, larger than 32 bits
included.
"""
from __future__ import annotations

import numpy as np

from bench import spec as specm

STREAMS = ("corpus", "labels", "queries", "requests", "sample", "build", "arrivals")


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

def gmm(n: int, dim: int, n_clusters: int, rng: np.random.Generator,
        spread: float = 0.35) -> np.ndarray:
    """``n`` points around ``n_clusters`` unit-normal centres: the shared
    body of the clustered generators."""
    centers = rng.normal(0.0, 1.0, size=(n_clusters, dim))
    assign = rng.integers(0, n_clusters, size=n)
    x = centers[assign] + rng.normal(0.0, spread, size=(n, dim))
    return x.astype(np.float32)


def corpus(config: dict, rng: np.random.Generator, root: str = specm.ROOT) -> np.ndarray:
    """The configuration's ``n_vectors`` records: ``make(n, dim, rng,
    **params)`` of the generator its ``corpus`` group names, with the
    group's other keys as ``params``."""
    spec = dict(config["corpus"])
    make = specm.plugin(root, "corpora", spec.pop("generator"), "corpus generator").make
    return make(int(config["n_vectors"]), int(spec.pop("dim")), rng, **spec)


# -- requests ----------------------------------------------------------------

def queries(spec: dict, corpus_: np.ndarray, n: int,
            rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """``n`` query vectors and the corpus row each was drawn near.
    ``spec``: {"kind": "near_corpus", "noise"} draws distinct corpus
    points plus Gaussian noise of ``noise`` times the corpus's mean
    absolute value."""
    if spec["kind"] != "near_corpus":
        raise ValueError(f"unknown query kind {spec['kind']!r}")
    scale = np.abs(corpus_).mean() * float(spec["noise"])
    picks = rng.choice(corpus_.shape[0], size=n, replace=False)
    q = corpus_[picks] + rng.normal(0.0, scale, size=(n, corpus_.shape[1]))
    return q.astype(np.float32), picks
