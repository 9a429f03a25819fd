"""label: one label per record; a request asks for the records that carry
one label (GateANN section 5.1).

A schema file gives, for its configurations (those whose ``filter`` names
it), every function below; ``passes`` alone is the reference's, and
imports nothing of the program.

Record labels follow the configuration's ``labels`` group:
{"kind": "uniform" | "zipf", "classes": C, "alpha": a}.  A request's
label follows the traffic's ``labels`` group: {"kind": "uniform"}.  The
frontend holds one tenant per class, ``label<c>``, bound to the program's
``label`` filter.
"""
import numpy as np


def class_probs(spec: dict, classes: int) -> np.ndarray:
    """Probability of each of ``classes`` classes: uniform, or Zipf with
    ``spec["alpha"]`` (alpha 1.0 over 10 classes: top 34%, rarest 3.4%)."""
    if spec["kind"] == "uniform":
        return np.full(classes, 1.0 / classes)
    if spec["kind"] == "zipf":
        w = 1.0 / np.arange(1, classes + 1) ** float(spec["alpha"])
        return w / w.sum()
    raise ValueError(f"unknown label kind {spec['kind']!r}")


def records(config: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """(n,) int32: each record's label, from the ``labels`` stream."""
    spec = config["labels"]
    c = int(spec["classes"])
    if spec["kind"] == "uniform":
        return rng.integers(0, c, size=n).astype(np.int32)
    return rng.choice(c, size=n, p=class_probs(spec, c)).astype(np.int32)


def build_args(config: dict, attrs: np.ndarray) -> dict:
    """Keyword arguments of ``GateANNEngine.build`` that carry the attributes."""
    return {"labels": attrs}


def key_groups(config: dict) -> dict:
    """What the index's bytes depend on beyond the cache key's own fields
    (which hold the ``labels`` group already): nothing."""
    return {}


def requests(config: dict, spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """(n,) int32: the label each request asks for, from the ``requests``
    stream; ``spec`` is the traffic's ``labels`` group."""
    c = int(config["labels"]["classes"])
    if spec["kind"] == "uniform":
        return rng.integers(0, c, size=n).astype(np.int32)
    raise ValueError(f"unknown request-label kind {spec['kind']!r}")


def tenants(config: dict) -> list[tuple]:
    """The frontend's tenants: (name, filter_kind, filter_params)."""
    return [(f"label{c}", "label", np.int32(c))
            for c in range(int(config["labels"]["classes"]))]


def route(config: dict, predicate) -> tuple[str, dict]:
    """How one request is sent: its tenant and further ``submit`` keywords."""
    return f"label{int(predicate)}", {}


def passes(attrs: np.ndarray, predicate) -> np.ndarray:
    """(N,) bool: the records that answer a request for ``predicate``."""
    return attrs == int(predicate)
