"""The two configurations make what they made before their corpus
generators, filter schema and loops became files of their own: digests
of the corpus, the records' labels, two seeds' request pools, the
tenants, the index cache key, and the reference's answers on a small
index made here in NumPy, all taken before the move."""
import hashlib
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import check, data, index, run, spec  # noqa: E402
from bench.reference import oracle  # noqa: E402

SEEDS = (2**33 + 5, 3141592653)
PINNED = {
    "bigann-ssd.closed64": {
        "corpus": "031811893584f3f9",
        "attrs": "0870e54a3760eeb2",
        "vecs8589934597": "cd4fff5b8cd008d4",
        "preds8589934597": "d562e4b9be7433be",
        "vecs3141592653": "ab48546c291964ac",
        "preds3141592653": "79e6c23c08ad89d2",
        "key": "d43cb5dfa9f9f07fc40fb939",
    },
    "deep-hbm.closed64": {
        "corpus": "5af79b34ebea27d6",
        "attrs": "7a8c8882bf86b8a8",
        "vecs8589934597": "1d92fc0edb090cd5",
        "preds8589934597": "d562e4b9be7433be",
        "vecs3141592653": "c7baf1eb06471b7a",
        "preds3141592653": "79e6c23c08ad89d2",
        "key": "cd01ba2cd92abb1781a1a60b",
    },
}


def _digest(a) -> str:
    a = np.ascontiguousarray(a)
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def made():
    bench = spec.Bench()
    out = {}
    for name in PINNED:
        cell = bench.cell(name)
        cfg = cell.config
        schema = bench.schema(cfg)
        rng = data.streams(cfg["data_seed"])
        corpus = data.corpus(cfg, rng["corpus"])
        got = {"corpus": corpus,
               "attrs": schema.records(cfg, corpus.shape[0], rng["labels"]),
               "key": index.cache_key(cfg, "0123456789abcdef", "None", "0.6.0", "tpu",
                                      schema.key_groups(cfg))}
        for seed in SEEDS:
            got[f"vecs{seed}"], got[f"preds{seed}"] = run.requests(cell, corpus, seed, schema)
        out[name] = (cfg, schema, got)
    return out


@pytest.mark.parametrize("item", list(PINNED["bigann-ssd.closed64"]))
@pytest.mark.parametrize("cell", list(PINNED))
def test_configuration_makes_what_it_made(made, cell, item):
    _, _, got = made[cell]
    value = got[item] if item == "key" else _digest(got[item])
    assert value == PINNED[cell][item]


@pytest.mark.parametrize("cell", list(PINNED))
def test_tenants_and_routes_are_the_labels(made, cell):
    cfg, schema, got = made[cell]
    tenants = schema.tenants(cfg)
    assert [(n, k, int(p)) for n, k, p in tenants] == [
        (f"label{c}", "label", c) for c in range(10)]
    assert all(np.asarray(p).dtype == np.int32 for _, _, p in tenants)
    preds = got[f"preds{SEEDS[0]}"]
    for p in preds[:50]:
        assert schema.route(cfg, p) == (f"label{int(p)}", {})
    assert schema.build_args(cfg, got["attrs"])["labels"] is got["attrs"]


def _tiny_index():
    """A small index of random records, codebooks and graph, and four
    queries, one per label."""
    rng = np.random.default_rng(20261019)
    n, d, c, kc = 600, 16, 4, 16
    vectors = rng.normal(size=(n, d)).astype(np.float32)
    labels = rng.integers(0, 4, size=n).astype(np.int32)
    books = rng.normal(size=(c, kc, d // c)).astype(np.float32)
    codes = rng.integers(0, kc, size=(n, c)).astype(np.int64)
    nbrs = rng.integers(0, n, size=(n, 8)).astype(np.int64)
    nbrs[rng.random((n, 8)) < 0.1] = -1
    queries = rng.normal(size=(4, d)).astype(np.float32)
    ix = oracle.Index(vectors=vectors, books=books, codes=codes, neighbors=nbrs,
                      entry=0, r_max=4)
    return ix, labels, queries, np.arange(4)


ANSWERS = {
    "f32": [[372, 367, 140, 320, 242], [598, 149, 349, 472, 569],
            [332, 530, 63, 271, 584], [224, 251, 241, 0, 215]],
    "bf16": [[372, 367, 140, 320, 242], [598, 149, 349, 472, 569],
             [332, 530, 63, 271, 584], [224, 251, 241, 0, 215]],
    "brute": [[435, 593, 473, 113, 333], [79, 433, 117, 274, 468],
              [552, 560, 285, 337, 145], [302, 295, 224, 553, 283]],
}


@pytest.mark.parametrize("which", list(ANSWERS))
def test_reference_answers_are_pinned(which):
    schema = spec.Bench().schema({"filter": "label"})
    ix, labels, queries, preds = _tiny_index()
    if which == "brute":
        got = check.brute_force(ix.vectors, labels, queries, preds, 5, schema.passes)
    else:
        got = [oracle.search(ix, q, schema.passes(labels, p), mode="gate", L=16, W=4,
                             K=5, precision=which)[0] for q, p in zip(queries, preds)]
    assert np.asarray(got).tolist() == ANSWERS[which]
