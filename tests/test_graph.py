"""Vamana build + beam search correctness."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import (
    beam_search_batch,
    build_filtered_vamana,
    build_vamana,
    find_medoid,
    robust_prune_batch,
)
from repro.data import make_bigann_like, uniform_labels


@pytest.fixture(scope="module")
def small_graph():
    corpus = make_bigann_like(600, 16, seed=3)
    g = build_vamana(corpus, degree=16, build_l=32, batch_size=128, seed=0)
    return corpus, g


def test_graph_shape_and_padding(small_graph):
    corpus, g = small_graph
    n = corpus.shape[0]
    nbrs = np.asarray(g.neighbors)
    assert nbrs.shape == (n, 16)
    assert (nbrs < n).all()
    # no self loops among valid entries
    rows = np.arange(n)[:, None]
    valid = nbrs >= 0
    assert not (nbrs[valid] == np.broadcast_to(rows, nbrs.shape)[valid]).any()


def test_rows_longest_edge_first(small_graph):
    """A row's prefix is the neighbor store: its longest edges come first,
    -1 padding last."""
    corpus, g = small_graph
    nbrs = np.asarray(g.neighbors)
    valid = nbrs >= 0
    d = ((corpus[np.maximum(nbrs, 0)] - corpus[:, None, :]) ** 2).sum(-1)
    d = np.where(valid, d, -1.0)
    assert (np.diff(d, axis=1) <= 0).all()
    assert (np.diff(valid.astype(np.int8), axis=1) <= 0).all()


def test_medoid_is_most_central(small_graph):
    corpus, g = small_graph
    med = int(g.medoid)
    cen = corpus.mean(0)
    d = ((corpus - cen) ** 2).sum(1)
    assert d[med] == pytest.approx(d.min())


def test_beam_search_exact_recall(small_graph):
    corpus, g = small_graph
    queries = jnp.asarray(corpus[:8])  # corpus points: NN = themselves
    res = beam_search_batch(
        g.neighbors, jnp.asarray(corpus), g.medoid, queries,
        search_l=32, beam_width=4,
    )
    top1 = np.asarray(res.ids)[:, 0]
    assert (top1 == np.arange(8)).mean() >= 0.9


def test_robust_prune_degree_and_dedup():
    corpus = jnp.asarray(make_bigann_like(100, 8, seed=1))
    cands = jnp.asarray(
        np.random.default_rng(0).integers(0, 100, size=(4, 30)), jnp.int32
    )
    out = np.asarray(robust_prune_batch(
        jnp.asarray([0, 1, 2, 3], jnp.int32), cands, corpus, alpha=1.2, degree=8
    ))
    assert out.shape == (4, 8)
    for row, p in zip(out, range(4)):
        vals = row[row >= 0]
        assert len(set(vals.tolist())) == len(vals)  # no dup edges
        assert p not in vals  # no self edge


def test_filtered_vamana_has_label_medoids():
    corpus = make_bigann_like(400, 8, seed=2)
    labels = uniform_labels(400, 4, seed=0)
    fg = build_filtered_vamana(corpus, labels, degree=12, build_l=24, batch_size=128)
    meds = np.asarray(fg.label_medoids)
    assert meds.shape == (4,)
    for lab in range(4):
        assert labels[meds[lab]] == lab
