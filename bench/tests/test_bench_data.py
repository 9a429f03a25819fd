"""The generators of corpora, labels and traffic: fixed by the seed, and
shaped as their traffic files ask."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import data  # noqa: E402

BIG = 2**40 + 123


def test_streams_repeat_per_seed_and_differ_between_seeds():
    a, b, c = data.streams(BIG), data.streams(BIG), data.streams(BIG + 1)
    x = a["queries"].integers(0, 1 << 30, 8)
    assert np.array_equal(x, b["queries"].integers(0, 1 << 30, 8))
    assert not np.array_equal(x, c["queries"].integers(0, 1 << 30, 8))
    assert 0 <= data.build_seed(BIG) < 2**31


def test_corpora_keep_their_shape():
    rng = data.streams(BIG)
    x = data.corpus({"n_vectors": 300, "corpus": {"generator": "bigann_like",
                                                  "dim": 128, "clusters": 8}},
                    rng["corpus"])
    assert x.shape == (300, 128) and x.dtype == np.float32
    assert x.min() >= 0 and x.max() <= 255 and np.all(x == np.round(x))
    y = data.deep_like(300, 96, rng["corpus"], n_clusters=8)
    np.testing.assert_allclose(np.linalg.norm(y, axis=1), 1.0, rtol=1e-5)


def test_zipf_labels_follow_their_masses():
    p = data.class_probs({"kind": "zipf", "classes": 10, "alpha": 1.0})
    assert p[0] == pytest.approx(0.3414, abs=1e-4)
    assert p[-1] == pytest.approx(0.0341, abs=1e-4)
    lab = data.labels({"kind": "zipf", "classes": 10, "alpha": 1.0}, 50_000,
                      data.streams(BIG)["labels"])
    assert np.bincount(lab, minlength=10)[0] / lab.size == pytest.approx(p[0], abs=0.01)


@pytest.mark.parametrize("call", [
    lambda rng: data.queries({"kind": "zipf_centres", "noise": 0.0},
                             np.zeros((8, 4), np.float32), 2, rng),
    lambda rng: data.request_labels({"kind": "fixed", "label": 1}, 4, 8, rng),
    lambda rng: data.labels({"kind": "tags", "classes": 4}, 8, rng),
], ids=["query", "request_label", "record_label"])
def test_unknown_kinds_are_refused(call):
    with pytest.raises((ValueError, KeyError)):
        call(data.streams(BIG)["queries"])


def test_uniform_request_labels_cover_the_classes():
    lab = data.request_labels({"kind": "uniform"}, 10, 5000,
                              data.streams(BIG)["requests"])
    assert set(np.unique(lab)) == set(range(10))
    assert np.bincount(lab).min() > 400
