"""The generators of corpora, attributes and traffic: fixed by the seed,
shaped as their files ask, and found by name."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import data, spec  # noqa: E402

BIG = 2**40 + 123
LABEL = spec.Bench().schema({"filter": "label"})
CFG = {"labels": {"kind": "uniform", "classes": 10}}


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
    y = data.corpus({"n_vectors": 300, "corpus": {"generator": "deep_like",
                                                  "dim": 96, "clusters": 8}},
                    rng["corpus"])
    np.testing.assert_allclose(np.linalg.norm(y, axis=1), 1.0, rtol=1e-5)


def test_zipf_labels_follow_their_masses():
    p = LABEL.class_probs({"kind": "zipf", "alpha": 1.0}, 10)
    assert p[0] == pytest.approx(0.3414, abs=1e-4)
    assert p[-1] == pytest.approx(0.0341, abs=1e-4)
    cfg = {"labels": {"kind": "zipf", "classes": 10, "alpha": 1.0}}
    lab = LABEL.records(cfg, 50_000, data.streams(BIG)["labels"])
    assert np.bincount(lab, minlength=10)[0] / lab.size == pytest.approx(p[0], abs=0.01)


@pytest.mark.parametrize("call", [
    lambda rng: data.queries({"kind": "zipf_centres", "noise": 0.0},
                             np.zeros((8, 4), np.float32), 2, rng),
    lambda rng: LABEL.requests(CFG, {"kind": "fixed", "label": 1}, 8, rng),
    lambda rng: LABEL.records({"labels": {"kind": "tags", "classes": 4}}, 8, rng),
], ids=["query", "request_label", "record_label"])
def test_unknown_kinds_are_refused(call):
    with pytest.raises((ValueError, KeyError)):
        call(data.streams(BIG)["queries"])


@pytest.mark.parametrize("folder,name,what", [
    ("corpora", "sift_files", "corpus generator"),
    ("filters", "tags_sparse", "filter schema"),
    ("loops", "bursty", "loop kind"),
    ("loops", "../loop", "loop kind"),
])
def test_unknown_files_are_refused_by_name(folder, name, what):
    with pytest.raises(ValueError, match=f"unknown {what}"):
        spec.plugin(spec.ROOT, folder, name, what)


def test_uniform_request_labels_cover_the_classes():
    lab = LABEL.requests(CFG, {"kind": "uniform"}, 5000, data.streams(BIG)["requests"])
    assert set(np.unique(lab)) == set(range(10))
    assert np.bincount(lab).min() > 400


def test_label_schema_passes_is_the_label():
    attrs = np.array([0, 3, 3, 1, 0], np.int32)
    assert LABEL.passes(attrs, np.int32(3)).tolist() == [False, True, True, False, False]
    assert LABEL.passes(attrs, 2).dtype == bool and not LABEL.passes(attrs, 2).any()


@pytest.mark.parametrize("schema", sorted(
    f[:-3] for f in os.listdir(os.path.join(spec.ROOT, "bench", "filters"))
    if f.endswith(".py")))
def test_schema_passes_imports_nothing_of_the_program(schema):
    with open(os.path.join(spec.ROOT, "bench", "filters", f"{schema}.py")) as f:
        src = f.read()
    assert "repro" not in src and "jax" not in src
