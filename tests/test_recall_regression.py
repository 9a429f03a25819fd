"""Recall regression pins — quality can't silently drift.

The oracle tests pin the search *loop* node-for-node; these pin the
end-to-end *quality* of the whole stack (Vamana build + PQ + loop) on a
seeded synthetic dataset: recall@10 per search mode must stay within
±0.01 of the values stored in ``tests/baselines/recall_at10.json``.
A legitimate quality change (better build, different PQ) regenerates
the pins explicitly:

    PYTHONPATH=src python tests/test_recall_regression.py --regen

The setup mirrors the session fixtures in conftest.py (same corpus,
labels and engine config), so tier-1 reuses the shared engine build.
The pins use their own PIN_QUERIES queries drawn from that corpus: at
10 result slots each, one result moves recall by 1/(10 * PIN_QUERIES),
well inside the tolerance (with the fixture's 16 queries one result
was the whole of it).
"""
import json
import os

import numpy as np
import pytest

from repro.core import SearchConfig, recall_at_k
from repro.data import filtered_ground_truth, make_queries

BASELINE_PATH = os.path.join(
    os.path.dirname(__file__), "baselines", "recall_at10.json"
)
MODES = ("gate", "post", "early", "pre_naive", "unfiltered")
TOLERANCE = 0.01
SEARCH_L, BEAM_W, K = 64, 8, 10
PIN_QUERIES = 128


def pin_queries(corpus):
    return make_queries(corpus, PIN_QUERIES, seed=2)


def compute_recalls(engine, corpus, labels, queries) -> dict:
    """recall@10 per mode: label==0 predicate (unfiltered: no predicate)."""
    out = {}
    for mode in MODES:
        if mode == "unfiltered":
            kind, params = None, None
            mask = np.ones(corpus.shape[0], bool)
        else:
            kind = "label"
            params = np.zeros(queries.shape[0], np.int32)
            mask = np.asarray(labels) == 0
        gt = filtered_ground_truth(corpus, queries, mask, k=K)
        res = engine.search(
            queries, filter_kind=kind, filter_params=params,
            search_config=SearchConfig(mode=mode, search_l=SEARCH_L,
                                       beam_width=BEAM_W, result_k=K),
        )
        out[mode] = round(float(recall_at_k(res.ids, gt, K)), 4)
    return out


@pytest.fixture(scope="module")
def measured(tiny_engine, tiny_corpus):
    corpus, labels, _ = tiny_corpus
    return compute_recalls(tiny_engine, corpus, labels, pin_queries(corpus))


@pytest.fixture(scope="module")
def baselines():
    assert os.path.exists(BASELINE_PATH), (
        f"missing {BASELINE_PATH} — regenerate with "
        "`PYTHONPATH=src python tests/test_recall_regression.py --regen`"
    )
    with open(BASELINE_PATH) as f:
        return json.load(f)


@pytest.mark.parametrize("mode", MODES)
def test_recall_within_pin(measured, baselines, mode):
    got = measured[mode]
    want = baselines[mode]
    assert abs(got - want) <= TOLERANCE, (
        f"{mode}: recall@10 {got:.4f} drifted from pinned {want:.4f} "
        f"(±{TOLERANCE}); if intentional, regenerate the baselines"
    )


def test_mode_quality_ordering(measured):
    """Structural sanity on the pins themselves: gate must not lose recall
    vs post at the same L (the paper's central claim), and the naive
    pre-filter must be the worst filtered mode (broken connectivity)."""
    assert measured["gate"] >= measured["post"] - TOLERANCE
    assert measured["pre_naive"] <= min(
        measured["gate"], measured["post"], measured["early"]
    ) + TOLERANCE


def _regen():
    # the same builders the session fixtures use (tests/conftest.py), so
    # regenerated pins always match what tier-1 measures
    from conftest import make_tiny_corpus, make_tiny_engine

    corpus, labels, _ = make_tiny_corpus()
    engine = make_tiny_engine(corpus, labels)
    recalls = compute_recalls(engine, corpus, labels, pin_queries(corpus))
    os.makedirs(os.path.dirname(BASELINE_PATH), exist_ok=True)
    with open(BASELINE_PATH, "w") as f:
        json.dump(recalls, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {BASELINE_PATH}: {recalls}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="recompute and overwrite the recall pins")
    args = ap.parse_args()
    if args.regen:
        _regen()
    else:
        ap.print_help()
