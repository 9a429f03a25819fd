"""The plain reference agrees with ``engine.search`` in gate mode, on the
memory tier (pipeline depth 1) and the disk tier (depth 2); its control,
in bfloat16, does not; and its reader of the index file reads what the
program's loader reads."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import check, data, spec  # noqa: E402
from bench.reference import index_file, oracle  # noqa: E402

LABEL = spec.Bench().schema({"filter": "label"})

N, DIM, CLASSES = 1500, 32, 4
SEARCH = {"mode": "gate", "search_l": 24, "beam_width": 4, "result_k": 10,
          "max_hops": 512}


@pytest.fixture(scope="module")
def index(tmp_path_factory):
    from repro.core import EngineConfig, GateANNEngine
    rng = data.streams(2**40 + 11)
    cfg = {"n_vectors": N, "corpus": {"generator": "bigann_like", "dim": DIM, "clusters": 8},
           "labels": {"kind": "uniform", "classes": CLASSES}}
    corpus = data.corpus(cfg, rng["corpus"])
    labels = LABEL.records(cfg, N, rng["labels"])
    queries = data.queries({"kind": "near_corpus", "noise": 0.05}, corpus, 24,
                           rng["queries"])
    q_labels = LABEL.requests(cfg, {"kind": "uniform"}, 24, rng["requests"])
    path = str(tmp_path_factory.mktemp("ref") / "index.gann")
    GateANNEngine.build(corpus, **LABEL.build_args(cfg, labels), config=EngineConfig(
        degree=12, build_l=24, pq_chunks=8, r_max=6, seed=3)).save(path)
    ref = check.reference_index(path, corpus, r_max=6)
    return path, ref, labels, queries, q_labels


@pytest.mark.parametrize("part", ["neighbors", "pq_books", "pq_codes", "medoid"])
def test_own_reader_reads_what_the_program_loads(index, part):
    from repro.store import format as fmt

    path = index[0]
    idx = fmt.read_index(path)
    want = {"neighbors": lambda: idx.neighbors(), "pq_books": lambda: idx.pq_books(),
            "pq_codes": lambda: idx.pq_codes(), "medoid": lambda: idx.header.medoid}[part]()
    np.testing.assert_array_equal(index_file.read(path)[part], np.asarray(want))


@pytest.mark.parametrize("tier,depth", [("memory", 1), ("disk", 2)])
def test_reference_agrees_with_engine_search(index, tier, depth):
    import jax.numpy as jnp

    from repro.core import GateANNEngine, SearchConfig

    path, ref, labels, queries, q_labels = index
    eng = GateANNEngine.load(path, store_tier=tier)
    try:
        out = eng.search(queries, filter_kind="label",
                         filter_params=jnp.asarray(q_labels, jnp.int32),
                         search_config=SearchConfig(
                             mode="gate", search_l=24, beam_width=4, result_k=10,
                             pipeline_depth=depth))
        got = np.asarray(out.ids)
    finally:
        if eng.measured_store() is not None:
            eng.measured_store().close()
    want = np.stack([oracle.search(ref, q, LABEL.passes(labels, lab), mode="gate",
                                   L=24, W=4, K=10)[0]
                     for q, lab in zip(queries, q_labels)])
    np.testing.assert_array_equal(got, want)
    truth = check.brute_force(ref.vectors, labels, queries, q_labels, 10, LABEL.passes)
    numbers = check.numbers(list(got), list(want), truth, ref.vectors, labels,
                            queries, q_labels, LABEL.passes, 10)
    assert numbers["mismatch"] == 0.0 and numbers["filter_violations"] == 0
    assert numbers["order_errors"] == 0


def test_control_in_bfloat16_fails_the_mismatch_limit(index):
    _, ref, labels, queries, q_labels = index
    passes = LABEL.passes
    served = check.answers(ref, queries, labels, q_labels, passes, SEARCH)
    truth = check.brute_force(ref.vectors, labels, queries, q_labels, 10, passes)
    control = check.numbers(check.answers(ref, queries, labels, q_labels, passes, SEARCH,
                                          precision="bf16"),
                            served, truth, ref.vectors, labels, queries, q_labels, passes, 10)
    bench = spec.Bench()
    limit = max(bench.config(c["name"])["limits"]["mismatch"]
                for c in bench.spec["configs"])
    assert control["mismatch"] > limit
    assert control["order_errors"] > 0
