"""The default reference plan agrees with ``engine.search`` in gate mode,
on the memory tier (pipeline depth 1) and the disk tier (depth 2); its
control, in bfloat16, does not; and its reader of the index file reads
what the program's loader reads, of the sections a plan names."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import check, data, spec  # noqa: E402
from bench.reference import index_file  # noqa: E402

LABEL = spec.Bench().schema({"filter": "label"})
WALK = spec.Bench().plan({})

N, DIM, CLASSES = 1500, 32, 4
CONFIG = {"index": {"r_max": 6},
          "search": {"mode": "gate", "search_l": 24, "beam_width": 4, "result_k": 10,
                     "max_hops": 512}}


@pytest.fixture(scope="module")
def index(tmp_path_factory):
    from repro.core import EngineConfig, GateANNEngine
    rng = data.streams(2**40 + 11)
    cfg = {"n_vectors": N, "corpus": {"generator": "bigann_like", "dim": DIM, "clusters": 8},
           "labels": {"kind": "uniform", "classes": CLASSES}}
    corpus = data.corpus(cfg, rng["corpus"])
    labels = LABEL.records(cfg, N, rng["labels"], corpus)
    queries, sources = data.queries({"kind": "near_corpus", "noise": 0.05}, corpus, 24,
                                    rng["queries"])
    q_labels = LABEL.requests(cfg, {"kind": "uniform"}, 24, rng["requests"], queries,
                              sources, labels)
    path = str(tmp_path_factory.mktemp("ref") / "index.gann")
    GateANNEngine.build(corpus, **LABEL.build_args(cfg, labels), config=EngineConfig(
        degree=12, build_l=24, pq_chunks=8, r_max=6, seed=3)).save(path)
    sections = index_file.read(path, WALK.SECTIONS)
    masks = [LABEL.passes(labels, lab) for lab in q_labels]
    return path, sections, corpus, masks, queries, q_labels


@pytest.mark.parametrize("part", ["neighbors", "pq_books", "pq_codes", "medoid"])
def test_own_reader_reads_what_the_program_loads(index, part):
    from repro.store import format as fmt

    path = index[0]
    idx = fmt.read_index(path)
    want = {"neighbors": lambda: idx.neighbors(), "pq_books": lambda: idx.pq_books(),
            "pq_codes": lambda: idx.pq_codes(), "medoid": lambda: idx.header.medoid}[part]()
    np.testing.assert_array_equal(index_file.read(path, WALK.SECTIONS)[part],
                                  np.asarray(want))


def test_reader_reads_the_sections_named_and_refuses_others(index):
    path = index[0]
    assert set(index_file.read(path, ("pq_codes",))) == {"pq_codes", "medoid"}
    with pytest.raises(ValueError, match="no section 'tag_postings'"):
        index_file.read(path, ("neighbors", "tag_postings"))


@pytest.mark.parametrize("tier,depth", [("memory", 1), ("disk", 2)])
def test_reference_agrees_with_engine_search(index, tier, depth):
    import jax.numpy as jnp

    from repro.core import GateANNEngine, SearchConfig

    path, sections, corpus, masks, queries, q_labels = index
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
    want = np.stack(WALK.answers(sections, corpus, queries, masks, CONFIG, "f32"))
    np.testing.assert_array_equal(got, want)
    truth = check.brute_force(corpus, queries, masks, 10)
    numbers = check.numbers(list(got), list(want), truth, corpus, queries, masks, 10)
    assert numbers["mismatch"] == 0.0 and numbers["filter_violations"] == 0
    assert numbers["order_errors"] == 0


def test_control_in_bfloat16_fails_the_mismatch_limit(index):
    _, sections, corpus, masks, queries, _ = index
    served = WALK.answers(sections, corpus, queries, masks, CONFIG, "f32")
    truth = check.brute_force(corpus, queries, masks, 10)
    control = check.numbers(WALK.answers(sections, corpus, queries, masks, CONFIG, "bf16"),
                            served, truth, corpus, queries, masks, 10)
    bench = spec.Bench()
    limit = max(bench.config(c["name"])["limits"]["mismatch"]
                for c in bench.spec["configs"])
    assert control["mismatch"] > limit
    assert control["order_errors"] > 0
