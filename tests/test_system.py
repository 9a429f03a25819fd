"""End-to-end behaviour tests for the GateANN system (engine-level)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SearchConfig, recall_at_k
from repro.core.io_model import DEFAULT_COST_MODEL


def test_engine_memory_report(tiny_engine):
    rep = tiny_engine.memory_report()
    n = rep["n"]
    assert rep["pq_bytes"] == n * 8  # 8 chunks
    assert rep["neighbor_store_bytes"] == n * (1 + 10) * 4  # Eq. (1)
    assert rep["filter_store_bytes"]["label"] == n
    assert rep["record_tier_bytes"] >= n * 4096  # 4 KB-aligned records


def test_neighbor_store_is_prefix_of_graph(tiny_engine):
    full = np.asarray(tiny_engine.record_store.neighbors)
    mem = np.asarray(tiny_engine.neighbor_store.neighbors)
    np.testing.assert_array_equal(mem, full[:, : mem.shape[1]])


def test_modeled_throughput_ordering(tiny_engine, tiny_corpus):
    """gate's modeled QPS must beat post's at the same recall operating
    point — the paper's headline (7.6x at s=10%)."""
    _, _, queries = tiny_corpus
    tgt = np.zeros(queries.shape[0], np.int32)
    out_g = tiny_engine.search(queries, filter_kind="label", filter_params=tgt,
                               search_config=SearchConfig(mode="gate", search_l=96))
    out_p = tiny_engine.search(queries, filter_kind="label", filter_params=tgt,
                               search_config=SearchConfig(mode="post", search_l=96))
    q_g = tiny_engine.modeled_qps(out_g.stats)
    q_p = tiny_engine.modeled_qps(out_p.stats)
    assert q_g > 2.0 * q_p, (q_g, q_p)


def test_rmax_is_runtime_knob(tiny_corpus):
    """Rebuilding the neighbor store at a different R_max must not touch
    the graph (paper §3.4: runtime parameter, no index rebuild)."""
    from repro.core import EngineConfig, GateANNEngine
    from repro.core.neighbor_store import NeighborStore

    corpus, labels, queries = tiny_corpus
    eng = GateANNEngine.build(
        corpus, config=EngineConfig(degree=20, build_l=40, pq_chunks=8, r_max=10),
        labels=labels,
    )
    graph_before = np.asarray(eng.record_store.neighbors).copy()
    eng.neighbor_store = NeighborStore.from_graph(eng.record_store.neighbors, 4)
    assert eng.neighbor_store.r_max == 4
    np.testing.assert_array_equal(np.asarray(eng.record_store.neighbors), graph_before)
    tgt = np.zeros(queries.shape[0], np.int32)
    out = eng.search(queries, filter_kind="label", filter_params=tgt,
                     search_config=SearchConfig(mode="gate", search_l=64))
    ids = np.asarray(out.ids)
    assert (np.asarray(labels)[ids[ids >= 0]] == 0).all()


def test_with_cache_threads_neighbors_explicitly(tiny_engine):
    """with_cache must not require a ``neighbors`` attribute on the
    backing — the sharded tier only exposes ``local_neighbors`` (and a
    regression here broke every non-in-memory backing)."""
    import dataclasses

    from repro.store import CachedRecordStore, ShardedRecordStore

    backing = ShardedRecordStore(
        local_vectors=tiny_engine.vectors,
        local_neighbors=tiny_engine.record_store.neighbors,
        rows_per_shard=int(tiny_engine.vectors.shape[0]),
    )
    eng = dataclasses.replace(tiny_engine, record_store=backing)
    cached = eng.with_cache(32 * 4096)
    assert isinstance(cached.record_store, CachedRecordStore)
    assert cached.record_store.backing is backing
    assert cached.record_store.n_cached == 32
    # and budget 0 unwraps back to the bare backing without touching it
    assert cached.with_cache(0).record_store is backing
    # a *partial* shard (local rows != corpus rows) must be rejected
    # loudly — its adjacency is locally indexed, not global
    half = int(tiny_engine.vectors.shape[0]) // 2
    partial = ShardedRecordStore(
        local_vectors=tiny_engine.vectors[:half],
        local_neighbors=tiny_engine.record_store.neighbors[:half],
        rows_per_shard=half,
    )
    eng_partial = dataclasses.replace(tiny_engine, record_store=partial)
    with pytest.raises(ValueError, match="partial"):
        eng_partial.with_cache(32 * 4096)


def test_recall_at_k_matches_reference():
    """The broadcast recall must equal the old per-row set loop exactly."""

    def reference(result_ids, gt_ids, k=10):
        res = np.asarray(result_ids)[:, :k]
        hits = denom = 0
        for r, g in zip(res, np.asarray(gt_ids)[:, :k]):
            gset = set(int(x) for x in g if x >= 0)
            if not gset:
                continue
            hits += len(gset & set(int(x) for x in r if x >= 0))
            denom += len(gset)
        return hits / max(denom, 1)

    rng = np.random.default_rng(0)
    for trial in range(20):
        b, k = int(rng.integers(1, 12)), int(rng.integers(1, 12))
        res = rng.integers(-1, 40, size=(b, k + 2))  # dup ids + -1 pads
        gt = np.full((b, k), -1, np.int64)
        for row in range(b):  # unique ids per gt row, variable fill
            fill = int(rng.integers(0, k + 1))
            gt[row, :fill] = rng.choice(40, size=fill, replace=False)
        got = recall_at_k(res, gt, k)
        want = reference(res, gt, k)
        assert got == pytest.approx(want), (trial, got, want)
    assert recall_at_k(np.full((3, 5), -1), np.full((3, 5), -1), 5) == 0.0


def test_rag_mixed_predicate_batch(tiny_engine, tiny_corpus):
    """retrieve() must serve a batch mixing predicate kinds (grouped by
    kind, results merged in request order) instead of asserting."""
    from repro.serve.rag import RAGRequest, RAGServer

    _, _, queries = tiny_corpus
    n = int(tiny_engine.vectors.shape[0])
    server = RAGServer(
        engine=tiny_engine, cfg=None, params=None, layout=None,
        passage_tokens=np.zeros((n, 2), np.int32),
        search_config=SearchConfig(mode="gate", search_l=48, beam_width=4),
    )
    reqs = []
    for i in range(6):
        if i % 3 == 0:  # unfiltered request
            reqs.append(RAGRequest(query_vec=queries[i], prompt_tokens=np.zeros(2, np.int32)))
        else:  # equality predicate, two different targets
            reqs.append(RAGRequest(
                query_vec=queries[i], prompt_tokens=np.zeros(2, np.int32),
                filter_kind="label", filter_params=np.int32(i % 2),
            ))
    ids, stats = server.retrieve(reqs)
    assert ids.shape == (6, server.search_config.result_k)
    assert np.asarray(stats.n_ios).shape == (6,)
    # per-request rows must equal the homogeneous sub-batch runs
    for kind, idxs in (("label", [1, 2, 4, 5]), (None, [0, 3])):
        sub = [reqs[i] for i in idxs]
        sub_ids, sub_stats = server.retrieve(sub)
        np.testing.assert_array_equal(ids[idxs], sub_ids)
        np.testing.assert_array_equal(
            np.asarray(stats.n_ios)[idxs], np.asarray(sub_stats.n_ios))
    assert server.served_queries == 6 + 6  # both retrieve calls accounted


def test_rag_empty_batch(tiny_engine):
    """An empty request batch must serve empty ids/stats, not crash —
    production streams legitimately drain to nothing between ticks."""
    from repro.core.search import SearchStats
    from repro.serve.rag import RAGServer

    n = int(tiny_engine.vectors.shape[0])
    server = RAGServer(
        engine=tiny_engine, cfg=None, params=None, layout=None,
        passage_tokens=np.zeros((n, 2), np.int32),
        search_config=SearchConfig(mode="gate", search_l=48, beam_width=4),
    )
    ids, stats = server.retrieve([])
    assert ids.shape == (0, server.search_config.result_k)
    assert ids.dtype == np.int32
    for f in SearchStats._fields:
        assert np.asarray(getattr(stats, f)).shape == (0,), f
    assert server.build_prompts([], ids).shape == (0, 0)
    tokens, gstats = server.generate([], max_new_tokens=4)
    assert tokens.shape == (0, 4)
    assert np.asarray(gstats.n_ios).shape == (0,)
    # nothing was accounted and the report still renders
    assert server.served_queries == 0 and server.served_ios == 0
    assert server.io_report()["queries"] == 0


def test_rag_batch_bucketing(tiny_engine, tiny_corpus):
    """bucket_sizes pads mixed-kind sub-batches to canonical sizes: the
    jitted loop only ever sees bucket-sized batches (bounded retraces),
    results match the unbucketed server exactly, and the padding rows are
    excluded from the served-I/O accounting (surfaced as padded_rows /
    padding_ios instead)."""
    from repro.serve.rag import RAGRequest, RAGServer

    _, _, queries = tiny_corpus
    n = int(tiny_engine.vectors.shape[0])

    def make_server(bucket_sizes):
        return RAGServer(
            engine=tiny_engine, cfg=None, params=None, layout=None,
            passage_tokens=np.zeros((n, 2), np.int32),
            search_config=SearchConfig(mode="gate", search_l=48, beam_width=4),
            bucket_sizes=bucket_sizes,
        )

    reqs = []
    for i in range(7):  # 3 unfiltered + 4 label rows -> buckets 4 and 4
        if i % 2 == 0 and i < 6:
            reqs.append(RAGRequest(query_vec=queries[i],
                                   prompt_tokens=np.zeros(2, np.int32)))
        else:
            reqs.append(RAGRequest(
                query_vec=queries[i], prompt_tokens=np.zeros(2, np.int32),
                filter_kind="label", filter_params=np.int32(0),
            ))
    plain = make_server(())
    bucketed = make_server((4, 8))
    seen_sizes = []
    real_search = tiny_engine.search

    def spy(q, **kw):
        seen_sizes.append(int(np.asarray(q).shape[0]))
        return real_search(q, **kw)

    import dataclasses

    bucketed.engine = dataclasses.replace(tiny_engine)
    bucketed.engine.search = spy  # instance attr shadows the method
    ids_p, stats_p = plain.retrieve(reqs)
    ids_b, stats_b = bucketed.retrieve(reqs)
    # identical results and identical *served* accounting row-for-row
    np.testing.assert_array_equal(ids_b, ids_p)
    np.testing.assert_array_equal(np.asarray(stats_b.n_ios),
                                  np.asarray(stats_p.n_ios))
    assert plain.served_ios == bucketed.served_ios
    assert plain.served_queries == bucketed.served_queries == 7
    # every sub-batch ran at a canonical size; padding was accounted apart
    assert set(seen_sizes) <= {4, 8}, seen_sizes
    assert bucketed.padded_rows == (4 - 3) + (4 - 4)
    assert bucketed.padding_ios >= 0
    rep = bucketed.io_report()
    assert rep["padded_rows"] == bucketed.padded_rows
    assert rep["padding_ios"] == bucketed.padding_ios
    assert "padded_rows" not in plain.io_report()
    # a group larger than every bucket runs at its natural size
    big = make_server((2,))
    big_ids, _ = big.retrieve(reqs)
    np.testing.assert_array_equal(big_ids, ids_p)
    assert big.padded_rows == 0


def test_multilabel_subset_search(tiny_corpus):
    from repro.core import EngineConfig, GateANNEngine
    from repro.core.filter_store import pack_tags
    from repro.data.labels import multilabel_tags, multilabel_queries

    corpus, _, queries = tiny_corpus
    n = corpus.shape[0]
    tags = multilabel_tags(n, vocab=64, mean_tags=4.0, seed=0)
    bits = pack_tags(tags, 64)
    eng = GateANNEngine.build(
        corpus, config=EngineConfig(degree=20, build_l=40, pq_chunks=8, r_max=10),
        tag_bits=bits,
    )
    qtags = multilabel_queries(tags, queries.shape[0], n_tags=(1, 1), seed=2)
    qbits = pack_tags(qtags, 64)
    out = eng.search(queries, filter_kind="tags", filter_params=jnp.asarray(qbits),
                     search_config=SearchConfig(mode="gate", search_l=64))
    ids = np.asarray(out.ids)
    for row, qt in zip(ids, qtags):
        for i in row[row >= 0]:
            assert set(qt) <= set(tags[int(i)])


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    unset, the cache lives at the one fixed, git-ignored in-checkout path."""
    import os

    import jax

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir:
            monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
            assert compile_cache.configure_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before  # untouched
        else:
            monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
            got = compile_cache.configure_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            with open(os.path.join(repo, ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
