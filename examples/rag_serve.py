"""Serve batched RAG requests: GateANN filtered retrieval + LM decode.

Each request carries a query vector, a metadata predicate (document
category), and prompt tokens.  Retrieval runs in 'gate' mode — record
fetches happen only for predicate-passing passages; the generator is a
reduced gemma3-family model decoding greedily with ring-buffer caches.

    PYTHONPATH=src python examples/rag_serve.py
"""
import dataclasses
import sys
import time

sys.path.insert(0, "src")

from repro.compile_cache import configure_compile_cache

configure_compile_cache()

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core import EngineConfig, GateANNEngine, SearchConfig
from repro.data import make_bigann_like, make_queries, uniform_labels
from repro.distributed.sharding import NULL_LAYOUT
from repro.models import transformer as tfm
from repro.serve.rag import RAGRequest, RAGServer

# --- corpus of "passages": vectors + category metadata + token payloads
N, DIM = 4_000, 32
corpus = make_bigann_like(N, DIM, seed=0)
labels = uniform_labels(N, 10, seed=0)
rng = np.random.default_rng(0)

cfg = dataclasses.replace(get_smoke_config("gemma3-4b"), dtype="float32")
passage_tokens = rng.integers(0, cfg.vocab_size, size=(N, 8)).astype(np.int32)

print("building retrieval index ...")
engine = GateANNEngine.build(
    corpus,
    config=EngineConfig(degree=24, build_l=48, pq_chunks=8, r_max=12,
                        # adaptive hot-node record cache: 256 records stay
                        # device-resident; online visit counters re-learn
                        # the hot set from live traffic after every batch,
                        # with a per-filter partition per category
                        cache_budget_bytes=256 * 4096,
                        cache_policy="adaptive", refresh_every=1),
    labels=labels,
)
params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
server = RAGServer(
    engine=engine, cfg=cfg, params=params, layout=NULL_LAYOUT,
    passage_tokens=passage_tokens,
    search_config=SearchConfig(mode="gate", search_l=48, result_k=3, beam_width=4),
)

# --- a batch of requests, all filtered to category 3
reqs = [
    RAGRequest(
        query_vec=make_queries(corpus, 1, seed=10 + i)[0],
        prompt_tokens=rng.integers(0, cfg.vocab_size, size=6).astype(np.int32),
        filter_kind="label",
        filter_params=np.int32(3),
    )
    for i in range(4)
]

t0 = time.perf_counter()
tokens, stats = server.generate(reqs, max_new_tokens=8)
ios = float(np.mean(np.asarray(stats.n_ios)))
tun = float(np.mean(np.asarray(stats.n_tunnels)))
hits = float(np.mean(np.asarray(stats.n_cache_hits)))
print(f"retrieval: {ios:.1f} slow-tier reads/query, {hits:.1f} cache hits/query, "
      f"{tun:.1f} tunnels/query (all retrieved passages satisfy category==3)")
print(f"server io_report: {server.io_report()}")
# a second retrieval pass of the same workload: the adaptive cache has
# refreshed its hot set from the first batch's visit counters
server.retrieve(reqs)
rep = server.io_report()
print(f"after adaptation: hit rate {rep['last_batch_hit_rate']:.2f} "
      f"(refreshes={rep['cache_refreshes']}, partitions={rep['cache_partitions']})")
print(f"generated {tokens.shape[1]} tokens per request in {time.perf_counter()-t0:.0f}s:")
for i, row in enumerate(tokens):
    print(f"  request {i}: {row.tolist()}")
