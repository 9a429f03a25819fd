"""Quickstart: build a GateANN index and run filtered search in 4 modes.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys, time

sys.path.insert(0, "src")

from repro.compile_cache import configure_compile_cache

configure_compile_cache()

import numpy as np

from repro.core import EngineConfig, GateANNEngine, SearchConfig, recall_at_k
from repro.data import (
    filtered_ground_truth,
    make_bigann_like,
    make_queries,
    uniform_labels,
)

# 1. A BigANN-style corpus with 10-class metadata (paper Table 3, scaled).
N, DIM, NQ = 8_000, 32, 32
corpus = make_bigann_like(N, DIM, seed=0)
labels = uniform_labels(N, 10, seed=0)
queries = make_queries(corpus, NQ, seed=1)

# 2. Build once: Vamana graph + PQ codes + neighbor store + filter store.
t0 = time.perf_counter()
engine = GateANNEngine.build(
    corpus,
    config=EngineConfig(degree=32, build_l=64, pq_chunks=8, r_max=16),
    labels=labels,
)
print(f"built index for N={N} in {time.perf_counter()-t0:.0f}s")
print("memory:", engine.memory_report())

# 3. Search with a 10%-selectivity equality predicate, in every mode.
target = np.zeros(NQ, np.int32)  # "category == 0"
gt = filtered_ground_truth(corpus, queries, labels == 0, k=10)

print(f"\n{'mode':12s} {'recall@10':>9s} {'ios/q':>8s} {'tunnels/q':>9s} "
      f"{'lat(model)':>10s} {'qps@32T':>9s}")
for mode in ("post", "early", "pre_naive", "gate"):
    out = engine.search(
        queries, filter_kind="label", filter_params=target,
        search_config=SearchConfig(mode=mode, search_l=100, beam_width=8),
    )
    r = recall_at_k(out.ids, gt, 10)
    ios = float(np.mean(np.asarray(out.stats.n_ios)))
    tun = float(np.mean(np.asarray(out.stats.n_tunnels)))
    print(f"{mode:12s} {r:9.3f} {ios:8.1f} {tun:9.1f} "
          f"{engine.modeled_latency_us(out.stats):9.0f}us "
          f"{engine.modeled_qps(out.stats):9.0f}")

print("\nGateANN ('gate') matches post-filter recall with ~10x fewer record "
      "fetches — the paper's headline, reproduced structurally.")

# 4. Add the hot-node cache tier (a runtime knob, no rebuild): the hot
#    records near the medoid are served from device memory, killing the
#    slow-tier reads tunneling can't (the filter-passing hot nodes).
print(f"\n{'cache':>12s} {'ios/q':>8s} {'hits/q':>8s} {'qps@32T':>9s}")
for n_records in (0, 256, 1024):
    cached = engine.with_cache(n_records * 4096)
    out = cached.search(
        queries, filter_kind="label", filter_params=target,
        search_config=SearchConfig(mode="gate", search_l=100, beam_width=8),
    )
    ios = float(np.mean(np.asarray(out.stats.n_ios)))
    hits = float(np.mean(np.asarray(out.stats.n_cache_hits)))
    print(f"{n_records:9d} rec {ios:8.1f} {hits:8.1f} "
          f"{cached.modeled_qps(out.stats):9.0f}")

# 5. Persist the index and serve it from disk: save() writes one
#    page-aligned file (4 KB record sectors + PQ/graph/filter sidecars);
#    load() restores without rebuilding the graph or retraining PQ, and
#    store_tier="disk" serves records straight off the file with
#    *measured* (not modeled) page reads.
import os, tempfile

path = os.path.join(tempfile.mkdtemp(), "quickstart.gann")
t0 = time.perf_counter()
engine.save(path)
print(f"\nsaved index -> {path} ({os.path.getsize(path)//1024} KiB) "
      f"in {time.perf_counter()-t0:.1f}s")

disk = GateANNEngine.load(path, store_tier="disk")  # no rebuild, no retrain
store = disk.record_store
print(f"{'mode':12s} {'pages/q':>8s} {'ios/q':>8s} {'uniq/q':>8s} "
      f"{'sys/round':>9s} {'ids==mem':>9s}")
for mode in ("post", "gate"):
    before = store.io_counters()
    out = disk.search(
        queries, filter_kind="label", filter_params=target,
        search_config=SearchConfig(mode=mode, search_l=100, beam_width=8),
    )
    ids = np.asarray(out.ids)  # materialize => measured counters final
    ref = engine.search(
        queries, filter_kind="label", filter_params=target,
        search_config=SearchConfig(mode=mode, search_l=100, beam_width=8),
    )
    match = bool(np.array_equal(ids, np.asarray(ref.ids)))
    d = {k: v - before[k] for k, v in store.io_counters().items()}
    ios = float(np.mean(np.asarray(out.stats.n_ios)))
    print(f"{mode:12s} {d['pages_read']/NQ:8.1f} {ios:8.1f} "
          f"{d['unique_sectors_read']/NQ:8.1f} "
          f"{d['syscalls']/max(d['read_rounds'],1):9.1f} {str(match):>9s}")

print("\nThe disk tier *measures* the paper's central quantity: gate mode "
      "reads a fraction of post's 4 KB sectors, now counted off a real file —\n"
      f"and each round's beam coalesces into ONE {store.io_mode} submission "
      "(sorted, deduplicated, range-merged).")
