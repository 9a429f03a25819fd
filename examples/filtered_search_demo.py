"""Every predicate family on one index, no rebuilds (paper §3.2).

Equality, range over a continuous attribute, multi-label subset, and a
conjunction — plus an R_max sweep showing the runtime DRAM knob.

    PYTHONPATH=src python examples/filtered_search_demo.py
"""
import sys

sys.path.insert(0, "src")

from repro.compile_cache import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np

from repro.core import EngineConfig, GateANNEngine, SearchConfig
from repro.core.filter_store import AndFilter, pack_tags
from repro.core.neighbor_store import NeighborStore
from repro.data import make_bigann_like, make_queries, uniform_labels
from repro.data.labels import multilabel_queries, multilabel_tags, norm_bin_attribute

N, DIM, NQ = 6_000, 32, 16
corpus = make_bigann_like(N, DIM, seed=0)
labels = uniform_labels(N, 10, seed=0)
norms, edges = norm_bin_attribute(corpus, 10)
tags = multilabel_tags(N, vocab=512, mean_tags=5.0, seed=0)

engine = GateANNEngine.build(
    corpus,
    config=EngineConfig(degree=28, build_l=56, pq_chunks=8, r_max=14),
    labels=labels,
    attributes=norms,
    tag_bits=pack_tags(tags, 512),
)
queries = make_queries(corpus, NQ, seed=1)
cfg = SearchConfig(mode="gate", search_l=80, beam_width=8)


def report(name, out, check):
    ids = np.asarray(out.ids)
    ok = all(check(int(i)) for row in ids for i in row if i >= 0)
    ios = float(np.mean(np.asarray(out.stats.n_ios)))
    tun = float(np.mean(np.asarray(out.stats.n_tunnels)))
    print(f"{name:28s} predicate-clean={ok}  ios/q={ios:6.1f} tunnels/q={tun:6.1f}")


# 1. equality
out = engine.search(queries, filter_kind="label",
                    filter_params=np.zeros(NQ, np.int32), search_config=cfg)
report("equality (label==0)", out, lambda i: labels[i] == 0)

# 2. range over the norm attribute (one equal-frequency bin, ~10%)
lo, hi = float(edges[3]), float(edges[4])
out = engine.search(queries, filter_kind="range",
                    filter_params=(np.full(NQ, lo, np.float32),
                                   np.full(NQ, hi, np.float32)),
                    search_config=cfg)
report(f"range (norm in [{lo:.0f},{hi:.0f}])", out,
       lambda i: lo <= norms[i] <= hi)

# 3. multi-label subset (YFCC semantics)
qtags = multilabel_queries(tags, NQ, n_tags=(1, 2), seed=2)
qbits = jnp.asarray(pack_tags(qtags, 512))
out = engine.search(queries, filter_kind="tags", filter_params=qbits,
                    search_config=cfg)
ok = all(
    set(qtags[q]) <= set(tags[int(i)])
    for q, row in enumerate(np.asarray(out.ids)) for i in row if i >= 0
)
print(f"{'subset (tags ⊆ node.tags)':28s} predicate-clean={ok}  "
      f"ios/q={float(np.mean(np.asarray(out.stats.n_ios))):6.1f} "
      f"tunnels/q={float(np.mean(np.asarray(out.stats.n_tunnels))):6.1f}")

# 4. conjunction: label==0 AND norm-bin — swap the filter store, same index
conj = AndFilter((engine.filters["label"], engine.filters["range"]))
check = conj.bind(np.zeros(NQ, np.int32),
                  (np.full(NQ, lo, np.float32), np.full(NQ, hi, np.float32)))
from repro.core import search as searchm
from repro.core import pq as pqm

out = searchm.filtered_search(
    fetch=engine.record_store.fetch_fn(), neighbor_store=engine.neighbor_store,
    filter_check=check, lut=pqm.build_lut(engine.codec, jnp.asarray(queries)),
    codes=engine.codes, entry=engine.medoid, queries=jnp.asarray(queries),
    config=cfg,
)
report("conjunction (label AND range)", out,
       lambda i: labels[i] == 0 and lo <= norms[i] <= hi)

# 5. R_max is a runtime knob — rebuild the neighbor store, never the graph
print("\nR_max sweep (no index rebuild):")
for r_max in (4, 8, 16):
    engine.neighbor_store = NeighborStore.from_graph(
        engine.record_store.neighbors, r_max)
    out = engine.search(queries, filter_kind="label",
                        filter_params=np.zeros(NQ, np.int32), search_config=cfg)
    print(f"  R_max={r_max:3d}: dram={engine.neighbor_store.memory_bytes()/1e3:7.0f}KB "
          f"ios/q={float(np.mean(np.asarray(out.stats.n_ios))):6.1f}")
