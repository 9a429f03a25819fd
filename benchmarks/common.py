"""Shared benchmark harness: cached corpus/engine builds, L-sweeps, CSV.

Scale is CPU-budget-resized (N=20k vs the paper's 100M+) — per DESIGN.md
§8, *structural* metrics (I/O counts, recall, 1/s law, tunnel counts) are
measured for real; *device-time* metrics (latency/QPS) come from the
calibrated io_model with the paper's own constants.  The distributed
dry-run covers the 100M-scale memory/collective story.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EngineConfig, GateANNEngine, SearchConfig, recall_at_k
from repro.core.graph import BUILD_REVISION, VamanaGraph, build_vamana
from repro.core.io_model import DEFAULT_COST_MODEL
from repro.data import (
    filtered_ground_truth,
    make_bigann_like,
    make_queries,
    uniform_labels,
)

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
# cached graphs and indexes live under the build revision that made them,
# so a change to build_vamana's output never serves a stale artifact
CACHE_DIR = os.path.join(REPO_ROOT, "results", "bench_cache", f"rev{BUILD_REVISION}")

# version stamp for every benchmark JSON artifact (BENCH_*.json) — bump
# on any field rename/removal so nightly consumers can fail loudly
# instead of silently reading shifted columns
BENCH_SCHEMA_VERSION = 1


def root_artifact(name: str) -> str:
    """Anchor an artifact filename at the repo root (stable across CWDs)."""
    return name if os.path.isabs(name) else os.path.join(REPO_ROOT, name)


def write_bench_json(path: str, benchmark: str, rows, extra: dict | None = None):
    """Write the standard benchmark JSON artifact (schema-versioned)."""
    import json

    doc = {"schema_version": BENCH_SCHEMA_VERSION, "benchmark": benchmark,
           "rows": rows}
    if extra:
        doc.update(extra)
    path = root_artifact(path)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, default=float)
    return path

# default benchmark scale
N, DIM, NQ, N_CLASSES = 20_000, 32, 48, 10
DEGREE, BUILD_L, PQ_CHUNKS, R_MAX = 32, 64, 8, 16
L_SWEEP = (20, 40, 60, 100, 150, 200)


def cached_graph(n: int = N, dim: int = DIM, seed: int = 0, degree: int = DEGREE,
                 build_l: int = BUILD_L, tag: str = "") -> tuple[np.ndarray, VamanaGraph]:
    os.makedirs(CACHE_DIR, exist_ok=True)
    path = os.path.join(CACHE_DIR, f"graph_{tag}{n}_{dim}_{degree}_{seed}.npz")
    corpus = make_bigann_like(n, dim, seed=seed)
    if os.path.exists(path):
        z = np.load(path)
        return corpus, VamanaGraph(
            neighbors=jnp.asarray(z["neighbors"]), medoid=jnp.int32(z["medoid"])
        )
    t0 = time.perf_counter()
    g = build_vamana(corpus, degree=degree, build_l=build_l, seed=seed)
    print(f"# built graph n={n} in {time.perf_counter()-t0:.0f}s", file=sys.stderr)
    np.savez(path, neighbors=np.asarray(g.neighbors), medoid=int(g.medoid))
    return corpus, g


def build_engine(corpus, graph, *, labels=None, attributes=None, tag_bits=None,
                 r_max: int = R_MAX) -> GateANNEngine:
    return GateANNEngine.build(
        corpus,
        config=EngineConfig(degree=graph.neighbors.shape[1], pq_chunks=PQ_CHUNKS,
                            r_max=r_max),
        labels=labels, attributes=attributes, tag_bits=tag_bits, graph=graph,
    )


def standard_setup(seed: int = 0):
    """The workhorse: 20k corpus + graph + uniform 10-class labels."""
    corpus, graph = cached_graph(seed=seed)
    labels = uniform_labels(N, N_CLASSES, seed=seed)
    queries = make_queries(corpus, NQ, seed=seed + 1)
    engine = build_engine(corpus, graph, labels=labels)
    gt = filtered_ground_truth(corpus, queries, labels == 0, k=10)
    return dict(corpus=corpus, graph=graph, labels=labels, queries=queries,
                engine=engine, gt=gt)


def sweep(engine, queries, gt, *, mode: str, l_values=L_SWEEP, beam_width: int = 8,
          filter_kind="label", filter_params=None, k: int = 10):
    """Returns rows: (L, recall, ios, tunnels, exact, lat1_us, qps32)."""
    if filter_params is None:
        filter_params = np.zeros(queries.shape[0], np.int32)
    rows = []
    for L in l_values:
        out = engine.search(
            queries, filter_kind=filter_kind, filter_params=filter_params,
            search_config=SearchConfig(mode=mode, search_l=L, result_k=k,
                                       beam_width=beam_width),
        )
        ios = float(np.mean(np.asarray(out.stats.n_ios)))
        tun = float(np.mean(np.asarray(out.stats.n_tunnels)))
        nex = float(np.mean(np.asarray(out.stats.n_exact)))
        rec = recall_at_k(out.ids, gt, k)
        lat = engine.modeled_latency_us(out.stats)
        qps = engine.modeled_qps(out.stats)
        rows.append(dict(L=L, recall=rec, ios=ios, tunnels=tun, exact=nex,
                         lat1_us=lat, qps32=qps))
    return rows


def emit(name: str, rows, derived_key: str = "recall"):
    """Print `name,us_per_call,derived` CSV lines (benchmark contract)."""
    out = []
    for r in rows:
        line = f"{name},{r.get('lat1_us', 0.0):.1f},{r[derived_key]:.4f}"
        print(line)
        out.append(line)
    return out
