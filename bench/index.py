"""The configuration's index: built once, then loaded.

A deployment builds its index offline and loads it when it starts.  The
benchmark does the same: the first run of a configuration builds
the index (``graph.build_vamana`` at the configuration's batch of
insertions, then ``GateANNEngine.build`` over that graph) and saves it under
``bench/.cache/index/``; every run, the first included, then serves an
engine made by ``GateANNEngine.load`` (bit-identical to the built one).

The cache key holds everything the saved bytes depend on: the
configuration's data and index groups and its ``data_seed``, what its
filter schema adds (``key_groups``: the groups its attributes come from,
beyond ``labels``), a hash of the build code, the matmul precision in
force, the JAX version and the platform.
The cache is bounded: the least recently used files go first.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os
import time

import numpy as np

from bench import data as datam

# the modules whose code decides the bytes of a saved index
BUILD_CODE = (
    "src/repro/core/engine.py",
    "src/repro/core/graph.py",
    "src/repro/core/pq.py",
    "src/repro/store/format.py",
)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_BYTES = 4 << 30  # index files kept, in all
RECORD_BYTES = 4096  # one record sector: a cache budget counts these


def code_hash(root: str = REPO) -> str:
    h = hashlib.sha256()
    for rel in BUILD_CODE:
        with open(os.path.join(root, rel), "rb") as f:
            h.update(rel.encode() + b"\0" + f.read())
    return h.hexdigest()[:16]


def cache_key(config: dict, code: str, precision: str, jax_version: str,
              platform: str, schema_groups: dict | None = None) -> str:
    blob = json.dumps({
        "n_vectors": config["n_vectors"], "corpus": config["corpus"],
        "labels": config.get("labels"), "index": config["index"],
        "data_seed": int(config["data_seed"]), "code": code,
        "precision": precision, "jax": jax_version, "platform": platform,
        **(schema_groups or {}),
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


def _evict(index_dir: str, keep: str, limit: int = CACHE_BYTES) -> None:
    files = sorted(glob.glob(os.path.join(index_dir, "*.gann")),
                   key=os.path.getmtime)
    total = sum(os.path.getsize(f) for f in files)
    for f in files:
        if total <= limit:
            break
        if f != keep:
            total -= os.path.getsize(f)
            os.remove(f)


def open_engine(config: dict, corpus: np.ndarray, attrs, schema, *,
                cache_dir: str, say):
    """The served engine of ``config``, whose records carry ``attrs`` of
    the filter ``schema``: (engine, info).

    ``info`` says whether this run built the index (``cold``) and, if it
    did, how long the build took."""
    import jax

    from repro.core import EngineConfig, GateANNEngine
    from repro.core import graph as graphm

    key = cache_key(config, code_hash(),
                    str(jax.config.jax_default_matmul_precision),
                    jax.__version__, jax.devices()[0].platform,
                    schema.key_groups(config))
    index_dir = os.path.join(cache_dir, "index")
    os.makedirs(index_dir, exist_ok=True)
    path = os.path.join(index_dir, f"{key}.gann")
    info = {"cold": not os.path.exists(path), "path": path}
    if info["cold"]:
        ix = config["index"]
        t0 = time.perf_counter()
        bseed = datam.build_seed(config["data_seed"])
        graph = graphm.build_vamana(
            corpus, degree=ix["degree"], build_l=ix["build_l"],
            alpha=ix["alpha"], batch_size=ix["build_batch"], seed=bseed)
        built = GateANNEngine.build(corpus, graph=graph,
                                    **schema.build_args(config, attrs),
                                    config=EngineConfig(
            degree=ix["degree"], build_l=ix["build_l"], alpha=ix["alpha"],
            pq_chunks=ix["pq_chunks"], r_max=ix["r_max"], seed=bseed))
        jax.block_until_ready((built.codes, built.record_store.neighbors))
        info["build_s"] = time.perf_counter() - t0
        info["nodes_per_s"] = corpus.shape[0] / info["build_s"]
        built.save(path)  # written to a temporary name, then renamed
        del built
        say(f"index built in {info['build_s']:.1f} s "
            f"({info['nodes_per_s']:.0f} nodes/s) and cached as {path}")
    else:
        os.utime(path)  # most recently used
    _evict(index_dir, keep=path)
    tier = config["record_tier"]
    overrides = {"store_tier": tier["tier"]}
    if tier.get("cache_records", 0):
        overrides.update(cache_budget_bytes=tier["cache_records"] * RECORD_BYTES,
                         cache_policy=tier["cache_policy"],
                         refresh_every=tier["refresh_every"])
    engine = GateANNEngine.load(path, **overrides)
    store = engine.measured_store()
    if store is not None and tier.get("page_cache") == "drop":
        store.drop_page_cache()  # a record's first read in the run is a disk read
    return engine, info
