"""A benchmark root of tiny cells for the CPU tests: the repository's
traffic mixes, metric readers, corpus generators, filter schemas, loops
and reference plans, with configurations small enough to build in seconds."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def config(tier: str) -> dict:
    """A tiny configuration of the given record tier."""
    disk = tier == "disk"
    return {
        "name": f"tiny-{tier}",
        "data_seed": 7,
        "n_vectors": 1200,
        "element_dtype": "float32",
        "corpus": {"generator": "bigann_like", "dim": 16, "clusters": 8},
        "filter": "label",
        "labels": {"kind": "uniform", "classes": 4},
        "index": {"degree": 12, "build_l": 24, "alpha": 1.2, "build_batch": 512, "pq_chunks": 4,
                  "r_max": 6},
        "record_tier": ({"tier": "disk", "cache_records": 32,
                         "cache_policy": "adaptive", "refresh_every": 2,
                         "page_cache": "drop"} if disk
                        else {"tier": "memory", "cache_records": 0}),
        "search": {"mode": "gate", "search_l": 24, "beam_width": 4,
                   "result_k": 10, "max_hops": 512,
                   "pipeline_depth": 2 if disk else 1},
        "limits": {"mismatch": 0.02, "order_errors": 0, "recall_miss": 0.5,
                   "filter_violations": 0, "unanswered": 0},
    }


TRAFFIC = {
    "loop": {"kind": "closed", "clients": 6},
    "query": {"kind": "near_corpus", "noise": 0.05},
    "labels": {"kind": "uniform"},
    "pool": 256,
    "max_batch": 8,
    "batch_window_s": 0.002,
    "bucket_sizes": [4, 8],
    "warmup_bursts": [4, 8, 8, 8],
    "check_sample": 48,
}
# the same requests arriving on their own schedule, below the tiny
# cells' capacity on a CPU
OPEN_TRAFFIC = dict(TRAFFIC, loop={"kind": "open", "rate_qps": 40.0})


# each of the repository's cells and the tiny cell that reports its metrics
STANDS_FOR = {"bigann-ssd.closed64": "tiny-disk.t", "deep-hbm.closed64": "tiny-memory.t",
              "bigann-ssd.open80": "tiny-disk.o"}


def make_root(path: str) -> str:
    """``path`` made into a benchmark root holding cells ``tiny-disk.t``,
    ``tiny-memory.t`` and ``tiny-disk.o`` (the open loop) beside the
    repository's own entries; each tiny cell is listed where the cell it
    stands for is (``STANDS_FOR``)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = os.path.join(path, "bench")
    for folder in ("metrics", "traffic", "configs", "corpora", "filters", "loops",
                   "reference/plans"):
        shutil.copytree(os.path.join(REPO, "bench", folder), os.path.join(bench, folder),
                        ignore=shutil.ignore_patterns("__pycache__"))
    for name, traffic in (("t", TRAFFIC), ("o", OPEN_TRAFFIC)):
        with open(os.path.join(bench, "traffic", f"{name}.json"), "w") as f:
            json.dump(traffic, f)
    for tier in ("disk", "memory"):
        cfg = config(tier)
        rel = f"bench/configs/{cfg['name']}.json"
        with open(os.path.join(path, rel), "w") as f:
            json.dump(cfg, f)
        spec["configs"].append({"name": cfg["name"], "source": "tests",
                                "file": rel, "reduced": [], "why": "tests"})
        spec["workloads"].append({"name": f"{cfg['name']}.t", "config": cfg["name"],
                                  "traffic": "t", "chips": 1, "why": "tests"})
    spec["workloads"].append({"name": "tiny-disk.o", "config": "tiny-disk",
                              "traffic": "o", "chips": 1, "why": "tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [t for real, t in STANDS_FOR.items() if real in m["workloads"]]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return path
