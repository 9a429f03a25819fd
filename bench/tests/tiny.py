"""A benchmark root of tiny cells for the CPU tests: the repository's
traffic mixes and metric readers, with configurations small enough to
build in seconds."""
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


def make_root(path: str) -> str:
    """``path`` made into a benchmark root holding cells ``tiny-disk.t``
    and ``tiny-memory.t`` beside the repository's own entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = os.path.join(path, "bench")
    shutil.copytree(os.path.join(REPO, "bench", "metrics"),
                    os.path.join(bench, "metrics"))
    shutil.copytree(os.path.join(REPO, "bench", "traffic"),
                    os.path.join(bench, "traffic"))
    shutil.copytree(os.path.join(REPO, "bench", "configs"),
                    os.path.join(bench, "configs"))
    with open(os.path.join(bench, "traffic", "t.json"), "w") as f:
        json.dump(TRAFFIC, f)
    for tier in ("disk", "memory"):
        cfg = config(tier)
        rel = f"bench/configs/{cfg['name']}.json"
        with open(os.path.join(path, rel), "w") as f:
            json.dump(cfg, f)
        spec["configs"].append({"name": cfg["name"], "source": "tests",
                                "file": rel, "reduced": [], "why": "tests"})
        spec["workloads"].append({"name": f"{cfg['name']}.t", "config": cfg["name"],
                                  "traffic": "t", "chips": 1, "why": "tests"})
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-disk.t")
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return path
