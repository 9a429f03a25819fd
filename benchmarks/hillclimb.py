"""§Perf hillclimb driver: lower one cell under a named variant, report the
three roofline terms, and append the iteration to results/perf_log.json.

Variants (composable via comma):
  baseline     — exactly what the dry-run sweep ran
  cast_early   — bf16-cast masters at the ZeRO shard before gather
                 (REPRO_CAST_EARLY=1): gathers + grad reduce-scatter in bf16
  donate       — donate the train state / decode caches (in-place updates,
                 no defensive copies)
  remat_dots   — checkpoint policy saving dot outputs (less recompute,
                 more activation memory) (REPRO_REMAT=dots)
  causal_skip  — skip fully-masked KV chunks in flash attention
                 (REPRO_CAUSAL_SKIP=1)
  kv_int8      — int8 KV cache with per-slot scales (REPRO_KV_INT8=1)

Usage:
  PYTHONPATH=src python -m benchmarks.hillclimb --arch deepseek-coder-33b \
      --shape train_4k --variant cast_early,donate
"""
import os
import sys

# must precede any jax import
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import json
import time


def main():
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--log", default="results/perf_log.json")
    args = ap.parse_args()

    variants = set(args.variant.split(","))
    os.environ["REPRO_CAST_EARLY"] = "1" if "cast_early" in variants else "0"
    os.environ["REPRO_GRAD_SHARD"] = "1" if "grad_shard" in variants else "0"
    os.environ["REPRO_REMAT"] = "dots" if "remat_dots" in variants else "full"
    os.environ["REPRO_KV_INT8"] = "1" if "kv_int8" in variants else "0"
    os.environ["REPRO_W_INT8"] = "1" if "w_int8" in variants else "0"
    donate = "donate" in variants

    from repro.configs.base import ALL_SHAPES
    from repro.launch.dryrun import lower_cell
    from benchmarks.roofline import (
        DRYRUN_KIND, analytic_collective_bytes, model_bytes_per_device,
        model_flops_per_device, peaks,
    )

    shape = next(s for s in ALL_SHAPES if s.name == args.shape)
    t0 = time.perf_counter()
    _, compiled, report, hlo = lower_cell(args.arch, shape, donate=donate)
    pk = peaks(DRYRUN_KIND)
    t_c = report["flops_per_device"] / pk["flops"]
    hlo_m = report["hbm_bytes_per_device"] / pk["hbm_bw"]
    ana_m = model_bytes_per_device(report, variants) / pk["hbm_bw"]
    t_m = min(hlo_m, ana_m)
    # collective: HLO parse is f32-normalized on the CPU backend (bf16
    # widened) — report both the parse and the dtype-corrected model
    t_x_hlo = report["collective_bytes_total"] / pk["link_bw"]
    coll_model = analytic_collective_bytes(report, variants)
    # two corrected estimates: (a) analytic structure x logical dtypes,
    # (b) HLO-parsed structure x bf16 correction (CPU f32-normalizes all
    # compute tensors; under cast_early everything big is logically bf16).
    dtype_factor = 0.5 if "cast_early" in variants else 1.0
    t_x_corrected_parse = t_x_hlo * dtype_factor
    t_x = min(coll_model["total"] / pk["link_bw"], t_x_corrected_parse)
    entry = {
        "arch": args.arch,
        "shape": args.shape,
        "variant": sorted(variants),
        "t_compute_s": t_c,
        "t_memory_s": t_m,
        "t_memory_hlo_s": hlo_m,
        "t_collective_s": t_x,
        "t_collective_hlo_s": t_x_hlo,
        "collective_model_by_kind": {k: v for k, v in coll_model.items()},
        "collective_hlo_by_kind": report["collective_bytes_per_device"],
        "collective_counts": report["collective_counts"],
        "useful_ratio": model_flops_per_device(report) / max(report["flops_per_device"], 1),
        "bound_s": max(t_c, t_m, t_x),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    print(json.dumps(entry, indent=2))
    os.makedirs(os.path.dirname(args.log), exist_ok=True)
    log = []
    if os.path.exists(args.log):
        with open(args.log) as f:
            log = json.load(f)
    log.append(entry)
    with open(args.log, "w") as f:
        json.dump(log, f, indent=2)


if __name__ == "__main__":
    main()
