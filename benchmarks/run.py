"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` runs a reduced set;
``--figure figNN`` runs one.  Builds are cached under results/bench_cache.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import common
from benchmarks import figures as F


def main() -> None:
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--figure", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="core figures only (motivation, main, io, ablation)")
    args = ap.parse_args()

    t0 = time.perf_counter()
    print("# building shared setup (cached)", file=sys.stderr)
    ctx = common.standard_setup()
    print(f"# setup ready ({time.perf_counter()-t0:.0f}s)", file=sys.stderr)

    quick_set = {"fig01_motivation", "fig05_main", "fig07_io", "fig18_ablation",
                 "table5_breakdown"}
    print("name,us_per_call,derived")
    for fn in F.ALL_FIGURES:
        if args.figure and not fn.__name__.startswith(args.figure):
            continue
        if args.quick and fn.__name__ not in quick_set:
            continue
        t1 = time.perf_counter()
        try:
            rows = fn(ctx)
        except Exception as e:  # noqa: BLE001 — report and continue
            print(f"{fn.__name__}_FAILED,0.0,0.0")
            print(f"# {fn.__name__} failed: {e}", file=sys.stderr)
            import traceback

            traceback.print_exc()
            continue
        for r in rows:
            print(f"{r['name']},{r.get('lat1_us', 0.0):.1f},{r['derived']:.4f}")
        print(f"# {fn.__name__} done ({time.perf_counter()-t1:.0f}s)", file=sys.stderr)


if __name__ == "__main__":
    main()
