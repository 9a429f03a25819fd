#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from.

    python3 bench/limits.py --workload <cell> --seeds 12 --seconds 20

In one process on the chip: the cell is set up once (index built or
loaded, serving stack, warm-up), then for each of ``--seeds`` fresh
seeds, drawn from ``--first-seed``, one window of the seed's traffic at
the cell's own load, long enough to answer as many requests as a run
checks, and the check of its sample.  Beside the program's numbers it
prints, on the same sampled requests, those of

  * the control: the reference computed in bfloat16 put in the
    program's place;
  * two faults planted in the program's answers: each answer's first id
    altered, and half of the sample given the other half's answers.

One JSON line per seed, then the lower reading of each number (the
largest the program gave) and its upper readings (the smallest the
control and each fault gave).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import data as datam  # noqa: E402
from bench import run as runm  # noqa: E402


def readings(name: str, seeds: list, seconds: float, *, root: str = runm.ROOT,
             cache_dir: str = runm.CACHE_DIR) -> list[dict]:
    """One reading per seed: the program's numbers, the control's and
    each fault's."""
    s = runm.set_up(name, seeds[0], seconds, root=root, cache_dir=cache_dir,
                    start_time=time.perf_counter())
    cfg, tr = s.cell.config, s.cell.traffic
    rows = []
    try:
        for seed in seeds:
            t0 = time.perf_counter()
            reqs, _calls, _box, vecs, preds = runm.window(s, seed, seconds, None)
            out = runm.check_answers(reqs, vecs, preds, s.corpus, s.attrs,
                                     s.schema.passes, cfg, tr, s.info["path"],
                                     datam.streams(seed)["sample"], readings=True)
            out = dict(seed=seed, attempted=len(reqs),
                       seconds=time.perf_counter() - t0, **out)
            rows.append(out)
            print("READING " + json.dumps(out), flush=True)
    finally:
        s.stack.close()
    return rows


def summary(rows: list[dict]) -> dict:
    """The lower reading of each number and the upper readings."""
    lower = {k: max(r["program"][k] for r in rows) for k in rows[0]["control"]}
    upper = {"control": {k: min(r["control"][k] for r in rows) for k in lower}}
    for f in rows[0]["faults"]:
        upper[f] = {k: min(r["faults"][f][k] for r in rows) for k in lower}
    return {"lower": lower, "upper": upper}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(runm.CACHE_DIR, "jax"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    runm.require_chips(runm.specm.Bench().cell(args.workload).chips)
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
    rows = readings(args.workload, seeds, args.seconds)
    print("SUMMARY " + json.dumps(summary(rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
