"""Cache sweep: slow-tier I/O, hit rate, and modeled QPS vs cache budget.

Sweeps the hot-node record cache (``EngineConfig.cache_budget_bytes``)
per search mode on the standard 20k setup, then pits the **adaptive**
policy against the static one on a *skewed selective-filter* workload
(Zipfian query centers over the rare-label region, gate mode) — the
regime where a static, filter-blind hot set thrashes.  The cache is a
runtime knob (``engine.with_cache``) so the graph/PQ build is shared
across the whole sweep.  Emits the benchmark-contract CSV
``name,us_per_call,derived``:

  cache_<mode>_r<records>_ios        derived = mean slow-tier reads/query
  cache_<mode>_r<records>_hitrate    derived = hits / (hits + slow reads)
  cache_<mode>_r<records>_qps32      derived = modeled QPS at 32 threads
  cache_<mode>_ids_match             derived = 1.0 iff every budget returned
                                     ids identical to the uncached engine
  cache_skew_<policy>_r<records>_*   the skewed-workload head-to-head
  cache_skew_ids_match               derived = 1.0 iff both policies stayed
                                     bit-identical to uncached at all budgets
  cache_skew_adaptive_ge_static      derived = 1.0 iff adaptive hit rate >=
                                     static at every budget, > at >= 1

    PYTHONPATH=src python -m benchmarks.cache_sweep [--quick] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmarks import common
from repro.core import SearchConfig
from repro.data import make_zipfian_queries, zipf_labels

BUDGET_RECORDS = (0, 64, 256, 1024, 4096)
RECORD_BYTES = 4096  # 32-dim, degree-32 records round to one 4 KB sector
MODES = ("gate", "post", "unfiltered")

# skewed-workload knobs: rare Zipf class (~3% selectivity), hot query centers
SKEW_ALPHA = 1.1
SKEW_CENTERS = 24
N_WARM_BATCHES = 3


def sweep_cache(ctx, *, budgets=BUDGET_RECORDS, modes=MODES, search_l=100,
                policy="visit_freq"):
    engine = ctx["engine"]
    queries = ctx["queries"]
    rows = []
    for mode in modes:
        kind = None if mode == "unfiltered" else "label"
        params = None if mode == "unfiltered" else np.zeros(common.NQ, np.int32)
        base_ids = None
        ids_match = True
        for nrec in budgets:
            eng = engine.with_cache(nrec * RECORD_BYTES, policy=policy)
            out = eng.search(
                queries, filter_kind=kind, filter_params=params,
                search_config=SearchConfig(mode=mode, search_l=search_l,
                                           beam_width=8),
            )
            ids = np.asarray(out.ids)
            if base_ids is None:
                base_ids = ids
            ids_match &= bool(np.array_equal(ids, base_ids))
            ios = float(np.mean(np.asarray(out.stats.n_ios)))
            hits = float(np.mean(np.asarray(out.stats.n_cache_hits)))
            lat = eng.modeled_latency_us(out.stats)
            rows.append(dict(name=f"cache_{mode}_r{nrec}_ios", lat1_us=lat,
                             derived=ios))
            rows.append(dict(name=f"cache_{mode}_r{nrec}_hitrate", lat1_us=lat,
                             derived=hits / max(hits + ios, 1e-9)))
            rows.append(dict(name=f"cache_{mode}_r{nrec}_qps32", lat1_us=lat,
                             derived=eng.modeled_qps(out.stats)))
        rows.append(dict(name=f"cache_{mode}_ids_match", lat1_us=0.0,
                         derived=float(ids_match)))
    return rows


def skewed_setup(seed: int = 0):
    """Zipf-labelled engine + skewed selective workload on the shared graph.

    Labels are Zipf(1.0) over 10 classes; the target is the *rarest*
    class (~3% selectivity).  Queries cluster Zipf-style around a few
    centers drawn from the rare-label region — warm and eval batches are
    independent draws from the same distribution.
    """
    corpus, graph = common.cached_graph(seed=seed)
    labels = zipf_labels(common.N, common.N_CLASSES, alpha=1.0, seed=seed)
    rare = int(np.argmin(np.bincount(labels, minlength=common.N_CLASSES)))
    mask = labels == rare
    engine = common.build_engine(corpus, graph, labels=labels)
    warm_batches = [
        make_zipfian_queries(
            corpus, common.NQ, n_centers=SKEW_CENTERS, alpha=SKEW_ALPHA,
            seed=seed + 100 + i, mask=mask,
        )
        for i in range(N_WARM_BATCHES)
    ]
    eval_queries = make_zipfian_queries(
        corpus, common.NQ, n_centers=SKEW_CENTERS, alpha=SKEW_ALPHA,
        seed=seed + 999, mask=mask,
    )
    return dict(engine=engine, labels=labels, rare=rare,
                warm_batches=warm_batches, eval_queries=eval_queries)


def sweep_adaptive_vs_static(skew, *, budgets=BUDGET_RECORDS, search_l=100):
    """Head-to-head on the skewed selective workload (gate mode).

    The adaptive engine is warmed on independent same-distribution
    batches (its counters learn the filtered fetch population), then
    both policies are measured on the eval batch.  Result ids must stay
    bit-identical to the uncached engine for every policy and budget.
    """
    engine = skew["engine"]
    eval_q = skew["eval_queries"]
    tgt = np.full(eval_q.shape[0], skew["rare"], np.int32)
    cfg = SearchConfig(mode="gate", search_l=search_l, beam_width=8)

    base = engine.search(eval_q, filter_kind="label", filter_params=tgt,
                         search_config=cfg)
    base_ids = np.asarray(base.ids)
    base_ios = np.asarray(base.stats.n_ios)

    rows = []
    ids_match = True
    hit_rates = {"static": [], "adaptive": []}
    for nrec in budgets:
        for policy in ("static", "adaptive"):
            if policy == "static":
                eng = engine.with_cache(nrec * RECORD_BYTES, policy="visit_freq")
            else:
                eng = engine.with_cache(nrec * RECORD_BYTES, policy="adaptive",
                                        refresh_every=1)
                for wq in skew["warm_batches"]:
                    wt = np.full(wq.shape[0], skew["rare"], np.int32)
                    eng.warm(wq, filter_kind="label", filter_params=wt,
                             search_config=cfg)
            out = eng.search(eval_q, filter_kind="label", filter_params=tgt,
                             search_config=cfg)
            ids_match &= bool(np.array_equal(np.asarray(out.ids), base_ids))
            ids_match &= bool(np.array_equal(
                np.asarray(out.stats.n_ios) + np.asarray(out.stats.n_cache_hits),
                base_ios))
            ios = float(np.mean(np.asarray(out.stats.n_ios)))
            hits = float(np.mean(np.asarray(out.stats.n_cache_hits)))
            rate = hits / max(hits + ios, 1e-9)
            hit_rates[policy].append(rate)
            lat = eng.modeled_latency_us(out.stats)
            rows.append(dict(name=f"cache_skew_{policy}_r{nrec}_hitrate",
                             lat1_us=lat, derived=rate))
            rows.append(dict(name=f"cache_skew_{policy}_r{nrec}_qps32",
                             lat1_us=lat, derived=eng.modeled_qps(out.stats)))
    ge = all(a >= s - 1e-12 for a, s in zip(hit_rates["adaptive"], hit_rates["static"]))
    gt = any(a > s + 1e-12 for a, s in zip(hit_rates["adaptive"], hit_rates["static"]))
    rows.append(dict(name="cache_skew_ids_match", lat1_us=0.0,
                     derived=float(ids_match)))
    rows.append(dict(name="cache_skew_adaptive_ge_static", lat1_us=0.0,
                     derived=float(ge and gt)))
    return rows


def fig19_cache_sweep(ctx):
    """Registered with benchmarks/run.py as fig19."""
    return sweep_cache(ctx)


def main() -> None:
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="gate mode only, 3 budgets")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write all rows as a JSON artifact")
    args = ap.parse_args()
    ctx = common.standard_setup()
    kw = {}
    budgets = BUDGET_RECORDS
    if args.quick:
        budgets = (0, 256, 4096)
        kw = dict(budgets=budgets, modes=("gate",))
    rows = sweep_cache(ctx, **kw)
    rows += sweep_adaptive_vs_static(skewed_setup(), budgets=budgets)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['lat1_us']:.1f},{r['derived']:.4f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "cache_sweep", "rows": rows}, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)
    print("# sweep done", file=sys.stderr)


if __name__ == "__main__":
    main()
