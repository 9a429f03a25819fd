"""Chaos matrix: fault-rate x policy sweep asserting graceful degradation.

The nightly resilience lane.  Each cell loads the cached disk index
with a seeded probabilistic ``FaultPlan`` (EIO on the raw read calls),
runs the full filtered search, and scores recall@10 against the exact
filtered ground truth.  The sweep crosses:

  * ``p_eio``  — 0 (baseline), 0.5%, 1%, 2% per read call
  * policy     — ``degrade`` (no retries) vs ``retry_then_degrade``
                 (3 bounded retries, then degrade)
  * mode       — ``gate`` and ``post`` filtered-search modes
  * depth      — pipeline depth 1 (sync) and 2 (overlapped)

Faults degrade failed read groups to tunneled records (+inf sentinel,
adjacency-sidecar neighbors), so the contract is *graceful decline*,
not parity: recall may drop with fault rate but must do so smoothly
and stay bounded.  Contract rows nightly asserts on:

  chaos_recall_floor    min recall@10 over every faulted cell
  chaos_drop_p1         worst (baseline - faulted) recall drop at 1%
                        EIO — the "no mode loses more than 0.05" gate
  chaos_monotone        1.0 iff recall declines (near-)monotonically in
                        p_eio for every (mode, depth, policy) series
  chaos_no_token_leak   1.0 iff abandoned_tokens == 0 after every cell
  chaos_reconciled      1.0 iff records_read == sum(n_ios) in every
                        cell (requested-records accounting under faults)
  chaos_degraded_total  degraded record slots across the whole matrix
  chaos_serve_ok        1.0 iff the serve hammer under 1% EIO with
                        retry_then_degrade completes every request

    PYTHONPATH=src python -m benchmarks.chaos_matrix [--quick]
        [--json PATH] [--seed N]

Writes ``BENCH_chaos.json`` (repo-root-anchored).  Deterministic for a
fixed ``--seed``: every injector decision is a pure function of
(seed, call index), so a red nightly replays exactly.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

from benchmarks import common
from repro.core import GateANNEngine, SearchConfig, recall_at_k
from repro.store import FaultPlan

RECORD = 4096

P_EIO = (0.0, 0.005, 0.01, 0.02)
POLICIES = ("degrade", "retry_then_degrade")
MODES = ("gate", "post")
DEPTHS = (1, 2)

# probabilistic faults jitter recall cell-to-cell; "monotone" means no
# big recovery at a higher fault rate, not strict ordering of noise
MONOTONE_TOL = 0.02


def index_path() -> str:
    os.makedirs(common.CACHE_DIR, exist_ok=True)
    return os.path.join(
        common.CACHE_DIR, f"index_{common.N}_{common.DIM}.gann"
    )


def load_cell_engine(path: str, *, p_eio: float, policy: str, seed: int):
    faults = FaultPlan(seed=seed, p_eio=p_eio) if p_eio > 0 else None
    return GateANNEngine.load(
        path, store_tier="disk", faults=faults,
        io_on_error="degrade",
        io_retries=3 if policy == "retry_then_degrade" else 0,
        io_retry_backoff_s=5e-4,
    )


def run_cell(path, queries, gt, *, mode, depth, p_eio, policy, seed,
             search_l=100):
    eng = load_cell_engine(path, p_eio=p_eio, policy=policy, seed=seed)
    store = eng.record_store
    cfg = SearchConfig(mode=mode, search_l=search_l, beam_width=8,
                       pipeline_depth=depth)
    # one search per query, not one batched call: reads for a batch
    # coalesce into a handful of preadv calls, so per-call fault
    # probabilities would barely fire and a single EIO would degrade a
    # whole round for every query at once.  Per-query searches give
    # ~fetch_rounds calls *per query* (the serving-path granularity)
    # and keep each degraded group one query's beam.
    ids = []
    n_ios = n_deg = 0
    for q in np.asarray(queries):
        out = eng.search(q[None, :], filter_kind="label",
                         filter_params=np.zeros(1, np.int32),
                         search_config=cfg)
        ids.append(np.asarray(out.ids)[0])
        # materialize stats before reading counters: the ordered
        # io_callbacks only complete when the stats arrays do
        n_ios += int(np.asarray(out.stats.n_ios).sum())
        n_deg += int(np.asarray(out.stats.n_degraded).sum())
    rec = recall_at_k(np.stack(ids), gt, 10)
    d = store.io_counters()
    f = store.fault_counters()
    cell = dict(
        recall=float(rec), n_ios=n_ios, n_degraded=n_deg,
        records_read=d["records_read"], abandoned=d["abandoned_tokens"],
        degraded_records=d["degraded_records"],
        retried=d["retried_ios"], exhausted=d["retry_exhausted"],
        read_calls=f.get("read_calls", 0), faults=f.get("faults_injected", 0),
    )
    store.close()
    return cell


def serve_hammer(ctx, *, p_eio, seed, n_requests=64):
    """The serving front end under probabilistic faults: every request
    must complete (retry_then_degrade absorbs what retries cannot)."""
    from benchmarks.serve_bench import make_frontend

    queries = ctx["queries"]
    engine, rag, srv = make_frontend(
        ctx, n_tenants=2, pipeline_depth=2,
        fault_eio=p_eio, fault_policy="retry_then_degrade",
        fault_seed=seed,
    )
    try:
        handles = [
            srv.submit(f"t{i % 2}", queries[i % queries.shape[0]],
                       timeout=30.0)
            for i in range(n_requests)
        ]
        results = [h.result(timeout=300.0) for h in handles]
        rep = srv.io_report()
    finally:
        srv.close()
    ok = (all(r is not None for r in results)
          and rep["failed"] == 0
          and rep["completed"] == n_requests
          and rep.get("abandoned_tokens", 0) == 0)
    return float(ok), rep


def main() -> None:
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small matrix (CI smoke): gate mode, depth 1, "
                         "p in {0, 0.01}")
    ap.add_argument("--json", metavar="PATH", default="BENCH_chaos.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--search-l", type=int, default=100)
    args = ap.parse_args()

    p_eio = (0.0, 0.01) if args.quick else P_EIO
    modes = ("gate",) if args.quick else MODES
    depths = (1,) if args.quick else DEPTHS

    ctx = common.standard_setup()
    queries, gt = ctx["queries"], ctx["gt"]
    path = index_path()
    if not os.path.exists(path):
        ctx["engine"].save(path)

    rows = []
    series: dict = {}
    no_leak = reconciled = True
    degraded_total = 0
    floor = 1.0
    drop_p1 = 0.0
    for mode in modes:
        for depth in depths:
            for policy in POLICIES:
                baseline = None
                for p in p_eio:
                    cell = run_cell(
                        path, queries, gt, mode=mode, depth=depth,
                        p_eio=p, policy=policy, seed=args.seed,
                        search_l=args.search_l,
                    )
                    tag = (f"chaos_{mode}_d{depth}_{policy}_"
                           f"p{p:g}".replace(".", "_"))
                    rows.append(dict(name=tag, lat1_us=0.0,
                                     derived=cell["recall"]))
                    print(f"# {tag}: recall={cell['recall']:.4f} "
                          f"calls={cell['read_calls']} "
                          f"faults={cell['faults']} "
                          f"degraded={cell['degraded_records']} "
                          f"retried={cell['retried']}", file=sys.stderr)
                    series.setdefault((mode, depth, policy), []).append(
                        (p, cell["recall"]))
                    no_leak &= cell["abandoned"] == 0
                    reconciled &= cell["records_read"] == cell["n_ios"]
                    degraded_total += cell["degraded_records"]
                    if p == 0.0:
                        baseline = cell["recall"]
                    else:
                        floor = min(floor, cell["recall"])
                    if p == 0.01 and baseline is not None:
                        drop_p1 = max(drop_p1, baseline - cell["recall"])

    monotone = True
    for pts in series.values():
        pts = sorted(pts)
        for (p0, r0), (p1, r1) in zip(pts, pts[1:]):
            # a higher fault rate may not *gain* recall beyond noise
            monotone &= r1 <= r0 + MONOTONE_TOL

    serve_ok, rep = serve_hammer(ctx, p_eio=0.01, seed=args.seed + 1,
                                 n_requests=32 if args.quick else 64)
    print(f"# serve hammer: ok={serve_ok} completed={rep['completed']} "
          f"degraded={rep.get('degraded', 0)}", file=sys.stderr)

    rows.append(dict(name="chaos_recall_floor", lat1_us=0.0, derived=floor))
    rows.append(dict(name="chaos_drop_p1", lat1_us=0.0, derived=drop_p1))
    rows.append(dict(name="chaos_monotone", lat1_us=0.0,
                     derived=float(monotone)))
    rows.append(dict(name="chaos_no_token_leak", lat1_us=0.0,
                     derived=float(no_leak)))
    rows.append(dict(name="chaos_reconciled", lat1_us=0.0,
                     derived=float(reconciled)))
    rows.append(dict(name="chaos_degraded_total", lat1_us=0.0,
                     derived=float(degraded_total)))
    rows.append(dict(name="chaos_serve_ok", lat1_us=0.0, derived=serve_ok))

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['lat1_us']:.1f},{r['derived']:.4f}")
    out = common.write_bench_json(args.json or "BENCH_chaos.json",
                                  "chaos_matrix", rows)
    print(f"# wrote {out}", file=sys.stderr)
    print("# chaos matrix done", file=sys.stderr)


if __name__ == "__main__":
    main()
