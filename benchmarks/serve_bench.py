"""SLO load generator: closed/open-loop multi-tenant serving benchmark.

The first end-to-end *serving* number in the repo: real concurrent
clients, Zipfian tenant skew, the disk-tier engine behind the async
``ServeFrontend``, and tail latency you can put an SLO on.  The two
loops follow the mlperf-inference convention:

  * **closed loop** — ``--clients`` threads each keep exactly one
    request in flight (submit, wait, repeat).  Measures the server's
    sustainable throughput and the latency under that self-limiting
    load.  Latency = submit -> result, measured by the client.
  * **open loop** — a Poisson arrival process at ``--qps`` submits
    regardless of completions (the "LON" in mlperf terms).  Measures
    tail behaviour under a fixed offered load, where queueing shows up
    in the tail.  Latency = *scheduled arrival* -> result, so scheduler
    lag and admission wait count against the server, not the client.

Tenants are label namespaces (tenant ``i`` -> ``label == i``) drawn
from a Zipf(``--alpha``) popularity distribution — the skew is what
makes per-tenant admission limits and the adaptive cache's per-filter
partitions earn their keep.  Requests run through the pipelined disk
path (``--pipeline-depth``, default 2), so this is also the concurrency
hammer for the submit/drain machinery.

Emits the benchmark-contract CSV ``name,us_per_call,derived`` and (by
default) the ``BENCH_serve.json`` artifact.  Contract rows nightly
asserts on:

  serve_<loop>_p50_ms / p99_ms / p999_ms   latency percentiles (ms)
  serve_<loop>_qps                         achieved completions / s
  serve_open_offered_qps                   the open loop's target rate
  serve_t<i>_ios_q                         per-tenant slow-tier reads /
                                           query (the I/O attribution)
  serve_recall_parity   1.0 iff every served result == the direct
                        ``engine.search`` ids for that (tenant, query)
  serve_reconciled      1.0 iff measured reads == served + padding
                        (``reconcile_drift == 0``) after both loops
  serve_abandoned       abandoned pipelined tokens (0.0 on happy path)
  serve_rejected        admission rejections across both loops

    PYTHONPATH=src python -m benchmarks.serve_bench [--quick]
        [--json PATH] [--obs-json PATH] [--qps F] [--clients N]
        [--requests N] [--tenants N] [--alpha F] [--pipeline-depth K]
        [--soak MINUTES] [--soak-qps F]
        [--fault-eio P] [--fault-policy POLICY]

``--soak MINUTES`` replaces the closed/open pair with a fixed-rate
(deterministic arrivals, not Poisson) open loop that runs for the
given wall time and reports a per-minute p99 series plus a drift row
(last-minute p99 vs first-minute p99) — the latency-stability soak the
nightly chaos lane runs under fault injection.  ``--fault-eio P``
attaches a ``FaultPlan(p_eio=P)`` to the disk tier and
``--fault-policy`` picks the front end's resilience mode
(``fail`` | ``degrade`` | ``retry_then_degrade``); recall parity is
only asserted (and only emitted) when no faults are injected.

``BENCH_serve.json`` is always written (repo-root-anchored, with a
``schema_version`` field); ``--obs-json`` additionally dumps the
process and serve-frontend telemetry registries.
"""
from __future__ import annotations

import argparse
import os
import sys
import threading
import time

import numpy as np

from benchmarks import common
from repro import obs
from repro.core import GateANNEngine, SearchConfig
from repro.serve import AdmissionError, RAGServer, ServeFrontend, TenantSpec
from repro.store import FaultPlan

RECORD = 4096  # one record sector


def index_path() -> str:
    os.makedirs(common.CACHE_DIR, exist_ok=True)
    return os.path.join(
        common.CACHE_DIR, f"index_{common.N}_{common.DIM}.gann"
    )


def zipf_probs(n: int, alpha: float) -> np.ndarray:
    p = (np.arange(1, n + 1, dtype=np.float64)) ** -alpha
    return p / p.sum()


def make_frontend(ctx, *, n_tenants, pipeline_depth, max_inflight=64,
                  fault_eio=0.0, fault_policy="fail", fault_seed=0):
    """Disk-tier engine + adaptive cache behind the async front end."""
    path = index_path()
    if not os.path.exists(path):
        ctx["engine"].save(path)
    faults = None
    if fault_eio > 0.0:
        faults = FaultPlan(seed=fault_seed, p_eio=fault_eio)
    engine = GateANNEngine.load(
        path, store_tier="disk", cache_budget_bytes=512 * RECORD,
        cache_policy="adaptive", refresh_every=4, faults=faults,
    )
    rag = RAGServer(
        engine=engine, cfg=None, params=None, layout=None,
        passage_tokens=np.zeros((common.N, 4), np.int32),
        search_config=SearchConfig(mode="gate", search_l=50, beam_width=8,
                                   pipeline_depth=pipeline_depth),
        bucket_sizes=(8, 16, 32),
    )
    tenants = [
        TenantSpec(f"t{i}", "label", np.int32(i), max_inflight=max_inflight)
        for i in range(n_tenants)
    ]
    srv = ServeFrontend(rag, tenants, max_batch=32, batch_window_s=0.002,
                        fault_policy=fault_policy)
    return engine, rag, srv


def run_closed(srv, queries, schedule, *, n_clients):
    """Each client keeps one request in flight; FIFO over the schedule."""
    lats, served, rejected = [], [], [0]
    lock = threading.Lock()
    cursor = [0]

    def client():
        while True:
            with lock:
                i = cursor[0]
                if i >= len(schedule):
                    return
                cursor[0] += 1
            tenant, qi = schedule[i]
            t0 = time.perf_counter()
            try:
                h = srv.submit(tenant, queries[qi], timeout=30.0)
                ids = h.result(timeout=120.0)
            except AdmissionError:
                with lock:
                    rejected[0] += 1
                continue
            lat = time.perf_counter() - t0
            with lock:
                lats.append(lat)
                served.append((tenant, qi, ids))

    t_start = time.perf_counter()
    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    return np.asarray(lats), served, len(lats) / max(wall, 1e-9), rejected[0]


def run_open(srv, queries, schedule, *, qps, seed):
    """Poisson arrivals at ``qps``; latency counts from scheduled arrival."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / qps, size=len(schedule))
    arrivals = np.cumsum(gaps)
    handles, served, rejected = [], [], 0
    t_start = time.perf_counter()
    for (tenant, qi), t_arr in zip(schedule, arrivals):
        now = time.perf_counter() - t_start
        if t_arr > now:
            time.sleep(t_arr - now)
        t_sched = t_start + t_arr
        try:
            h = srv.submit(tenant, queries[qi], timeout=5.0)
        except AdmissionError:
            rejected += 1
            continue
        lag = time.perf_counter() - t_sched  # scheduler + admission wait
        handles.append((tenant, qi, h, lag))
    lats = []
    for tenant, qi, h, lag in handles:
        ids = h.result(timeout=120.0)
        served.append((tenant, qi, ids))
        lats.append(lag + h.trace.total)
    wall = time.perf_counter() - t_start
    return np.asarray(lats), served, len(lats) / max(wall, 1e-9), rejected


def run_soak(srv, queries, schedule, *, qps, minutes, seed):
    """Fixed-rate open loop for ``minutes`` of wall time: arrival i is
    scheduled at exactly ``i / qps`` seconds, latency counts from that
    scheduled instant, and completions are bucketed by arrival minute
    so tail drift over the run is visible as a series, not an average."""
    del seed  # arrivals are deterministic; the schedule carries the mix
    handles, served, rejected = [], [], 0
    horizon = minutes * 60.0
    t_start = time.perf_counter()
    i = 0
    while True:
        t_arr = i / qps
        if t_arr >= horizon:
            break
        tenant, qi = schedule[i % len(schedule)]
        now = time.perf_counter() - t_start
        if t_arr > now:
            time.sleep(t_arr - now)
        t_sched = t_start + t_arr
        try:
            h = srv.submit(tenant, queries[qi], timeout=5.0)
        except AdmissionError:
            rejected += 1
            i += 1
            continue
        lag = time.perf_counter() - t_sched
        handles.append((tenant, qi, h, lag, int(t_arr // 60)))
        i += 1
    lats, minutes_of = [], []
    for tenant, qi, h, lag, minute in handles:
        ids = h.result(timeout=120.0)
        served.append((tenant, qi, ids))
        lats.append(lag + h.trace.total)
        minutes_of.append(minute)
    wall = time.perf_counter() - t_start
    return (np.asarray(lats), np.asarray(minutes_of), served,
            len(lats) / max(wall, 1e-9), rejected)


def soak_rows(lats_s, minutes_of, qps_achieved, offered):
    rows = pctl_rows("soak", lats_s, qps_achieved)
    rows.append(dict(name="serve_soak_offered_qps", lat1_us=0.0,
                     derived=offered))
    p99s = []
    for m in range(int(minutes_of.max()) + 1 if minutes_of.size else 0):
        sel = lats_s[minutes_of == m]
        if sel.size == 0:
            continue
        p99 = float(np.percentile(sel * 1e3, 99))
        p99s.append(p99)
        rows.append(dict(name=f"serve_soak_p99_m{m}_ms", lat1_us=p99 * 1e3,
                         derived=p99))
    # drift: last-minute p99 relative to the first — flat is ~1.0; a
    # leak (queue growth, cache thrash, fd exhaustion) trends upward
    drift = p99s[-1] / max(p99s[0], 1e-9) if len(p99s) >= 2 else 1.0
    rows.append(dict(name="serve_soak_p99_drift", lat1_us=0.0,
                     derived=drift))
    return rows


def check_parity(engine, rag, queries, served) -> float:
    """Served ids vs direct ``engine.search`` for every (tenant, query)."""
    by_tenant: dict = {}
    for tenant, qi, ids in served:
        by_tenant.setdefault(tenant, {}).setdefault(qi, []).append(ids)
    ok = total = 0
    for tenant, qmap in sorted(by_tenant.items()):
        qis = sorted(qmap)
        label = np.full(len(qis), int(tenant[1:]), np.int32)
        out = engine.search(
            queries[qis], filter_kind="label", filter_params=label,
            search_config=rag.search_config,
        )
        direct = np.asarray(out.ids)[:, : rag.search_config.result_k]
        for row, qi in enumerate(qis):
            for ids in qmap[qi]:
                total += 1
                ok += int(np.array_equal(ids, direct[row]))
    return ok / max(total, 1)


def pctl_rows(tag: str, lats_s: np.ndarray, qps: float):
    p50, p99, p999 = np.percentile(lats_s * 1e3, [50, 99, 99.9])
    return [
        dict(name=f"serve_{tag}_p50_ms", lat1_us=p50 * 1e3, derived=p50),
        dict(name=f"serve_{tag}_p99_ms", lat1_us=p99 * 1e3, derived=p99),
        dict(name=f"serve_{tag}_p999_ms", lat1_us=p999 * 1e3, derived=p999),
        dict(name=f"serve_{tag}_qps", lat1_us=0.0, derived=qps),
    ]


def main() -> None:
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small request counts (CI smoke)")
    ap.add_argument("--json", metavar="PATH", default="BENCH_serve.json",
                    help="artifact path (always written; relative paths "
                         "anchor at the repo root)")
    ap.add_argument("--obs-json", metavar="PATH", default=None,
                    help="also dump the telemetry registries (process + "
                         "serve sections) as a JSON snapshot")
    ap.add_argument("--qps", type=float, default=40.0,
                    help="open-loop offered load (Poisson arrivals)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=600,
                    help="requests per loop (closed and open)")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=1.1,
                    help="Zipf skew across tenants")
    ap.add_argument("--pipeline-depth", type=int, default=2)
    ap.add_argument("--soak", type=float, metavar="MINUTES", default=0.0,
                    help="run a fixed-rate soak for this many minutes "
                         "INSTEAD of the closed/open pair")
    ap.add_argument("--soak-qps", type=float, default=25.0,
                    help="the soak loop's fixed arrival rate")
    ap.add_argument("--fault-eio", type=float, default=0.0,
                    help="per-read-call EIO probability injected into the "
                         "disk tier (chaos lane)")
    ap.add_argument("--fault-policy", default="fail",
                    choices=("fail", "degrade", "retry_then_degrade"),
                    help="front-end resilience mode when faults fire")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    n_requests = 120 if args.quick else args.requests
    if args.obs_json:
        obs.enable()
        obs.trace.enable()

    ctx = common.standard_setup()
    queries = ctx["queries"]
    engine, rag, srv = make_frontend(
        ctx, n_tenants=args.tenants, pipeline_depth=args.pipeline_depth,
        fault_eio=args.fault_eio, fault_policy=args.fault_policy,
        fault_seed=args.seed,
    )
    rng = np.random.default_rng(args.seed)
    probs = zipf_probs(args.tenants, args.alpha)

    def make_schedule(n):
        ts = rng.choice(args.tenants, size=n, p=probs)
        qs = rng.integers(0, queries.shape[0], size=n)
        return [(f"t{t}", int(q)) for t, q in zip(ts, qs)]

    rows = []
    try:
        # warm the jit traces (one burst per bucket size) so compile time
        # lands here, not in the measured tails
        for burst in (8, 16, 32):
            hs = [srv.submit(f"t{i % args.tenants}", queries[i % queries.shape[0]],
                             timeout=30.0) for i in range(burst)]
            for h in hs:
                h.result(timeout=300.0)
        print("# warmup done", file=sys.stderr)

        if args.soak > 0.0:
            n_sched = max(int(args.soak_qps * args.soak * 60) + 1, 1)
            lats_s, minutes_of, served_all, qps_s, rej_total = run_soak(
                srv, queries, make_schedule(n_sched), qps=args.soak_qps,
                minutes=args.soak, seed=args.seed + 1,
            )
            print(f"# soak: {len(lats_s)} reqs over {args.soak:.2f} min, "
                  f"offered {args.soak_qps:.1f} achieved {qps_s:.1f} qps",
                  file=sys.stderr)
            rows += soak_rows(lats_s, minutes_of, qps_s, args.soak_qps)
        else:
            lats_c, served_c, qps_c, rej_c = run_closed(
                srv, queries, make_schedule(n_requests),
                n_clients=args.clients
            )
            print(f"# closed: {len(lats_c)} reqs, {qps_c:.1f} qps",
                  file=sys.stderr)
            rows += pctl_rows("closed", lats_c, qps_c)

            lats_o, served_o, qps_o, rej_o = run_open(
                srv, queries, make_schedule(n_requests), qps=args.qps,
                seed=args.seed + 1,
            )
            print(f"# open: {len(lats_o)} reqs, offered {args.qps:.1f} "
                  f"achieved {qps_o:.1f} qps", file=sys.stderr)
            rows += pctl_rows("open", lats_o, qps_o)
            rows.append(dict(name="serve_open_offered_qps", lat1_us=0.0,
                             derived=args.qps))
            served_all = served_c + served_o
            rej_total = rej_c + rej_o

        # parity vs direct search only holds fault-free: with faults
        # injected, the direct rerun draws its own (different) faults
        parity = (check_parity(engine, rag, queries, served_all)
                  if args.fault_eio == 0.0 else None)
        rep = srv.io_report()
        if args.obs_json:
            payload = obs.export.write_obs_json(
                common.root_artifact(args.obs_json),
                sections={"serve": (srv.metrics, srv.tracer)},
            )
            n_fam = len(payload["serve"]["families"])
            print(f"# wrote {args.obs_json} ({n_fam} serve families)",
                  file=sys.stderr)
    finally:
        srv.close()

    for name in sorted(rep["per_tenant"]):
        ts = rep["per_tenant"][name]
        rows.append(dict(name=f"serve_{name}_ios_q", lat1_us=0.0,
                         derived=ts["ios"] / max(ts["queries"], 1)))
        rows.append(dict(name=f"serve_{name}_share", lat1_us=0.0,
                         derived=ts["queries"] / max(rep["completed"], 1)))
    for span, mean_s in rep["spans_mean_s"].items():
        rows.append(dict(name=f"serve_span_{span}_ms", lat1_us=mean_s * 1e6,
                         derived=mean_s * 1e3))
    if parity is not None:
        rows.append(dict(name="serve_recall_parity", lat1_us=0.0,
                         derived=parity))
    rows.append(dict(name="serve_reconciled", lat1_us=0.0,
                     derived=float(rep.get("reconcile_drift", 0) == 0)))
    rows.append(dict(name="serve_abandoned", lat1_us=0.0,
                     derived=float(rep.get("abandoned_tokens", 0))))
    rows.append(dict(name="serve_rejected", lat1_us=0.0,
                     derived=float(rej_total)))
    if args.fault_eio > 0.0:
        rows.append(dict(name="serve_fault_eio", lat1_us=0.0,
                         derived=args.fault_eio))
        rows.append(dict(name="serve_degraded", lat1_us=0.0,
                         derived=float(rep.get("degraded", 0))))
        rows.append(dict(name="serve_deadline_shed", lat1_us=0.0,
                         derived=float(rep.get("deadline_shed", 0))))
        rows.append(dict(name="serve_failed", lat1_us=0.0,
                         derived=float(rep.get("failed", 0))))
    rows.append(dict(name="serve_mean_batch", lat1_us=0.0,
                     derived=rep["mean_batch_size"]))
    rows.append(dict(name="serve_cache_hit_rate", lat1_us=0.0,
                     derived=rep["cache_hit_rate"]))

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['lat1_us']:.1f},{r['derived']:.4f}")
    # the JSON artifact is unconditional: nightly uploads BENCH_serve.json
    # from the repo root, so an empty --json falls back to the default
    path = common.write_bench_json(
        args.json or "BENCH_serve.json", "serve_bench", rows
    )
    print(f"# wrote {path}", file=sys.stderr)
    print("# serve bench done", file=sys.stderr)


if __name__ == "__main__":
    main()
