"""Disk sweep: *measured* reads and syscalls vs the cost model's ``n_ios``.

Every other benchmark prices slow-tier I/O through the calibrated cost
model.  This one builds the standard engine, persists it to the
page-aligned index format, reloads it with ``store_tier="disk"`` and
compares, per search mode and per cache budget:

  * measured  — ``DiskRecordStore`` counter deltas (the host callback
                counts the sectors the loop requested AND what the
                coalesced reader physically did)
  * modeled   — ``sum(SearchStats.n_ios) * pages_per_record`` (what the
                cost model prices)

Two reconciliation contracts, both enforced nightly:

  * logical (exact): requested pages == modeled pages — cache hits and
    filter-gated nodes never reach the file.
  * physical (coalesced): ``unique_sectors_read <= sum(n_ios)`` (equality
    iff no round fetched the same record for two queries at once), and
    one vectored syscall per search round, plus one per hole wider than
    the gap bound, on the preadv path (``syscalls == read_rounds +
    split_gaps``) or one per merged range on the fallback (``syscalls ==
    ranges_read``).

Emits the benchmark-contract CSV ``name,us_per_call,derived``:

  disk_<mode>_r<records>_pages_q    derived = requested pages / query
  disk_<mode>_r<records>_model_q    derived = modeled pages / query
  disk_<mode>_r<records>_reconciled derived = 1.0 iff measured == modeled
  disk_<mode>_r<records>_uniq_q     derived = unique sectors read / query
  disk_<mode>_r<records>_sys_round  derived = syscalls / read round
  disk_ids_match                    derived = 1.0 iff every disk-tier run
                                    returned ids identical to in-memory
  disk_gate_lt_post                 derived = 1.0 iff gate read strictly
                                    fewer pages than post (uncached)
  disk_unique_le_ios                derived = 1.0 iff unique <= requested
                                    sectors held in every cell
  disk_syscall_contract             derived = 1.0 iff the syscall law for
                                    the store's io_mode held in every cell

``--pipeline-depth K`` additionally sweeps the software pipeline
(SearchConfig.pipeline_depth in {1, 2, 4, ...} up to K) on the
cold-cache disk tier — page cache dropped (posix_fadvise DONTNEED)
before every timed run — and emits wall-clock-per-query columns:

  pipe_gate_d<p>_wall_q       derived = measured wall-clock us / query
  pipe_gate_d<p>_reconciled   derived = 1.0 iff pages_read == sum(n_ios)
                              * pages_per_record at this depth
  pipe_ids_match              derived = 1.0 iff every depth returned ids
                              AND dists bit-identical to depth 1
  pipe_recall_match           derived = 1.0 iff pipelined recall@K ==
                              synchronous recall@K at every depth
  pipe_unique_le_ios          derived = 1.0 iff unique <= requested held
                              under overlap at every depth
  pipe_overlap_observed       derived = 1.0 iff depth > 1 runs overlapped
                              at least one read (overlapped_rounds > 0)
  pipe_speedup_d<p>           derived = wall(depth 1) / wall(depth p)

With ``--obs-json PATH`` the process telemetry registry + tracer are
enabled for the sweep and dumped to PATH, and two more contract rows
appear (the nightly ``obs-contracts`` job asserts both == 1.0):

  obs_store_reconciled    1.0 iff every mirrored ``disk.*`` registry
                          counter == the store's measured counter,
                          bit-exact (checked before any counter reset)
  obs_search_reconciled   1.0 iff registry ``search.ios{tier=disk}`` ==
                          registry ``disk.records_read`` — the
                          cross-reset form of the logical contract

    PYTHONPATH=src python -m benchmarks.disk_sweep [--quick] [--json PATH]
        [--pipeline-depth K] [--obs-json PATH]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from benchmarks import common
from repro import obs
from repro.core import GateANNEngine, SearchConfig, recall_at_k

BUDGET_RECORDS = (0, 256, 1024)
MODES = ("gate", "post", "unfiltered")


def index_path(tag: str = "") -> str:
    os.makedirs(common.CACHE_DIR, exist_ok=True)
    return os.path.join(common.CACHE_DIR, f"index_{tag}{common.N}_{common.DIM}.gann")


def sweep_disk(ctx, *, budgets=BUDGET_RECORDS, modes=MODES, search_l=100):
    engine = ctx["engine"]
    queries = ctx["queries"]
    nq = queries.shape[0]
    path = index_path()
    engine.save(path)
    print(f"# saved index: {os.path.getsize(path)} B", file=sys.stderr)

    # one load: all budgets re-wrap the same DiskRecordStore (same file
    # handle, same measured counters, same jit traces per mode)
    disk_engine = GateANNEngine.load(path, store_tier="disk")
    store = disk_engine.record_store
    print(f"# disk io_mode: {store.io_mode}", file=sys.stderr)

    rows = []
    ids_match = True
    unique_ok = True
    syscall_ok = True
    gate_pages = post_pages = None
    for mode in modes:
        kind = None if mode == "unfiltered" else "label"
        params = None if mode == "unfiltered" else np.zeros(nq, np.int32)
        cfg = SearchConfig(mode=mode, search_l=search_l, beam_width=8)
        mem_out = engine.search(queries, filter_kind=kind, filter_params=params,
                                search_config=cfg)
        mem_ids = np.asarray(mem_out.ids)
        for nrec in budgets:
            # budgets are in *records*; the store knows its sector size
            disk = disk_engine.with_cache(nrec * store.sector_bytes)
            before = store.io_counters()
            out = disk.search(queries, filter_kind=kind, filter_params=params,
                              search_config=cfg)
            ids = np.asarray(out.ids)  # materialize => all callbacks ran
            after = store.io_counters()
            d = {k: after[k] - before[k] for k in after}
            measured = d["pages_read"]
            modeled = int(np.sum(np.asarray(out.stats.n_ios))) * store.pages_per_record
            ids_match &= bool(np.array_equal(ids, mem_ids))
            # physical contracts: dedup never reads more than requested;
            # the preadv path spends one vectored syscall per round (per
            # touched segment) plus one per unbridged hole, the pread
            # fallback one per merged range
            unique_ok &= d["unique_sectors_read"] <= d["records_read"]
            if store.io_mode == "preadv":
                # on this (unsharded) index: one call per round, plus one
                # per hole wider than the gap bound
                syscall_ok &= d["syscalls"] == d["read_rounds"] + d["split_gaps"]
            elif store.io_mode == "pread":
                syscall_ok &= d["syscalls"] == d["ranges_read"]
            else:  # gather oracle issues no explicit syscalls
                syscall_ok &= d["syscalls"] == 0
            if mode == "gate" and nrec == 0:
                gate_pages = measured
            if mode == "post" and nrec == 0:
                post_pages = measured
            lat = disk.modeled_latency_us(out.stats)
            rows.append(dict(name=f"disk_{mode}_r{nrec}_pages_q", lat1_us=lat,
                             derived=measured / nq))
            rows.append(dict(name=f"disk_{mode}_r{nrec}_model_q", lat1_us=lat,
                             derived=modeled / nq))
            rows.append(dict(name=f"disk_{mode}_r{nrec}_reconciled", lat1_us=0.0,
                             derived=float(measured == modeled)))
            rows.append(dict(name=f"disk_{mode}_r{nrec}_uniq_q", lat1_us=lat,
                             derived=d["unique_sectors_read"] / nq))
            rows.append(dict(name=f"disk_{mode}_r{nrec}_sys_round", lat1_us=0.0,
                             derived=d["syscalls"] / max(d["read_rounds"], 1)))
    rows.append(dict(name="disk_ids_match", lat1_us=0.0, derived=float(ids_match)))
    if gate_pages is not None and post_pages is not None:
        rows.append(dict(name="disk_gate_lt_post", lat1_us=0.0,
                         derived=float(gate_pages < post_pages)))
    rows.append(dict(name="disk_unique_le_ios", lat1_us=0.0,
                     derived=float(unique_ok)))
    rows.append(dict(name="disk_syscall_contract", lat1_us=0.0,
                     derived=float(syscall_ok)))
    reg = obs.default_registry()
    if reg.enabled:
        # telemetry-vs-measured contract, checked BEFORE any
        # reset_io_counters (sweep_pipeline resets per repeat; registry
        # counters are monotonic and would stop matching the store's):
        # every mirrored counter must agree bit-exactly with the store
        c = store.io_counters()
        mirrored = ("records_read", "pages_read", "bytes_read",
                    "unique_sectors_read", "ranges_read", "syscalls",
                    "fetch_rounds", "read_rounds", "split_gaps")
        ok = all(reg.family_total(f"disk.{k}") == c[k] for k in mirrored)
        rows.append(dict(name="obs_store_reconciled", lat1_us=0.0,
                         derived=float(ok)))
    return rows


def sweep_pipeline(ctx, *, max_depth=4, search_l=100, repeats=3):
    """Software-pipeline sweep on the cold-cache disk tier.

    For each depth the page cache is dropped before every timed run, so
    each round's ``preadv`` pays a real storage read — exactly the regime
    the submit/drain overlap is built for.  Results must be bit-identical
    to depth 1 (the synchronous loop) and the logical counters must keep
    reconciling exactly; only wall-clock may change.
    """
    engine = ctx["engine"]
    queries = ctx["queries"]
    nq = queries.shape[0]
    path = index_path()
    if not os.path.exists(path):
        engine.save(path)
    disk_engine = GateANNEngine.load(path, store_tier="disk")
    store = disk_engine.record_store
    depths = [d for d in (1, 2, 4, 8, 16) if d <= max_depth]
    if max_depth not in depths:
        depths.append(max_depth)
    kind, params = "label", np.zeros(nq, np.int32)

    rows = []
    walls = {}
    ref_ids = ref_dists = None
    ids_match = recall_match = unique_ok = True
    overlap_seen = True
    for depth in depths:
        cfg = SearchConfig(mode="gate", search_l=search_l, beam_width=8,
                           pipeline_depth=depth)
        run = lambda: disk_engine.search(  # noqa: E731
            queries, filter_kind=kind, filter_params=params,
            search_config=cfg,
        )
        out = run()  # compile + warm the trace before timing
        np.asarray(out.ids)
        best = float("inf")
        for _ in range(repeats):
            store.drop_page_cache()
            store.reset_io_counters()
            t0 = time.perf_counter()
            out = run()
            ids = np.asarray(out.ids)  # materialize => all reads retired
            dists = np.asarray(out.dists)
            best = min(best, time.perf_counter() - t0)
        c = store.io_counters()
        measured = c["pages_read"]
        modeled = int(np.sum(np.asarray(out.stats.n_ios))) * store.pages_per_record
        unique_ok &= c["unique_sectors_read"] <= c["records_read"]
        if depth == 1:
            ref_ids, ref_dists = ids, dists
        else:
            ids_match &= bool(np.array_equal(ids, ref_ids))
            ids_match &= bool(np.array_equal(dists, ref_dists))
            # recall against the synchronous ids as ground truth — equality
            # of the id sets is the nightly "pipelined recall ==
            # synchronous recall" contract (bit-identity implies it; this
            # row keeps the contract explicit even if ordering ever drifts)
            recall_match &= recall_at_k(ids, ref_ids, k=10) == 1.0
            overlap_seen &= c["overlapped_rounds"] > 0
        walls[depth] = best
        wall_q = best * 1e6 / nq
        rows.append(dict(name=f"pipe_gate_d{depth}_wall_q", lat1_us=wall_q,
                         derived=wall_q))
        rows.append(dict(name=f"pipe_gate_d{depth}_reconciled", lat1_us=0.0,
                         derived=float(measured == modeled)))
        print(f"# pipeline depth {depth}: {wall_q:.0f} us/q "
              f"(inflight_max {c['inflight_depth_max']}, "
              f"overlapped {c['overlapped_rounds']})", file=sys.stderr)
    rows.append(dict(name="pipe_ids_match", lat1_us=0.0,
                     derived=float(ids_match)))
    rows.append(dict(name="pipe_recall_match", lat1_us=0.0,
                     derived=float(recall_match)))
    rows.append(dict(name="pipe_unique_le_ios", lat1_us=0.0,
                     derived=float(unique_ok)))
    rows.append(dict(name="pipe_overlap_observed", lat1_us=0.0,
                     derived=float(overlap_seen)))
    for depth in depths[1:]:
        rows.append(dict(name=f"pipe_speedup_d{depth}", lat1_us=0.0,
                         derived=walls[1] / max(walls[depth], 1e-9)))
    return rows


def main() -> None:
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="gate+post only, budgets (0, 256)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write all rows as a JSON artifact")
    ap.add_argument("--pipeline-depth", type=int, metavar="K", default=0,
                    help="also sweep SearchConfig.pipeline_depth up to K "
                         "on the cold-cache disk tier (0 = skip)")
    ap.add_argument("--obs-json", metavar="PATH", default=None,
                    help="enable telemetry for the sweep and dump the "
                         "registry + span rings as a JSON snapshot")
    args = ap.parse_args()
    if args.obs_json:
        obs.enable()
        obs.trace.enable()
    ctx = common.standard_setup()
    kw = {}
    if args.quick:
        kw = dict(budgets=(0, 256), modes=("gate", "post"))
    rows = sweep_disk(ctx, **kw)
    if args.pipeline_depth > 0:
        rows += sweep_pipeline(ctx, max_depth=args.pipeline_depth)
    reg = obs.default_registry()
    if reg.enabled:
        # cross-reset contract: the registry is monotonic, so the
        # search-side and store-side *registry* totals must agree even
        # though sweep_pipeline reset the store's own counters
        rows.append(dict(
            name="obs_search_reconciled", lat1_us=0.0,
            derived=float(
                reg.family_total("search.ios", tier="disk")
                == reg.family_total("disk.records_read")
            ),
        ))
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['lat1_us']:.1f},{r['derived']:.4f}")
    if args.obs_json:
        obs.export.write_obs_json(common.root_artifact(args.obs_json))
        print(f"# wrote {args.obs_json}", file=sys.stderr)
    if args.json:
        path = common.write_bench_json(args.json, "disk_sweep", rows)
        print(f"# wrote {path}", file=sys.stderr)
    print("# sweep done", file=sys.stderr)


if __name__ == "__main__":
    main()
