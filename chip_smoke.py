#!/usr/bin/env python3
"""On-chip smoke test: the filtered-search serving path on a TPU.

    python chip_smoke.py              # one chip: build, save, load, serve, kernels
    python chip_smoke.py --chips 4    # four chips: the sharded retrieve step only

The deployment is BIGANN-shaped: N 128-d vectors with uint8-range values
stored as f32 (``make_bigann_like``), 10 uniform labels, and an index
built with degree 32, L_build 64, 32 PQ chunks and an R_max of 16 (half
of each adjacency row in the in-memory neighbor store).  Searches use
W=8 and L=256; gate, the served mode, L=512.
Everything is generated from ``--seed``; nothing is read from
``results/``.

One chip runs the main path through the entry points a user calls, in
one process (the disk tier's readers and the frontend's dispatcher are
threads):

  1. device   — the TPU JAX sees, and the compile-cache directory
  2. build    — ``GateANNEngine.build`` in the memory tier, timed
  3. disk     — ``save``, ``GateANNEngine.load(store_tier="disk")`` with
                the adaptive cache, page cache dropped
  4. serve    — ``ServeFrontend`` with 4 label tenants, requests from
                concurrent client threads; served ids equal direct
                memory-tier search, measured reads reconcile, recall@10
                against exact filtered brute force: gate at L=512
                reaches post's at L=256 with fewer reads
  5. kernels  — the fused stage-A kernel is bit-identical to the default
                loop; the ADC / exact-distance kernels keep recall; each
                Pallas call lowers to a ``tpu_custom_call``

``--chips 4`` saves the index in 4 shards, opens each with
``load_shard_records`` onto its own chip, and checks the sharded
retrieve step against single-host ``filtered_search`` (gate and post).

The last line of standard output is one JSON object naming the device.
The script exits non-zero before that line if JAX finds no TPU or any
check fails.  Scratch files live in ``<repo>/.smoke`` and are removed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import numpy as np  # noqa: E402

# BIGANN-1M shaped, cut to the largest N whose in-run index build fits the
# 1,200 s budget of the whole script: build_vamana ran at 620 nodes/s on
# one TPU v5e at N=100k, bound by its Python batch loop (linear in N)
N_DEFAULT = 400_000
# --chips 4 builds on one chip while the host holds four, billed four
# times: at 588 nodes/s (one v5e, N=400k) the smoke's N would hold four
# chips for about 55 minutes, so the sharded check builds a quarter of it
N_SHARDED = 100_000
DIM = 128
N_LABELS = 10
N_TENANTS = 4
N_REQUESTS = 64
N_CLIENTS = 8
BATCH = 32
R_MAX = 16
# Filtered recall at 10% selectivity needs a long frontier at this N:
# L=64 found almost none of the true neighbors at N=400k.
SEARCH = dict(search_l=256, beam_width=8, result_k=10)
# Tunneling walks the nodes that fail the filter (90% here) over R_max=16
# of their 32 edges, so at one L it stops short of post-filtering on this
# data (N=400k on the CPU, recall@10 at L=256: gate 0.3031, post 0.4062).
# The claim checked is the paper's: gate reaches post's recall while
# reading far fewer records, given twice the frontier.
GATE_SEARCH = dict(SEARCH, search_l=2 * SEARCH["search_l"])
RECORD = 4096


class SmokeFailure(Exception):
    """A phase of the smoke test found the system wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# -- phases -----------------------------------------------------------------

def phase_device(chips: int):
    """The TPU devices JAX sees (raises SmokeFailure without one)."""
    import jax

    from repro.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX sees {len(devs)} {devs[0].platform} device(s)"
        )
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)}")
    say("device", f"{devs} kind={devs[0].device_kind!r} count={len(devs)} "
                  f"compile_cache={cache} "
                  f"disk_free={shutil.disk_usage(REPO).free / 2**30:.1f} GiB")
    return devs


def make_data(n: int, seed: int):
    from repro.data import make_bigann_like, make_queries, uniform_labels

    corpus = make_bigann_like(n, DIM, seed=seed)
    labels = uniform_labels(n, N_LABELS, seed=seed)
    queries = make_queries(corpus, N_REQUESTS, seed=seed + 1)
    tenant = np.arange(N_REQUESTS, dtype=np.int32) % N_TENANTS  # label = tenant
    return corpus, labels, queries, tenant


def phase_build(corpus, labels, seed: int):
    import jax

    from repro.core import EngineConfig, GateANNEngine

    n = corpus.shape[0]
    t0 = time.perf_counter()
    eng = GateANNEngine.build(corpus, labels=labels, config=EngineConfig(
        degree=32, build_l=64, pq_chunks=32, r_max=R_MAX, seed=seed))
    jax.block_until_ready((eng.codes, eng.record_store.neighbors))
    dt = time.perf_counter() - t0
    say("build", f"N={n} D={DIM} labels={N_LABELS} degree=32 build_l=64 "
                 f"pq_chunks=32 r_max={R_MAX} (memory tier): {dt:.1f} s, "
                 f"{n / dt:.0f} nodes/s")
    return eng


def phase_disk(mem, workdir: str):
    from repro.core import GateANNEngine

    path = os.path.join(workdir, "index.gann")
    t0 = time.perf_counter()
    mem.save(path)
    t_save = time.perf_counter() - t0
    disk = GateANNEngine.load(
        path, store_tier="disk", cache_budget_bytes=512 * RECORD,
        cache_policy="adaptive", refresh_every=4,
    )
    store = disk.measured_store()
    store.drop_page_cache()
    say("disk", f"saved {store.index_bytes() / 2**30:.2f} GiB in {t_save:.1f} s; "
                f"loaded the disk tier ({store.sector_bytes} B sectors, "
                f"io_mode={store.io_mode}) behind a 512-record adaptive "
                f"cache; page cache dropped")
    return disk


def search_rows(eng, queries, tenant, cfg):
    """Direct ``engine.search`` in request order, BATCH rows per call,
    each row filtered on its tenant's label."""
    ids, dists, stats = [], [], []
    for s in range(0, len(queries), BATCH):
        out = eng.search(queries[s:s + BATCH], filter_kind="label",
                         filter_params=tenant[s:s + BATCH], search_config=cfg)
        ids.append(np.asarray(out.ids))
        dists.append(np.asarray(out.dists))
        stats.append({f: np.asarray(getattr(out.stats, f))
                      for f in out.stats._fields})
    return (np.concatenate(ids), np.concatenate(dists),
            {f: np.concatenate([s[f] for s in stats]) for f in stats[0]})


def phase_serve(mem, disk, corpus, labels, queries, tenant):
    """Serve N_REQUESTS through the frontend.  Returns the direct
    memory-tier gate search (ids, dists) and the ground truth."""
    from repro.core import SearchConfig, recall_at_k
    from repro.data import filtered_ground_truth
    from repro.serve import RAGServer, ServeFrontend, TenantSpec

    n = corpus.shape[0]
    rag = RAGServer(
        engine=disk, cfg=None, params=None, layout=None,
        passage_tokens=np.zeros((n, 1), np.int32),
        search_config=SearchConfig(mode="gate", pipeline_depth=2, **GATE_SEARCH),
        bucket_sizes=(8, 16, 32),
    )
    tenants = [TenantSpec(f"t{i}", "label", np.int32(i),
                          max_inflight=N_REQUESTS) for i in range(N_TENANTS)]
    store = disk.measured_store()
    reads0 = store.io_counters()["records_read"]
    handles = [None] * N_REQUESTS
    errors = []

    def client(c):
        try:
            for j in range(c, N_REQUESTS, N_CLIENTS):
                handles[j] = srv.submit(f"t{tenant[j]}", queries[j], timeout=60.0)
        except Exception as e:  # noqa: BLE001 — reported as a failed check
            errors.append(e)

    with ServeFrontend(rag, tenants, max_batch=32, batch_window_s=0.002) as srv:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(N_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        check(not errors and all(not t.is_alive() for t in threads),
              f"client submission failed: {errors}")
        served = np.stack([h.result(timeout=1800.0) for h in handles])
        wall = time.perf_counter() - t0
        rep = srv.io_report()
    counters = store.io_counters()
    served_ios = sum(h.trace.n_ios for h in handles)
    reads = counters["records_read"] - reads0
    say("serve", f"{N_REQUESTS} requests from {N_CLIENTS} client threads over "
                 f"{N_TENANTS} tenants in {rep['batches']} batches; wall "
                 f"{wall:.2f} s (compiles included, informational)")
    say("serve", f"records_read={reads} served_ios={served_ios} "
                 f"padding_ios={rep['padding_ios']} "
                 f"reconcile_drift={rep['reconcile_drift']} "
                 f"abandoned_tokens={rep['abandoned_tokens']} "
                 f"failed={rep['failed']}")
    check(rep["failed"] == 0, "the frontend failed requests")
    check(reads == served_ios + rep["padding_ios"],
          f"records_read {reads} != sum(n_ios) {served_ios + rep['padding_ios']}")
    check(rep["reconcile_drift"] == 0, "measured reads drifted from n_ios")
    check(rep["abandoned_tokens"] == 0, "pipelined rounds were abandoned")

    gate_cfg = SearchConfig(mode="gate", **GATE_SEARCH)
    direct, direct_d, st = search_rows(mem, queries, tenant, gate_cfg)
    same = int(np.sum(np.all(served == direct, axis=1)))
    say("serve", f"served ids equal direct memory-tier search: "
                 f"{same}/{N_REQUESTS} requests")
    check(same == N_REQUESTS, "served ids differ from direct memory-tier search")

    mask = labels[None, :] == tenant[:, None]
    gt = filtered_ground_truth(corpus, queries, mask, k=SEARCH["result_k"])
    gl, l = GATE_SEARCH["search_l"], SEARCH["search_l"]
    rec = {f"gate@L{gl}": recall_at_k(direct, gt, 10)}
    ios = {f"gate@L{gl}": st["n_ios"].mean()}
    for mode in ("post", "pre_naive", "gate"):
        ids, _, s = search_rows(mem, queries, tenant,
                                SearchConfig(mode=mode, **SEARCH))
        rec[f"{mode}@L{l}"] = recall_at_k(ids, gt, 10)
        ios[f"{mode}@L{l}"] = s["n_ios"].mean()
    say("serve", "recall@10 " + " ".join(f"{m}={r:.4f}" for m, r in rec.items())
        + "; n_ios per query " + " ".join(f"{m}={v:.2f}" for m, v in ios.items())
        + f"; gate@L{gl} n_tunnels={st['n_tunnels'].mean():.2f} "
          f"hops={st['n_hops'].mean():.1f}")
    gate, post = f"gate@L{gl}", f"post@L{l}"
    check(rec[gate] >= rec[post] - 0.01, f"{gate} recall below {post} - 0.01")
    check(rec[gate] > rec[f"pre_naive@L{l}"], f"{gate} recall not above pre_naive")
    check(ios[gate] < ios[post], f"{gate} read no fewer records than {post}")
    return direct, direct_d, gt


def custom_calls(eng, queries, tenant, cfg) -> int:
    """``tpu_custom_call``s in the program ``engine.search`` runs for this
    memory-tier config (lowered, not compiled)."""
    import jax.numpy as jnp

    from repro.core import pq as pqm
    from repro.core import search as searchm

    q = jnp.asarray(queries[:BATCH], jnp.float32)
    lowered = searchm.filtered_search.lower(
        fetch=eng.record_store.fetch_fn(), neighbor_store=eng.neighbor_store,
        filter_check=eng.make_filter("label", jnp.asarray(tenant[:BATCH])),
        lut=pqm.build_lut(eng.codec, q), codes=eng.codes, entry=eng.medoid,
        queries=q, config=cfg,
    )
    return lowered.as_text().count("tpu_custom_call")


def phase_kernels(mem, queries, tenant, ids0, d0, gt):
    """Kernel paths against the default loop's gate results (ids0, d0)."""
    from repro.core import SearchConfig, recall_at_k
    from repro.kernels.backend import resolve_interpret

    base_cfg = SearchConfig(mode="gate", **GATE_SEARCH)
    r0 = recall_at_k(ids0, gt, 10)

    fused_cfg = dataclasses.replace(base_cfg, use_fused_kernel=True)
    ids_f, d_f, _ = search_rows(mem, queries, tenant, fused_cfg)
    bit_same = np.array_equal(ids_f, ids0) and np.array_equal(
        d_f.view(np.uint32), d0.view(np.uint32))
    say("kernels", f"fused stage-A kernel: ids and dists bit-identical to the "
                   f"default loop: {bit_same}")
    check(bit_same, "fused kernel results differ from the default loop")

    kern_cfg = dataclasses.replace(base_cfg, use_kernel=True)
    ids_k, d_k, _ = search_rows(mem, queries, tenant, kern_cfg)
    rk = recall_at_k(ids_k, gt, 10)
    both = (ids_k == ids0) & (ids0 >= 0)
    rel = np.abs(d_k[both] - d0[both]) / np.maximum(np.abs(d0[both]), 1e-30)
    say("kernels", f"ADC + exact-distance kernels: recall@10 {rk:.4f} vs "
                   f"default {r0:.4f}; {int(np.sum(ids_k != ids0))} of "
                   f"{ids0.size} ids differ; max relative distance error "
                   f"{float(rel.max()) if rel.size else 0.0:.3e}")
    check(abs(rk - r0) <= 0.01, "use_kernel recall moved by more than 0.01")

    n_fused = custom_calls(mem, queries, tenant, fused_cfg)
    n_kern = custom_calls(mem, queries, tenant, kern_cfg)
    n_base = custom_calls(mem, queries, tenant, base_cfg)
    say("kernels", f"lowering: interpret={resolve_interpret(None)}; "
                   f"tpu_custom_call in the search program: fused={n_fused} "
                   f"use_kernel={n_kern} default={n_base}")
    check(not resolve_interpret(None), "Pallas kernels would be interpreted")
    check(n_fused >= 1 and n_kern >= 2 and n_base == 0,
          "the Pallas kernels are not in the compiled search program")


def phase_sharded(mem, labels, queries, tenant, workdir: str, devs):
    """The ``model``-sharded retrieve step on a (1, 4) mesh vs single host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import SearchConfig
    from repro.core import pq as pqm
    from repro.core.distributed_search import (
        DistSearchConfig, load_shard_records, make_retrieve_step,
    )

    path = os.path.join(workdir, "index.gann")
    mem.save(path, shards=4)
    mesh = Mesh(np.array(devs[:4]).reshape(1, 4), ("data", "model"))
    shards = [load_shard_records(path, s) for s in range(4)]
    rows = shards[0][2]

    def place(arrays):
        sharding = NamedSharding(mesh, P("model", None))
        parts = [jax.device_put(a, d) for a, d in zip(arrays, mesh.devices.flat)]
        shape = (4 * rows,) + arrays[0].shape[1:]
        return jax.make_array_from_single_device_arrays(shape, sharding, parts)

    rec_vecs = place([v for v, _, _ in shards])
    rec_graph = place([g for _, g, _ in shards])
    on = [str(s.device) for s in rec_vecs.addressable_shards]
    say("sharded", f"mesh {dict(mesh.shape)}; record shards ({rows} rows each) "
                   f"on {on}")
    check(len(set(on)) == 4, "record shards do not sit on four devices")

    b = 16
    q = jnp.asarray(queries[:b], jnp.float32)
    targets = jnp.asarray(tenant[:b])
    lut = pqm.build_lut(mem.codec, q)
    w = SEARCH["beam_width"]
    r = int(rec_graph.shape[1])
    r_max = mem.neighbor_store.r_max
    for mode in ("gate", "post"):
        ref = mem.search(q, filter_kind="label", filter_params=targets,
                         search_config=SearchConfig(mode=mode, **SEARCH))
        hops = int(np.asarray(ref.stats.n_hops).max()) + 1
        cap = 1 << (hops * w * (r + r_max)).bit_length()
        step = make_retrieve_step(mesh, DistSearchConfig(
            n_hops=hops, visited_cap=cap, mode=mode, **SEARCH,
        ), rows_per_shard=rows)
        out = step(q, lut, mem.codes, mem.neighbor_store.neighbors,
                   mem.filters["label"].labels, rec_vecs, rec_graph,
                   mem.medoid, targets)
        same_ids = np.array_equal(np.asarray(out["ids"]), np.asarray(ref.ids))
        same_ios = np.array_equal(np.asarray(out["n_ios"]),
                                  np.asarray(ref.stats.n_ios))
        same_tun = np.array_equal(np.asarray(out["n_tunnels"]),
                                  np.asarray(ref.stats.n_tunnels))
        say("sharded", f"{mode}: ids equal {same_ids}, n_ios equal {same_ios}, "
                       f"n_tunnels equal {same_tun} ({hops} hops, visited "
                       f"ring {cap}); mean n_ios "
                       f"{float(np.mean(np.asarray(out['n_ios']))):.2f}")
        check(same_ids and same_ios and same_tun,
              f"sharded retrieve step differs from single host ({mode})")


# -- entry point ------------------------------------------------------------

def run(args) -> dict:
    devs = phase_device(args.chips)
    n = args.n or (N_SHARDED if args.chips == 4 else N_DEFAULT)
    workdir = os.path.join(REPO, ".smoke")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        corpus, labels, queries, tenant = make_data(n, args.seed)
        mem = phase_build(corpus, labels, args.seed)
        if args.chips == 4:
            phase_sharded(mem, labels, queries, tenant, workdir, devs)
        else:
            disk = phase_disk(mem, workdir)
            try:
                ids, dists, gt = phase_serve(mem, disk, corpus, labels,
                                             queries, tenant)
            finally:
                disk.measured_store().close()
            phase_kernels(mem, queries, tenant, ids, dists, gt)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return {"ok": True, "device": {"platform": devs[0].platform,
                                   "kind": devs[0].device_kind,
                                   "count": len(devs)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path; 4: the sharded retrieve step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=0,
                    help=f"corpus size (default {N_DEFAULT}; "
                         f"{N_SHARDED} with --chips 4)")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
