"""Distributed GateANN: filtered search sharded over the production mesh.

Deployment layout (DESIGN.md §2):

  * queries           — sharded over ``data`` (and ``pod``): query DP.
  * record tier       — full-precision vectors + full adjacency sharded
                        row-wise over ``model`` *within each data group*
                        (serving replicas).  A fetch = masked local gather
                        + ``psum`` over ``model`` — remote HBM over ICI,
                        the TPU-native "SSD read".
  * traversal metadata— PQ codes, neighbor store, filter store replicated
                        per device (the paper's "in-memory" tier; ~13 GB
                        at 100M scale, Table 2).

Graph tunneling therefore eliminates *collective* traffic: non-matching
nodes never reach the psum fetch path.  The loop is a fixed-hop
``fori_loop`` inside ``shard_map``; the visited set is a bounded ring
buffer (bitmaps don't scale to 100M x batch).

The multi-pod dry-run lowers this step at BigANN-100M scale on both
production meshes (see ``repro.launch.dryrun --retrieval``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.frontier import _dedup_mask
from repro.kernels import ref as kref
from repro.store import format as idx_format

INVALID = jnp.int32(-1)
INF = jnp.float32(3.4e38)


def load_shard_records(
    path: str, shard: int, *, n_shards: int | None = None
) -> tuple[np.ndarray, np.ndarray, int]:
    """Open ONLY this shard's record rows off a persistent index.

    This is the per-host load path for the ``model``-axis record tier:
    on a sharded index (``engine.save(shards=k)``) it memmaps just the
    local segment file — the other shards' bytes are never opened; on a
    monolithic index it memmaps a row-slice of the records section
    (touching only those pages), with ``n_shards`` supplied by the
    caller.  Rows are padded to ``rows_per_shard`` (zero vectors, -1
    adjacency) exactly like ``ShardedRecordStore.shard_arrays``, so the
    result drops into ``make_retrieve_step``'s ``rec_vecs`` /
    ``rec_graph`` slots.

    Returns ``(vectors (rows, D) f32, neighbors (rows, R) i32, rows)``.
    """
    idx = idx_format.read_index(path)
    h = idx.header
    if h.shards:
        k = h.n_shards
        if n_shards is not None and n_shards != k:
            raise ValueError(
                f"{path} is sharded {k}-way but n_shards={n_shards} requested"
            )
        rows = int(h.shards["rows_per_shard"])
        if not 0 <= shard < k:
            raise ValueError(f"shard {shard} out of range [0, {k})")
        recs = idx.segment_records(shard)
    else:
        if n_shards is None:
            raise ValueError(
                f"{path} has monolithic records — pass n_shards to slice it"
            )
        k = int(n_shards)
        rows = -(-h.n // k)
        if not 0 <= shard < k:
            raise ValueError(f"shard {shard} out of range [0, {k})")
        recs = idx.records()[shard * rows : min((shard + 1) * rows, h.n)]
    vecs = np.ascontiguousarray(recs["vec"], np.float32)
    nbrs = np.ascontiguousarray(recs["nbrs"], np.int32)
    pad = rows - vecs.shape[0]
    if pad > 0:  # the last shard may run short of rows_per_shard
        vecs = np.pad(vecs, ((0, pad), (0, 0)))
        nbrs = np.pad(nbrs, ((0, pad), (0, 0)), constant_values=-1)
    return vecs, nbrs, rows


def load_sharded_record_arrays(
    path: str, *, n_shards: int | None = None
) -> tuple[np.ndarray, np.ndarray, int]:
    """Stack every shard's rows for the single-process ``shard_map``
    harness (tests / CPU-mesh emulation): the concatenation of
    ``load_shard_records`` over all shards, shaped exactly like
    ``ShardedRecordStore.shard_arrays`` output."""
    idx = idx_format.read_index(path)
    k = idx.header.n_shards if idx.header.shards else int(n_shards or 1)
    parts = [load_shard_records(path, s, n_shards=None if idx.header.shards else k)
             for s in range(k)]
    vecs = np.concatenate([p[0] for p in parts])
    nbrs = np.concatenate([p[1] for p in parts])
    return vecs, nbrs, parts[0][2]


@dataclasses.dataclass(frozen=True)
class DistSearchConfig:
    search_l: int = 64
    result_k: int = 10
    beam_width: int = 8
    n_hops: int = 48  # fixed rounds (SPMD-friendly)
    visited_cap: int = 2048
    mode: str = "gate"  # gate | post


def _adc(lut, codes_rows):
    """lut (B, C, K) f32; codes_rows (B, M, C) int32 -> (B, M) f32 — the
    single-host loop's ADC arithmetic, so both loops order alike."""
    return kref.pq_lookup_gathered_ref(lut, codes_rows)


def make_retrieve_step(
    mesh: Mesh, cfg: DistSearchConfig, *, rows_per_shard: int, multi_pod: bool = False,
):
    """Builds the jitted distributed retrieve step.

    Args (global shapes):
      queries (B, D) f32          sharded (batch_axes, None)
      lut     (B, C, K) f32       per-query ADC tables, sharded like queries
      codes   (N, C) i32          replicated
      nbr_store (N, R_max) i32    replicated
      labels  (N,) i32            replicated
      rec_vecs (N, Dv) f32        sharded ('model', None)
      rec_graph (N, R) i32        sharded ('model', None)
      entry   () i32              replicated
      targets (B,) i32            per-query equality filter target
    """
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    L, W, K_res = cfg.search_l, cfg.beam_width, cfg.result_k

    def step(queries, lut, codes, nbr_store, labels, rec_vecs, rec_graph, entry, targets):
        b = queries.shape[0]
        r = rec_graph.shape[1]
        r_max = nbr_store.shape[1]
        shard = jax.lax.axis_index("model")
        lo = shard * rows_per_shard

        def fetch(ids):  # (B, W) -> vecs (B, W, Dv), nbrs (B, W, R)
            local = ids - lo
            mine = (ids >= 0) & (local >= 0) & (local < rows_per_shard)
            safe = jnp.clip(local, 0, rec_vecs.shape[0] - 1)
            vecs = jnp.where(mine[..., None], rec_vecs[safe], 0.0)
            nbrs = jnp.where(mine[..., None], rec_graph[safe] + 1, 0)
            vecs = jax.lax.psum(vecs, "model")
            nbrs = jax.lax.psum(nbrs, "model") - 1
            return vecs, jnp.where(ids[..., None] >= 0, nbrs, INVALID)

        # frontier + results + ring-buffer visited set
        f_ids = jnp.full((b, L), INVALID)
        f_d = jnp.full((b, L), INF)
        f_exp = jnp.zeros((b, L), bool)
        res_ids = jnp.full((b, K_res), INVALID)
        res_d = jnp.full((b, K_res), INF)
        vis = jnp.full((b, cfg.visited_cap), INVALID)
        vis_n = jnp.zeros((b,), jnp.int32)

        e = jnp.broadcast_to(entry, (b,))
        ed = _adc(lut, codes[e[:, None]])[:, 0]
        f_ids = f_ids.at[:, 0].set(e)
        f_d = f_d.at[:, 0].set(ed)
        vis = vis.at[:, 0].set(e)
        vis_n = vis_n + 1

        n_ios = jnp.zeros((b,), jnp.int32)
        n_tun = jnp.zeros((b,), jnp.int32)

        def is_visited(vis, ids):  # (B, M) membership against the buffer
            return jnp.any(ids[:, :, None] == vis[:, None, :], axis=-1) & (ids >= 0)

        def push_visited(vis, vis_n, ids):  # append (ring overwrite)
            m = ids.shape[1]
            slots = (vis_n[:, None] + jnp.cumsum(jnp.ones_like(ids), axis=1) - 1)
            slots = jnp.where(ids >= 0, slots % cfg.visited_cap, cfg.visited_cap - 1)
            vis = vis.at[jnp.arange(b)[:, None], slots].set(
                jnp.where(ids >= 0, ids, vis[jnp.arange(b)[:, None], slots])
            )
            vis_n = vis_n + jnp.sum(ids >= 0, axis=1).astype(jnp.int32)
            return vis, vis_n

        def body(_, state):
            f_ids, f_d, f_exp, res_ids, res_d, vis, vis_n, n_ios, n_tun = state
            sel_d = jnp.where((~f_exp) & (f_ids >= 0), f_d, INF)
            order = jnp.argsort(sel_d, axis=1)[:, :W]
            sel = jnp.take_along_axis(f_ids, order, axis=1)
            valid = jnp.take_along_axis(sel_d, order, axis=1) < INF
            sel = jnp.where(valid, sel, INVALID)
            upd = jnp.zeros_like(f_exp).at[jnp.arange(b)[:, None], order].set(valid)
            f_exp = f_exp | upd

            passes = (labels[jnp.maximum(sel, 0)] == targets[:, None]) & valid
            if cfg.mode == "gate":
                fetch_mask = passes
                tunnel_mask = valid & (~passes)
            else:  # post-filter baseline
                fetch_mask = valid
                tunnel_mask = jnp.zeros_like(valid)

            vecs, disk_nbrs = fetch(jnp.where(fetch_mask, sel, INVALID))
            diff = vecs - queries[:, None, :]
            exact = kref.pairwise_sum(diff * diff)  # as core.search._exact_dist
            exact = jnp.where(passes & fetch_mask, exact, INF)
            # results insert (dedup by id, exactly like fr.results_insert)
            cat_i = jnp.concatenate([res_ids, jnp.where(passes & fetch_mask, sel, INVALID)], 1)
            cat_d = jnp.concatenate([res_d, exact], 1)
            cat_d = jnp.where(_dedup_mask(cat_i) | (cat_i < 0), INF, cat_d)
            cat_i = jnp.where(cat_d >= INF, INVALID, cat_i)
            ordr = jnp.argsort(cat_d, axis=1)[:, :K_res]
            res_ids = jnp.take_along_axis(cat_i, ordr, axis=1)
            res_d = jnp.take_along_axis(cat_d, ordr, axis=1)

            tun_nbrs = jnp.where(
                tunnel_mask[..., None], nbr_store[jnp.maximum(sel, 0)], INVALID
            ) if cfg.mode == "gate" else jnp.full((b, W, r_max), INVALID)

            new = jnp.concatenate([disk_nbrs.reshape(b, -1), tun_nbrs.reshape(b, -1)], 1)
            # visited-set check + within-round first-occurrence dedup: the
            # single-host loop gets the latter from fr.insert; without it a
            # node reachable from two same-round expansions enters the
            # frontier twice and is fetched twice (double I/O, dup results)
            fresh = (new >= 0) & (~is_visited(vis, new)) & (~_dedup_mask(new))
            new = jnp.where(fresh, new, INVALID)
            vis, vis_n = push_visited(vis, vis_n, new)
            nd = jnp.where(new >= 0, _adc(lut, codes[jnp.maximum(new, 0)]), INF)
            ci = jnp.concatenate([f_ids, new], 1)
            cd = jnp.concatenate([f_d, nd], 1)
            ce = jnp.concatenate([f_exp, jnp.zeros_like(new, bool)], 1)
            cd = jnp.where(_dedup_mask(ci), INF, cd)  # vs frontier residents
            ci = jnp.where(cd >= INF, INVALID, ci)  # dead slots carry no id
            o2 = jnp.argsort(cd, axis=1)[:, :L]
            f_ids = jnp.take_along_axis(ci, o2, axis=1)
            f_d = jnp.take_along_axis(cd, o2, axis=1)
            f_exp = jnp.take_along_axis(ce, o2, axis=1)

            n_ios = n_ios + jnp.sum(fetch_mask, 1).astype(jnp.int32)
            n_tun = n_tun + jnp.sum(tunnel_mask, 1).astype(jnp.int32)
            return f_ids, f_d, f_exp, res_ids, res_d, vis, vis_n, n_ios, n_tun

        state = (f_ids, f_d, f_exp, res_ids, res_d, vis, vis_n, n_ios, n_tun)
        state = jax.lax.fori_loop(0, cfg.n_hops, body, state)
        _, _, _, res_ids, res_d, _, _, n_ios, n_tun = state
        return {"ids": res_ids, "dists": res_d, "n_ios": n_ios, "n_tunnels": n_tun}

    qspec = P(batch_axes, None)
    rep = P(None, None)
    mapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(qspec, P(batch_axes, None, None), rep, rep, P(None),
                  P("model", None), P("model", None), P(), P(batch_axes)),
        out_specs={"ids": qspec, "dists": qspec, "n_ios": P(batch_axes),
                   "n_tunnels": P(batch_axes)},
        check_vma=False,
    )
    return jax.jit(mapped)
