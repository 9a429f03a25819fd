"""Vamana graph construction and in-memory beam search.

This is the index substrate under DiskANN / PipeANN / GateANN: all three
search the *same* standard Vamana graph (paper §5.1).  We implement:

  * ``build_vamana``          — batched two-pass Vamana build
                                (greedy search for candidates + RobustPrune,
                                reverse-edge insertion with overflow pruning),
                                rows written longest edge first (``longest_first_batch``).
  * ``build_filtered_vamana`` — the F-DiskANN baseline: label-aware pruning
                                and per-label medoid entry points.
  * ``beam_search_batch``     — jitted batched best-first search over
                                full-precision in-memory vectors (the
                                Vamana baseline, and the build workhorse).

Graphs are dense int32 arrays ``(N, R)`` padded with -1, matching the
paper's fixed-degree on-disk records.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pq import argmin_f32

INVALID = jnp.int32(-1)
INF = jnp.float32(3.4e38)
# bumped whenever build_vamana's output changes (2: rows longest edge
# first); artifact caches key on it
BUILD_REVISION = 2


class VamanaGraph(NamedTuple):
    neighbors: jax.Array  # (N, R) int32, -1 padded
    medoid: jax.Array  # () int32 — global entry point


# ---------------------------------------------------------------------------
# distance helpers
# ---------------------------------------------------------------------------

def l2_sq(x: jax.Array, y: jax.Array) -> jax.Array:
    """Squared L2 between rows of x (..., D) and y (..., D)."""
    diff = x - y
    return jnp.sum(diff * diff, axis=-1)


def l2_sq_pairwise(x: jax.Array, y: jax.Array) -> jax.Array:
    """(Nx, D) x (Ny, D) -> (Nx, Ny).  The matmul runs at full f32
    precision: the TPU default (bf16 operands) would make the expansion
    cancel away the distances between close points."""
    return (
        jnp.sum(x * x, axis=1, keepdims=True)
        - 2.0 * jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
        + jnp.sum(y * y, axis=1)[None, :]
    )


def find_medoid(vectors: jax.Array) -> jax.Array:
    """Node closest to the dataset centroid (the DiskANN entry point)."""
    centroid = jnp.mean(vectors, axis=0, keepdims=True)
    return argmin_f32(l2_sq(vectors, centroid), axis=0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# batched best-first beam search (in-memory, full precision)
# ---------------------------------------------------------------------------

class SearchResult(NamedTuple):
    ids: jax.Array  # (B, L) int32 candidate ids, sorted by distance
    dists: jax.Array  # (B, L) float32
    expanded_ids: jax.Array  # (B, max_expand) int32, -1 padded (the visited set V)
    n_expanded: jax.Array  # (B,) int32
    n_hops: jax.Array  # (B,) int32


def _frontier_insert(ids, dists, flags, new_ids, new_dists, new_flags):
    """Merge new candidates into the sorted frontier, dedup by id, keep L."""
    l = ids.shape[-1]
    all_ids = jnp.concatenate([ids, new_ids], axis=-1)
    all_d = jnp.concatenate([dists, new_dists], axis=-1)
    all_f = jnp.concatenate([flags, new_flags], axis=-1)
    # Dedup: mark later duplicates invalid. O(M^2) mask, M small (<= L + W*R).
    m = all_ids.shape[-1]
    eye_lt = jnp.tril(jnp.ones((m, m), dtype=bool), k=-1)
    same = all_ids[..., None, :] == all_ids[..., :, None]  # (..., M, M)
    dup = jnp.any(same & eye_lt[None, ...] & (all_ids[..., None, :] >= 0), axis=-1)
    all_d = jnp.where(dup, INF, all_d)
    all_ids = jnp.where(all_d >= INF, INVALID, all_ids)  # kill dup/dead slots
    order = jnp.argsort(all_d, axis=-1)
    take = order[..., :l]
    return (
        jnp.take_along_axis(all_ids, take, axis=-1),
        jnp.take_along_axis(all_d, take, axis=-1),
        jnp.take_along_axis(all_f, take, axis=-1),
    )


@functools.partial(
    jax.jit,
    static_argnames=("search_l", "beam_width", "max_expand"),
)
def beam_search_batch(
    neighbors: jax.Array,  # (N, R)
    vectors: jax.Array,  # (N, D)
    entry: jax.Array,  # () or (B,) int32
    queries: jax.Array,  # (B, D)
    *,
    search_l: int = 64,
    beam_width: int = 4,
    max_expand: int = 256,
) -> SearchResult:
    """Batched best-first graph search with exact in-memory distances.

    Faithful to DiskANN's GreedySearch: maintain a sorted size-L frontier;
    repeatedly expand the best `beam_width` unexpanded candidates; stop
    when the top-L contains no unexpanded candidate.
    """
    b, d = queries.shape
    n, r = neighbors.shape
    if entry.ndim == 0:
        entry = jnp.broadcast_to(entry, (b,))

    ids0 = jnp.full((b, search_l), INVALID)
    dists0 = jnp.full((b, search_l), INF)
    flags0 = jnp.zeros((b, search_l), dtype=bool)  # True = expanded
    e_dist = l2_sq(vectors[entry], queries)
    ids0 = ids0.at[:, 0].set(entry)
    dists0 = dists0.at[:, 0].set(e_dist)

    exp_ids0 = jnp.full((b, max_expand), INVALID)
    exp_d0 = jnp.full((b, max_expand), INF)
    n_exp0 = jnp.zeros((b,), dtype=jnp.int32)
    hops0 = jnp.zeros((b,), dtype=jnp.int32)

    # visited bitmap (B, ceil(N/32)) packed uint32
    nw = (n + 31) // 32
    visited0 = jnp.zeros((b, nw), dtype=jnp.uint32)

    def set_visited(vis, idx):  # idx (B, K)
        word = jnp.clip(idx // 32, 0, nw - 1)
        bit = (jnp.uint32(1) << (idx % 32).astype(jnp.uint32))
        bit = jnp.where(idx >= 0, bit, 0)
        upd = jnp.zeros_like(vis)

        def body(c, args):
            upd, = args
            upd = upd.at[jnp.arange(b), word[:, c]].set(
                upd[jnp.arange(b), word[:, c]] | bit[:, c]
            )
            return (upd,)

        (upd,) = jax.lax.fori_loop(0, idx.shape[1], body, (upd,))
        return vis | upd

    def is_visited(vis, idx):  # (B, K) -> bool
        word = jnp.clip(idx // 32, 0, nw - 1)
        bit = (jnp.uint32(1) << (idx % 32).astype(jnp.uint32))
        got = jnp.take_along_axis(vis, word, axis=1)
        return (got & bit) != 0

    visited0 = set_visited(visited0, entry[:, None])

    state0 = (ids0, dists0, flags0, visited0, exp_ids0, exp_d0, n_exp0, hops0)

    def cond(state):
        ids, dists, flags, *_ , n_exp, hops = state
        has_work = jnp.any((~flags) & (ids >= 0), axis=1)
        return jnp.any(has_work) & jnp.all(hops < max_expand)

    def body(state):
        ids, dists, flags, visited, exp_ids, exp_d, n_exp, hops = state
        # pick up to beam_width best unexpanded candidates per query
        sel_d = jnp.where((~flags) & (ids >= 0), dists, INF)
        order = jnp.argsort(sel_d, axis=1)[:, :beam_width]  # (B, W)
        sel_ids = jnp.take_along_axis(ids, order, axis=1)  # (B, W)
        sel_valid = jnp.take_along_axis(sel_d, order, axis=1) < INF
        sel_ids = jnp.where(sel_valid, sel_ids, INVALID)

        # mark them expanded in the frontier
        w = order.shape[1]
        flag_upd = jnp.zeros_like(flags)
        flag_upd = flag_upd.at[jnp.arange(b)[:, None], order].set(sel_valid)
        flags = flags | flag_upd

        # record the visited set V (for RobustPrune)
        sel_dists = l2_sq(vectors[jnp.maximum(sel_ids, 0)], queries[:, None, :])
        sel_dists = jnp.where(sel_valid, sel_dists, INF)
        slots = n_exp[:, None] + jnp.arange(w)[None, :]
        slots = jnp.clip(slots, 0, max_expand - 1)
        exp_ids = exp_ids.at[jnp.arange(b)[:, None], slots].set(
            jnp.where(sel_valid, sel_ids, exp_ids[jnp.arange(b)[:, None], slots])
        )
        exp_d = exp_d.at[jnp.arange(b)[:, None], slots].set(
            jnp.where(sel_valid, sel_dists, exp_d[jnp.arange(b)[:, None], slots])
        )
        n_exp = n_exp + jnp.sum(sel_valid, axis=1).astype(jnp.int32)

        # expand: gather neighbor lists
        nbrs = neighbors[jnp.maximum(sel_ids, 0)]  # (B, W, R)
        nbrs = jnp.where(sel_valid[..., None], nbrs, INVALID)
        nbrs = nbrs.reshape(b, w * r)
        fresh = (nbrs >= 0) & (~is_visited(visited, jnp.maximum(nbrs, 0)))
        nbrs = jnp.where(fresh, nbrs, INVALID)
        visited = set_visited(visited, nbrs)

        nd = l2_sq(vectors[jnp.maximum(nbrs, 0)], queries[:, None, :])
        nd = jnp.where(nbrs >= 0, nd, INF)
        nf = jnp.zeros_like(nbrs, dtype=bool)
        ids, dists, flags = _frontier_insert(ids, dists, flags, nbrs, nd, nf)
        return ids, dists, flags, visited, exp_ids, exp_d, n_exp, hops + 1

    ids, dists, flags, visited, exp_ids, exp_d, n_exp, hops = jax.lax.while_loop(
        cond, body, state0
    )
    return SearchResult(ids=ids, dists=dists, expanded_ids=exp_ids, n_expanded=n_exp, n_hops=hops)


# ---------------------------------------------------------------------------
# RobustPrune (vectorized over a batch of points)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("degree",))
def robust_prune_batch(
    point_ids: jax.Array,  # (B,) int32
    cand_ids: jax.Array,  # (B, C) int32, -1 padded (V ∪ current neighbors)
    vectors: jax.Array,  # (N, D)
    *,
    alpha: float,
    degree: int,
) -> jax.Array:
    """DiskANN RobustPrune: greedily keep the closest candidate, drop any
    candidate c' with alpha * d(c, c') <= d(p, c'). Returns (B, degree)."""
    b, c = cand_ids.shape
    p_vec = vectors[point_ids]  # (B, D)
    c_vec = vectors[jnp.maximum(cand_ids, 0)]  # (B, C, D)
    valid = cand_ids >= 0
    # drop self
    valid = valid & (cand_ids != point_ids[:, None])
    d_p = jnp.where(valid, l2_sq(c_vec, p_vec[:, None, :]), INF)  # (B, C)
    # pairwise candidate distances (B, C, C)
    d_cc = jax.vmap(l2_sq_pairwise)(c_vec, c_vec)

    def select_one(state, _):
        alive, d_p_cur, out, k = state
        best = argmin_f32(jnp.where(alive, d_p_cur, INF), axis=1)  # (B,)
        best_ok = jnp.take_along_axis(jnp.where(alive, d_p_cur, INF), best[:, None], axis=1)[
            :, 0
        ] < INF
        out = out.at[jnp.arange(b), k].set(
            jnp.where(best_ok, jnp.take_along_axis(cand_ids, best[:, None], axis=1)[:, 0], INVALID)
        )
        # occlusion rule
        d_best = jnp.take_along_axis(d_cc, best[:, None, None], axis=1)[:, 0, :]  # (B, C)
        occluded = alpha * d_best <= d_p_cur
        alive = alive & (~occluded) & best_ok[:, None]
        alive = alive.at[jnp.arange(b), best].set(False)
        return (alive, d_p_cur, out, k + 1), None

    out0 = jnp.full((b, degree), INVALID)
    (alive, _, out, _), _ = jax.lax.scan(
        select_one, (valid, d_p, out0, 0), None, length=degree
    )
    return out


@jax.jit
def longest_first_batch(point_ids: jax.Array, rows: jax.Array, vectors: jax.Array) -> jax.Array:
    """Reorder adjacency rows (B, R): longest edge first, -1 padding last.

    The neighbor store keeps a row's first R_max entries for tunneling.
    The closest neighbors of a node crowd together, so a tunneled walk
    over them goes in circles; the long edges are the ones that carry it
    toward the query.  The set of neighbors is unchanged.
    """
    d = l2_sq(vectors[jnp.maximum(rows, 0)], vectors[point_ids][:, None, :])
    order = jnp.argsort(jnp.where(rows >= 0, -d, INF), axis=1, stable=True)
    return jnp.take_along_axis(rows, order, axis=1)


# ---------------------------------------------------------------------------
# Vamana build
# ---------------------------------------------------------------------------

def _init_random_graph(n: int, r: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    nbrs = rng.integers(0, n, size=(n, r), dtype=np.int32)
    # avoid self loops
    self_hit = nbrs == np.arange(n, dtype=np.int32)[:, None]
    nbrs[self_hit] = (nbrs[self_hit] + 1) % n
    return nbrs


def build_vamana(
    vectors: np.ndarray | jax.Array,
    *,
    degree: int = 32,
    build_l: int = 64,
    alpha: float = 1.2,
    batch_size: int = 512,
    seed: int = 0,
    two_pass: bool = True,
) -> VamanaGraph:
    """Batched Vamana build (ParlayANN-style batch insertion, two passes).

    Pass 1 uses alpha=1.0, pass 2 the final alpha — as in DiskANN. Each
    batch: greedy-search every point from the medoid, RobustPrune its
    visited set, install edges, then add reverse edges and re-prune nodes
    whose degree overflows.  Last, every row is put longest edge first
    (``longest_first_batch``), so any prefix is a usable neighbor store.
    """
    vectors = jnp.asarray(vectors, dtype=jnp.float32)
    n, d = vectors.shape
    degree = min(degree, n - 1)
    nbrs = _init_random_graph(n, degree, seed)
    medoid = int(find_medoid(vectors))
    rng = np.random.default_rng(seed + 1)

    alphas = [1.0, alpha] if two_pass else [alpha]
    max_expand = max(2 * build_l, 128)

    for pass_alpha in alphas:
        order = rng.permutation(n)
        for start in range(0, n, batch_size):
            batch = order[start : start + batch_size].astype(np.int32)
            if len(batch) < batch_size:  # pad to a fixed shape (no retrace);
                batch = np.concatenate(  # duplicate writes are idempotent
                    [batch, batch[np.zeros(batch_size - len(batch), dtype=np.int64)]]
                )
            bq = vectors[batch]
            res = beam_search_batch(
                jnp.asarray(nbrs),
                vectors,
                jnp.int32(medoid),
                bq,
                search_l=build_l,
                beam_width=4,
                max_expand=max_expand,
            )
            # candidate pool: visited set ∪ current neighbors
            cur = jnp.asarray(nbrs[batch])  # (B, R)
            cands = jnp.concatenate([res.expanded_ids, res.ids, cur], axis=1)
            pruned = robust_prune_batch(
                jnp.asarray(batch), cands, vectors, alpha=pass_alpha, degree=degree
            )
            pruned_np = np.asarray(pruned)
            nbrs[batch] = pruned_np

            # reverse edges
            src = np.repeat(batch, degree)
            dst = pruned_np.reshape(-1)
            ok = dst >= 0
            src, dst = src[ok], dst[ok]
            overflow_nodes = _add_reverse_edges(nbrs, dst, src, degree)
            if len(overflow_nodes):
                onodes = np.asarray(sorted(overflow_nodes), dtype=np.int32)
                for os in range(0, len(onodes), batch_size):
                    ob = onodes[os : os + batch_size]
                    if len(ob) < batch_size:
                        ob = np.concatenate(
                            [ob, ob[np.zeros(batch_size - len(ob), dtype=np.int64)]]
                        )
                    ocands = jnp.asarray(
                        np.concatenate([nbrs[ob], _overflow_extra(ob)], axis=1)
                    )
                    opr = robust_prune_batch(
                        jnp.asarray(ob), ocands, vectors, alpha=pass_alpha, degree=degree
                    )
                    nbrs[ob] = np.asarray(opr)

    for start in range(0, n, batch_size):
        batch = np.arange(start, min(start + batch_size, n), dtype=np.int32)
        if len(batch) < batch_size:
            batch = np.concatenate([batch, np.full(batch_size - len(batch), batch[0], np.int32)])
        nbrs[batch] = np.asarray(longest_first_batch(jnp.asarray(batch), jnp.asarray(nbrs[batch]), vectors))
    return VamanaGraph(neighbors=jnp.asarray(nbrs), medoid=jnp.int32(medoid))


_OVERFLOW_BUF: dict[int, np.ndarray] = {}


def _overflow_extra(ob: np.ndarray) -> np.ndarray:
    """Extra candidate columns gathered for overflowing nodes this batch."""
    out = np.full((len(ob), _OVERFLOW_W), -1, dtype=np.int32)
    for i, node in enumerate(ob):
        extra = _OVERFLOW_BUF.get(int(node))
        if extra is not None:
            k = min(len(extra), _OVERFLOW_W)
            out[i, :k] = extra[:k]
    return out


_OVERFLOW_W = 32


def _add_reverse_edges(nbrs: np.ndarray, dst: np.ndarray, src: np.ndarray, degree: int):
    """Append src into dst's adjacency; collect nodes that overflow."""
    _OVERFLOW_BUF.clear()
    overflow = set()
    # group by destination
    order = np.argsort(dst, kind="stable")
    dst, src = dst[order], src[order]
    starts = np.searchsorted(dst, np.unique(dst))
    uniq = np.unique(dst)
    bounds = np.append(starts, len(dst))
    for i, node in enumerate(uniq):
        incoming = src[bounds[i] : bounds[i + 1]]
        row = nbrs[node]
        existing = set(row[row >= 0].tolist())
        new = [s for s in incoming.tolist() if s not in existing and s != node]
        if not new:
            continue
        free = np.where(row < 0)[0]
        n_fit = min(len(free), len(new))
        if n_fit:
            nbrs[node, free[:n_fit]] = new[:n_fit]
        rest = new[n_fit:]
        if rest:
            _OVERFLOW_BUF[int(node)] = np.asarray(rest[:_OVERFLOW_W], dtype=np.int32)
            overflow.add(int(node))
    return overflow


# ---------------------------------------------------------------------------
# FilteredVamana (F-DiskANN baseline)
# ---------------------------------------------------------------------------

class FilteredVamanaGraph(NamedTuple):
    neighbors: jax.Array  # (N, R)
    medoid: jax.Array  # global medoid
    label_medoids: jax.Array  # (n_labels,) int32 per-label entry points


def build_filtered_vamana(
    vectors: np.ndarray | jax.Array,
    labels: np.ndarray,  # (N,) int single-label
    *,
    degree: int = 32,
    build_l: int = 64,
    alpha: float = 1.2,
    batch_size: int = 512,
    seed: int = 0,
) -> FilteredVamanaGraph:
    """F-DiskANN's FilteredVamana (single-label form).

    Label-aware construction: candidate generation searches from the
    point's *label medoid* and the candidate pool is biased toward
    same-label nodes; RobustPrune keeps an edge to c' only if it shares
    the point's label or survives the unfiltered rule (the "stitched"
    simplification documented in DESIGN.md §8).
    """
    vectors = jnp.asarray(vectors, dtype=jnp.float32)
    labels = np.asarray(labels)
    n, d = vectors.shape
    n_labels = int(labels.max()) + 1
    base = build_vamana(
        vectors, degree=degree, build_l=build_l, alpha=alpha, batch_size=batch_size, seed=seed
    )
    nbrs = np.asarray(base.neighbors).copy()

    # per-label medoids
    label_medoids = np.zeros(n_labels, dtype=np.int32)
    vec_np = np.asarray(vectors)
    for lab in range(n_labels):
        idx = np.where(labels == lab)[0]
        if len(idx) == 0:
            label_medoids[lab] = int(base.medoid)
            continue
        cen = vec_np[idx].mean(axis=0, keepdims=True)
        label_medoids[lab] = idx[np.argmin(((vec_np[idx] - cen) ** 2).sum(axis=1))]

    # label-aware edge augmentation: reserve a fraction of each node's
    # degree for same-label neighbors found by a filtered search.
    reserve = max(degree // 4, 4)
    rng = np.random.default_rng(seed + 7)
    order = rng.permutation(n)
    labels_j = jnp.asarray(labels.astype(np.int32))
    for start in range(0, n, batch_size):
        batch = order[start : start + batch_size].astype(np.int32)
        bl = labels[batch]
        entries = jnp.asarray(label_medoids[bl])
        res = beam_search_batch(
            jnp.asarray(nbrs), vectors, entries, vectors[batch],
            search_l=build_l, beam_width=4, max_expand=2 * build_l,
        )
        # same-label candidates only
        cand = np.asarray(res.ids)
        cand_lab = np.where(cand >= 0, labels[np.maximum(cand, 0)], -2)
        same = np.where(cand_lab == bl[:, None], cand, -1)
        same_j = jnp.asarray(same.astype(np.int32))
        pruned = robust_prune_batch(
            jnp.asarray(batch), same_j, vectors, alpha=alpha, degree=reserve
        )
        pruned_np = np.asarray(pruned)
        # install into the last `reserve` slots (keeping base connectivity)
        nbrs[batch, degree - reserve :] = pruned_np

    return FilteredVamanaGraph(
        neighbors=jnp.asarray(nbrs),
        medoid=base.medoid,
        label_medoids=jnp.asarray(label_medoids),
    )
