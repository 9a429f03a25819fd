"""Pure-jnp oracles for every Pallas kernel (the correctness contracts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_INF = jnp.float32(3.4e38)
_INVALID = jnp.int32(-1)


def pairwise_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis as a fixed pairwise tree: (0+1), (2+3), ...,
    an odd tail carried to the end of the next level.

    Every step is an elementwise add, whose IEEE result no compiler may
    change, so the bits do not depend on what the backend fuses around
    the call or how it would order a ``jnp.sum``.  The Pallas ADC kernels
    sum their chunk partials in the same tree (``pq_lookup.tree_sum``),
    which is what keeps them bitwise equal to this reference.
    """
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2 * 2
        head = x[..., 0:half:2] + x[..., 1:half:2]
        if half != x.shape[-1]:
            head = jnp.concatenate([head, x[..., half:]], axis=-1)
        x = head
    return x[..., 0]


def pq_lookup_gathered_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """lut (B, C, K) f32, codes (B, M, C) i32 -> (B, M) f32."""
    # out[b, m] = sum_c lut[b, c, codes[b, m, c]]
    return pairwise_sum(jnp.take_along_axis(
        lut.astype(jnp.float32).transpose(0, 2, 1),  # (B, K, C)
        codes,  # (B, M, C) indexes the K axis
        axis=1,
    ))


def pq_scan_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """lut (B, C, K) f32, codes (N, C) i32 -> (B, N) f32."""
    b = lut.shape[0]
    return pq_lookup_gathered_ref(lut, jnp.broadcast_to(codes[None], (b,) + codes.shape))


def l2_dist_ref(queries: jax.Array, rows: jax.Array) -> jax.Array:
    """queries (B, D), rows (B, W, D) -> (B, W) squared L2."""
    diff = rows.astype(jnp.float32) - queries.astype(jnp.float32)[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def topk_merge_ref(dists: jax.Array, ids: jax.Array, k: int):
    """Sorted ascending top-k on the lexicographic (dist, id) key.

    Distance ties break by ascending id — the same total order the
    bitonic kernel realizes, so kernel and oracle agree on which id
    survives at rank k even among duplicate distances.
    """
    order = jnp.lexsort((ids, dists), axis=-1)[:, :k]
    return (
        jnp.take_along_axis(dists, order, axis=-1).astype(jnp.float32),
        jnp.take_along_axis(ids, order, axis=-1).astype(jnp.int32),
    )


def _dedup_mask_ref(ids: jax.Array) -> jax.Array:
    """True where a slot duplicates an earlier slot with the same id
    (``core.frontier._dedup_mask`` semantics, restated here so the
    kernels package stays dependency-free of ``core``)."""
    m = ids.shape[-1]
    lt = jnp.tril(jnp.ones((m, m), dtype=bool), k=-1)
    same = ids[..., None, :] == ids[..., :, None]
    return jnp.any(same & lt & (ids[..., None, :] >= 0), axis=-1)


def fused_traversal_round_ref(
    frontier_ids: jax.Array,  # (B, L) int32
    frontier_dists: jax.Array,  # (B, L) float32
    frontier_expanded: jax.Array,  # (B, L) bool
    frontier_passes: jax.Array,  # (B, L) bool
    new_ids: jax.Array,  # (B, M) int32
    new_codes: jax.Array,  # (B, M, C) int32
    new_passes: jax.Array,  # (B, M) bool
    lut: jax.Array,  # (B, C, K) float32
    entry: jax.Array,  # (B,) int32
    *,
    mode: str,
    width: int,
):
    """jnp twin of ``fused_traversal.fused_traversal_round``.

    Composes the unfused building blocks — ADC reference, stable-argsort
    frontier merge (``frontier.insert`` semantics), stable-argsort beam
    selection (``frontier.best_unexpanded``), and the shared
    ``mode_masks`` — in the same rotated round shape as the kernel.
    Returns a ``fused_traversal.FusedRound``.
    """
    from repro.kernels.fused_traversal import FusedRound, mode_masks

    b, l = frontier_ids.shape
    m = new_ids.shape[1]

    if m:
        nd = pq_lookup_gathered_ref(lut, new_codes)
        nd = jnp.where(new_ids >= 0, nd, _INF)
        ids = jnp.concatenate([frontier_ids, new_ids], axis=-1)
        dists = jnp.concatenate([frontier_dists, nd], axis=-1)
        exp = jnp.concatenate(
            [frontier_expanded, jnp.zeros((b, m), bool)], axis=-1
        )
        pas = jnp.concatenate([frontier_passes, new_passes], axis=-1)
    else:
        ids, dists = frontier_ids, frontier_dists
        exp, pas = frontier_expanded, frontier_passes

    # frontier.insert: dedup + invalid -> dead (+INF, -1), stable top-L
    dists = jnp.where(_dedup_mask_ref(ids) | (ids < 0), _INF, dists)
    ids = jnp.where(dists >= _INF, _INVALID, ids)
    order = jnp.argsort(dists, axis=-1)[:, :l]
    mf_ids = jnp.take_along_axis(ids, order, axis=-1)
    mf_d = jnp.take_along_axis(dists, order, axis=-1)
    mf_exp = jnp.take_along_axis(exp, order, axis=-1)
    mf_pas = jnp.take_along_axis(pas, order, axis=-1)

    # frontier.best_unexpanded + mark_expanded
    selkey = jnp.where((~mf_exp) & (mf_ids >= 0), mf_d, _INF)
    slots = jnp.argsort(selkey, axis=-1)[:, :width]
    valid = jnp.take_along_axis(selkey, slots, axis=-1) < _INF
    sel_ids = jnp.where(
        valid, jnp.take_along_axis(mf_ids, slots, axis=-1), _INVALID
    )
    passes = jnp.take_along_axis(mf_pas, slots, axis=-1) & valid
    upd = jnp.zeros_like(mf_exp)
    upd = upd.at[jnp.arange(b)[:, None], slots].set(valid)
    mf_exp = mf_exp | upd

    fetch, tun, res, exact = mode_masks(mode, sel_ids, valid, passes,
                                        entry[:, None])
    return FusedRound(
        frontier_ids=mf_ids,
        frontier_dists=mf_d,
        frontier_expanded=mf_exp,
        frontier_passes=mf_pas,
        sel_ids=sel_ids,
        valid=valid,
        fetch_ids=jnp.where(fetch, sel_ids, _INVALID),
        fetch_mask=fetch,
        tunnel_mask=tun,
        result_mask=res,
        exact_mask=exact,
    )
