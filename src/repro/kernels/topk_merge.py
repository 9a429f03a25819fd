"""Pallas TPU kernel: bitonic top-k selection for frontier maintenance.

Frontier upkeep ("Other: list mgmt", 26–34% of per-query time in paper
Table 5) is a sort-and-truncate over the merged candidate list.  A full
``argsort`` is wasteful when only the best L survive; this kernel runs a
static **bitonic sorting network** over a VMEM tile of (dist, id) pairs
and emits the first L — ids ride along through every compare-exchange.

Ordering is **deterministic on the lexicographic (dist, id) key**: ties
in distance break by ascending id, in both this kernel and the
``ref.topk_merge_ref`` oracle.  A bitonic network is not a stable sort,
so breaking ties by network position (the old behavior) let kernel and
reference disagree about which id survives at rank k whenever two
candidates shared a distance; the id tiebreak makes the key total and
the result unique.  Padding rows (to the power-of-two network width)
carry an id *above* every real id, so they sort after genuine
+INF-distance entries and come back as (-1, +INF).

The network is O(M log² M) compare-exchanges of full vectors, entirely on
the VPU with no data-dependent control flow — exactly the shape TPUs
like.  Each compare-exchange reads its partner lane (``i ^ j``) from two
lane rotations of the row (``partner_lanes``) instead of a gather, which
the TPU lowering does not offer.  Rows enter and leave as ``(B, 1, M)``
arrays, so every block's last two dimensions are whole array dimensions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

_INF = jnp.float32(3.4e38)
# pad id: sorts after every real id at equal (+INF) distance; mapped back
# to -1 on output.  Real ids are node indices, far below int32 max.
_PAD_ID = jnp.int32(2**31 - 1)


def partner_lanes(x, j: int, lower):
    """x[..., i ^ j] for a (1, M) row, M a power of two and j < M.

    Lanes whose bit ``j`` is clear (``lower``) read lane ``i + j``, the
    others lane ``i - j``: two static rotations and a select.
    """
    m = x.shape[-1]
    up = pltpu.roll(x, m - j, 1)  # up[i] = x[i + j]
    down = pltpu.roll(x, j, 1)  # down[i] = x[i - j]
    return jnp.where(lower, up, down)


def _bitonic_kernel(d_ref, i_ref, od_ref, oi_ref, *, m: int):
    d = d_ref[0]  # (1, M) f32
    ids = i_ref[0]  # (1, M) i32
    logm = m.bit_length() - 1
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    for stage in range(logm):
        block = 1 << (stage + 1)
        for sub in reversed(range(stage + 1)):
            j = 1 << sub
            is_lower = (idx & j) == 0
            pd = partner_lanes(d, j, is_lower)
            pi = partner_lanes(ids, j, is_lower)
            # strict lexicographic (dist, id) "self < partner"; ids are
            # unique per batch row in the intended use, but even with
            # duplicates the <= on equal keys keeps the exchange stable
            lt = (d < pd) | ((d == pd) & (ids <= pi))
            # the lower lane of an ascending pair (or the upper lane of a
            # descending one) keeps the smaller key
            ascending = (idx & block) == 0
            keep_self = lt == (ascending == is_lower)
            d = jnp.where(keep_self, d, pd)
            ids = jnp.where(keep_self, ids, pi)
    od_ref[0] = d
    oi_ref[0] = ids


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_merge(
    dists: jax.Array,  # (B, M) float32 — merged candidate keys
    ids: jax.Array,  # (B, M) int32
    k: int,
    *,
    interpret: bool | None = None,
):
    """Sorted top-k by ascending (distance, id). Returns (dists (B,k), ids (B,k))."""
    interpret = resolve_interpret(interpret)
    b, m = dists.shape
    mp = 1 << (m - 1).bit_length()  # next power of two
    if mp != m:
        dists = jnp.pad(dists, ((0, 0), (0, mp - m)), constant_values=_INF)
        ids = jnp.pad(ids, ((0, 0), (0, mp - m)), constant_values=_PAD_ID)
    k = min(k, mp)
    row = pl.BlockSpec((1, 1, mp), lambda i: (i, 0, 0))
    od, oi = pl.pallas_call(
        functools.partial(_bitonic_kernel, m=mp),
        grid=(b,),
        in_specs=[row, row],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, mp), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, mp), jnp.int32),
        ],
        interpret=interpret,
    )(dists.astype(jnp.float32)[:, None], ids.astype(jnp.int32)[:, None])
    od, oi = od[:, 0, :k], oi[:, 0, :k]
    return od, jnp.where(oi == _PAD_ID, jnp.int32(-1), oi)
