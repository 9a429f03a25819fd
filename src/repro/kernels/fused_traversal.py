"""Pallas TPU kernel: one fused stage-A traversal round.

With SSD reads overlapped (pipelined disk search) the in-memory traversal
is the throughput wall: each round runs PQ-lookup, filter masking,
candidate selection, and frontier top-k merge as *separate* ops with HBM
round-trips between them (NDSEARCH's argument — traversal compute, not
just I/O, bounds graph-ANNS throughput).  This kernel fuses one whole
round into a single VMEM-resident pass per query:

  1. **ADC PQ-lookup** over the round's gathered candidate codes — the
     same one-hot select-and-sum as ``pq_lookup`` (``adc_row``), bitwise
     equal to the unfused gather + pairwise-tree reference.
  2. **Kill masking** — invalid ids and within-concat duplicates go to
     (+INF, -1), replicating ``frontier.insert``'s ``_dedup_mask``
     (earlier slot wins) exactly.
  3. **Frontier merge** — a bitonic sorting network over the padded
     [old frontier ‖ new candidates] keyed on ``(dist, seq)``; the
     position tiebreak makes the (unstable) network reproduce a *stable*
     ascending sort bit-for-bit, so the merged frontier equals
     ``jnp.argsort``'s.  ``expanded`` / filter-pass flags ride along as
     payload lanes through every compare-exchange.
  4. **Beam selection** — the ``width`` best unexpanded entries of the
     merged frontier (ties by slot, matching ``frontier.best_unexpanded``'s
     stable argsort) are picked by a prefix count and marked expanded.
  5. **Filter / tunnel masks** — the per-mode fetch/tunnel/result/exact
     mask logic (``mode_masks`` below — the *same function* the unfused
     loop calls) runs on the selected beam inside the kernel.

Filter-store lookups stay outside (they are per-query closures over
engine state); their boolean verdicts enter once per candidate and ride
the sort as payload, so the kernel never re-evaluates a predicate.

The round is *rotated* relative to the unfused loop: one call merges the
previous round's candidates and selects the next beam, which is exactly
``expand`` ∘ ``stage_a`` of ``core/search.py``.  ``filtered_search``
carries the selection in loop state; results are bit-identical (pinned
by the fused-vs-unfused parity lattice in ``tests/test_fused_traversal``).

Layout: the wrapper lays each query's round out as one lane row of P
slots, [frontier (L) | candidates (M) | pads], P a power of two of at
least one lane tile.  Pads are (+INF, -1, seq>=real) entries, which sort
strictly after every real slot, so M and L need not be powers of two.
Every block's last two dimensions are whole array dimensions, as the
TPU lowering requires; the sorting network and the prefix count move
lanes with rotations, not gathers.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pq_lookup as pqk
from repro.kernels import topk_merge as tkm
from repro.kernels.backend import resolve_interpret

# numpy scalars, not jnp: the kernel body references them, and a traced
# jnp scalar would be captured as a pallas_call constant (a trace error)
INF = np.float32(3.4e38)
INVALID = np.int32(-1)

# VMEM ceilings of one program: the (P, P) dedup mask over the padded
# sort width P, and the (K, P) f32 one-hot ADC workspace
_MAX_SORT = 1024
_MAX_ADC_BYTES = 4 * 1024 * 1024
# the TPU's lane width: the sort row is padded to at least this many lanes
_LANES = 128


def mode_masks(mode: str, sel_ids, valid, passes, entry_ids):
    """Per-mode dispatch masks for a selected beam — the single source of
    truth shared by the unfused ``stage_a``, this kernel's body, and the
    jnp reference twin (``ref.fused_traversal_round_ref``).

    All arguments broadcast elementwise against ``sel_ids`` (boolean
    ``valid``/``passes``; ``entry_ids`` is the per-query entry id).
    Returns ``(fetch_mask, tunnel_mask, result_mask, exact_mask)``.
    """
    no = jnp.zeros_like(valid)
    if mode == "unfiltered":
        return valid, no, valid, valid
    if mode == "post":
        return valid, no, passes, valid
    if mode == "early":
        return valid, no, passes, passes
    if mode == "pre_naive":
        is_entry = sel_ids == entry_ids
        fetch = passes | (is_entry & valid)
        return fetch, no, passes, fetch
    # gate
    return passes, valid & (~passes), passes, passes


class FusedRound(NamedTuple):
    """One kernel call's outputs: the merged+marked frontier and the next
    beam with its per-mode masks (shapes ``(B, L)`` / ``(B, W)``)."""

    frontier_ids: jax.Array
    frontier_dists: jax.Array
    frontier_expanded: jax.Array  # bool
    frontier_passes: jax.Array  # bool — filter verdict payload per slot
    sel_ids: jax.Array
    valid: jax.Array  # bool
    fetch_ids: jax.Array  # sel_ids where fetch_mask, else -1
    fetch_mask: jax.Array  # bool
    tunnel_mask: jax.Array  # bool
    result_mask: jax.Array  # bool
    exact_mask: jax.Array  # bool


def sort_width(l: int, m: int) -> int:
    """Padded lane width P of the sort row: a power of two >= L + M and
    >= one lane tile."""
    return max(1 << (l + m - 1).bit_length(), _LANES)


def check_fused_supported(*, l: int, width: int, m: int, k: int) -> None:
    """Raise ``ValueError`` naming the limit these shapes break.

    The fused loop has no fallback: a caller that asks for it at shapes
    one program cannot hold gets this error, not the unfused loop.
    """
    if width < 1 or l < 1 or m < 0:
        raise ValueError(
            f"fused traversal needs width >= 1, L >= 1 and M >= 0 "
            f"(got width={width}, L={l}, M={m})"
        )
    p = sort_width(l, m)
    if p > _MAX_SORT:
        raise ValueError(
            f"fused traversal sort width {p} (L={l} + M={m}, padded) exceeds "
            f"{_MAX_SORT}: its ({p}, {p}) dedup mask would not fit VMEM"
        )
    if k * p * 4 > _MAX_ADC_BYTES:
        raise ValueError(
            f"fused traversal one-hot ADC workspace K*P*4 = {k * p * 4} bytes "
            f"(K={k}, P={p}) exceeds {_MAX_ADC_BYTES}"
        )


def _bitonic_merge(dists, ids, exp, pas, lane):
    """Stable ascending sort of a (1, P) row and its payload lanes.

    Pad lanes hold (+INF, -1, expanded, fail) and sit *after* every real
    slot, so with the seq lane as tiebreak they sort strictly last among
    INF ties, and the network's total order on (dist, seq) equals a
    stable sort by distance.  Partners come from lane rotations
    (``topk_merge.partner_lanes``).
    """
    p = dists.shape[-1]
    d, i, e, f, s = dists, ids, exp, pas, lane
    for stage in range(p.bit_length() - 1):
        block = 1 << (stage + 1)
        ascending = (lane & block) == 0
        for sub in reversed(range(stage + 1)):
            j = 1 << sub
            lower = (lane & j) == 0
            pd, pi, pe, pf, ps = (tkm.partner_lanes(x, j, lower)
                                  for x in (d, i, e, f, s))
            # strict lexicographic (dist, seq) — seqs are unique, so this
            # is a total order and == / >= cases never arise
            lt = (d < pd) | ((d == pd) & (s < ps))
            keep = lt == (ascending == lower)
            d = jnp.where(keep, d, pd)
            i = jnp.where(keep, i, pi)
            e = jnp.where(keep, e, pe)
            f = jnp.where(keep, f, pf)
            s = jnp.where(keep, s, ps)
    return d, i, e, f


def _fused_kernel(
    ids_ref, idc_ref, d_ref, exp_ref, pas_ref, codes_ref, lut_ref, entry_ref,
    ofid_ref, ofd_ref, ofexp_ref, ofpass_ref,
    osel_ref, ovalid_ref, ofids_ref, ofetch_ref, otun_ref, ores_ref, oexact_ref,
    *, mode: str, l: int, m: int, width: int,
):
    """One query's round: merge M candidates into the L-frontier, select
    the next W-beam, emit its per-mode masks.

    Rows are (1, P) lane rows of [frontier | candidates | pads]; the ids
    also come as a (P, 1) column for the pairwise dedup.  Frontier
    outputs are rows (the wrapper keeps the first L lanes), beam outputs
    (W, 1) columns.  Bool lanes travel as i32."""
    ids = ids_ref[0]  # (1, P)
    p = ids.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
    dists = d_ref[0]
    exp = exp_ref[0]
    pas = pas_ref[0]
    if m:  # candidates get their ADC distance; round 0 has none
        nd = pqk.adc_row(lut_ref[0], codes_ref[0])
        dists = jnp.where((lane >= l) & (lane < l + m), nd, dists)

    # kill mask, exactly as frontier.insert: a slot dies if it duplicates
    # an EARLIER slot holding the same (non-negative) id, or its own id is
    # invalid; dead slots become (+INF, -1).  [a, b]: slot a precedes b.
    col = idc_ref[0]  # (P, 1)
    earlier = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
               < jax.lax.broadcasted_iota(jnp.int32, (p, p), 1))
    hit = (col == ids) & earlier & (col >= 0)
    dup = jnp.max(hit.astype(jnp.int32), axis=0, keepdims=True) > 0
    dists = jnp.where(dup | (ids < 0), INF, dists)
    ids = jnp.where(dists >= INF, INVALID, ids)

    d, i, e, f = _bitonic_merge(dists, ids, exp, pas, lane)

    # beam selection == frontier.best_unexpanded (stable argsort of the
    # masked key).  The merged frontier is sorted by distance, so the
    # selectable slots are already in key order, ties by slot: a slot's
    # rank is the count of selectable slots before it (a log-step scan).
    selectable = (lane < l) & (e == 0) & (i >= 0)
    cnt = selectable.astype(jnp.int32)
    incl = cnt
    shift = 1
    while shift < l:
        incl = incl + jnp.where(lane >= shift, pltpu.roll(incl, shift, 1), 0)
        shift *= 2
    rank = incl - cnt
    selected = selectable & (rank < width)
    e = e | selected.astype(e.dtype)

    # gather the selected slots into beam order (rank w -> row w)
    oh = (rank == jax.lax.broadcasted_iota(jnp.int32, (width, p), 0)) & selected
    valid = jnp.max(oh.astype(jnp.int32), axis=1, keepdims=True) > 0
    sel_ids = jnp.sum(jnp.where(oh, i, 0), axis=1, keepdims=True)
    sel_ids = jnp.where(valid, sel_ids, INVALID)
    passes = (jnp.max(jnp.where(oh & (f != 0), 1, 0), axis=1, keepdims=True)
              > 0) & valid

    fetch, tun, res, exact = mode_masks(mode, sel_ids, valid, passes,
                                        entry_ref[0])

    ofid_ref[0] = i
    ofd_ref[0] = d
    ofexp_ref[0] = e
    ofpass_ref[0] = f
    osel_ref[0] = sel_ids
    ovalid_ref[0] = valid.astype(jnp.int32)
    ofids_ref[0] = jnp.where(fetch, sel_ids, INVALID)
    ofetch_ref[0] = fetch.astype(jnp.int32)
    otun_ref[0] = tun.astype(jnp.int32)
    ores_ref[0] = res.astype(jnp.int32)
    oexact_ref[0] = exact.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("mode", "width", "interpret")
)
def fused_traversal_round(
    frontier_ids: jax.Array,  # (B, L) int32
    frontier_dists: jax.Array,  # (B, L) float32
    frontier_expanded: jax.Array,  # (B, L) bool
    frontier_passes: jax.Array,  # (B, L) bool — filter verdicts per slot
    new_ids: jax.Array,  # (B, M) int32 — already visited-masked (-1 = dead)
    new_codes: jax.Array,  # (B, M, C) int32 — gathered PQ codes
    new_passes: jax.Array,  # (B, M) bool — filter verdicts for new ids
    lut: jax.Array,  # (B, C, K) float32 per-query ADC tables
    entry: jax.Array,  # (B,) int32 per-query entry point (pre_naive mode)
    *,
    mode: str,
    width: int,
    interpret: bool | None = None,
) -> FusedRound:
    """Batched fused round; see module docstring.  Grid is one program
    per query; everything for a query lives in VMEM for the whole pass."""
    interpret = resolve_interpret(interpret)
    b, l = frontier_ids.shape
    m = new_ids.shape[1]
    c, k = lut.shape[1], lut.shape[2]
    w = width
    check_fused_supported(l=l, width=w, m=m, k=k)
    p = sort_width(l, m)

    def lay(front, new, fill_new, fill_pad, dtype):
        """[frontier | candidates | pads] as one (B, 1, P) row."""
        parts = [front.astype(dtype)]
        if new is not None:
            parts.append(new.astype(dtype))
        else:
            parts.append(jnp.full((b, m), fill_new, dtype))
        parts.append(jnp.full((b, p - l - m), fill_pad, dtype))
        return jnp.concatenate(parts, axis=1)[:, None, :]

    ids = lay(frontier_ids, new_ids, None, INVALID, jnp.int32)
    codes_t = jnp.zeros((b, c, p), jnp.int32).at[:, :, l:l + m].set(
        new_codes.astype(jnp.int32).transpose(0, 2, 1)
    )
    row = pl.BlockSpec((1, 1, p), lambda i: (i, 0, 0))
    beam = pl.BlockSpec((1, w, 1), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, mode=mode, l=l, m=m, width=w),
        grid=(b,),
        in_specs=[
            row,  # ids
            pl.BlockSpec((1, p, 1), lambda i: (i, 0, 0)),  # ids as a column
            row,  # dists (candidate lanes are filled in by the kernel)
            row,  # expanded
            row,  # filter passes
            pl.BlockSpec((1, c, p), lambda i: (i, 0, 0)),  # chunk-major codes
            pl.BlockSpec((1, k, c), lambda i: (i, 0, 0)),  # transposed lut
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),  # entry
        ],
        out_specs=[row] * 4 + [beam] * 7,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, p), jnp.int32),  # frontier ids
            jax.ShapeDtypeStruct((b, 1, p), jnp.float32),  # frontier dists
            jax.ShapeDtypeStruct((b, 1, p), jnp.int32),  # frontier expanded
            jax.ShapeDtypeStruct((b, 1, p), jnp.int32),  # frontier passes
            # sel_ids, valid, fetch_ids, fetch/tunnel/result/exact masks
        ] + [jax.ShapeDtypeStruct((b, w, 1), jnp.int32)] * 7,
        interpret=interpret,
    )(
        ids,
        ids.transpose(0, 2, 1),
        lay(frontier_dists, None, 0.0, INF, jnp.float32),
        lay(frontier_expanded, None, 0, 1, jnp.int32),
        lay(frontier_passes, new_passes, None, 0, jnp.int32),
        codes_t,
        lut.astype(jnp.float32).transpose(0, 2, 1),
        entry.astype(jnp.int32)[:, None, None],
    )
    ofid, ofd, ofexp, ofpass = (x[:, 0, :l] for x in out[:4])
    osel, ovalid, ofids, ofetch, otun, ores, oexact = (x[..., 0] for x in out[4:])
    return FusedRound(
        frontier_ids=ofid,
        frontier_dists=ofd,
        frontier_expanded=ofexp != 0,
        frontier_passes=ofpass != 0,
        sel_ids=osel,
        valid=ovalid != 0,
        fetch_ids=ofids,
        fetch_mask=ofetch != 0,
        tunnel_mask=otun != 0,
        result_mask=ores != 0,
        exact_mask=oexact != 0,
    )


def fused_round_for_backend():
    """The search loop's fused-round callable for this process's backend.

    The Pallas kernel wherever a compiled lowering exists (TPU); its
    bit-identical jnp twin (``ref.fused_traversal_round_ref``) elsewhere.
    Interpret-mode Pallas inside ``jax.lax.while_loop`` makes CPU XLA
    compile times pathological (minutes per mode, unbounded for some mask
    configurations) — it is a kernel-debugging tool, not a serving path.
    The twin is pinned bitwise to the kernel by the parity lattice in
    ``tests/test_fused_traversal.py``, so routing through it preserves
    the fused loop's bit-identity contract on every backend.
    """
    from repro.kernels.backend import supports_compiled_pallas

    if supports_compiled_pallas():
        return fused_traversal_round
    from repro.kernels import ref

    return ref.fused_traversal_round_ref
