"""GateANN search loop (Algorithm 1) and the paper's baselines.

One batched, jittable loop implements all five search modes:

  * ``gate``      — GateANN: pre-I/O filter check; filter-passing nodes
                    follow the fetch path (record read + exact distance),
                    filter-failing nodes are *tunneled* in memory
                    (neighbor-store expansion + PQ scoring). §3.3.
  * ``post``      — DiskANN/PipeANN post-filtering: fetch every dispatched
                    node, apply the predicate afterwards. §2.2.
  * ``early``     — the Fig.18 ablation: fetch every node but skip exact
                    distance on non-matching ones (CPU saving, no I/O
                    saving); neighbors expanded normally.
  * ``pre_naive`` — naive pre-filtering: non-matching nodes are dropped
                    outright (no fetch, no expansion) — breaks
                    connectivity, Fig.1(b).
  * ``unfiltered``— plain beam search (selectivity 1.0).

When the record store carries a hot-node cache (``CachedRecordStore``),
``cached_mask`` splits each round's fetches into cache hits (device
gather, counted as ``n_cache_hits``) and slow-tier reads (counted as
``n_ios``) — results are bit-identical either way, only the I/O
accounting and cost change.

The frontier is ordered by PQ distance; results are always drawn from
filter-passing fetched nodes ranked by exact distance (§3.4).  DiskANN's
synchronous beam and PipeANN's asynchronous pipeline both map to the
W-wide dispatch: on TPU a round's W fetches execute as one batched
gather/collective — the hardware-native form of "W in-flight reads".

**Pipelined disk search** (``SearchConfig.pipeline_depth > 1`` with a
store exposing the async ``submit``/``drain`` pair, i.e. the disk tier):
traversal needs only neighbor lists and PQ distances, never the
full-precision record, so the per-round slow-tier read feeds nothing but
the exact-distance result pool.  Stage A expands/tunnels the frontier
from the neighbor lists ``submit`` returns immediately (the adjacency
sidecar) and dispatches round r+1's beam while round r's ``preadv`` is
still in flight; stage B retires completed fetches — up to
``pipeline_depth`` rounds behind — into the result heap, in FIFO round
order.  The result heap is write-only state (beam selection never reads
it), retirement preserves insertion order, and the drained vectors are
byte-identical to the synchronous read, so output is **bit-identical**
to the synchronous loop at every depth; ``pipeline_depth=1`` (the
default) *is* the synchronous loop.  Only wall-clock changes.

**Stage names.**  The loop's pieces run under ``jax.named_scope``
names, which reach the compiled ops' ``op_name`` metadata and so the
device ops of a profiler trace; they change no computation:

  * ``select``  — beam selection (best unexpanded), the filter check,
                  the mode masks, the stats, the loop condition;
  * ``adc``     — PQ distances of the new candidates and of the entry;
  * ``visited`` — the visited bitmap's test and update;
  * ``merge``   — the frontier insert;
  * ``fetch``   — record fetch / submit / drain, the cache split and
                  visit counts, the tunnel path's neighbor lookup, the
                  pipeline's rings;
  * ``rerank``  — exact distances into the result heap (``retire``);
  * ``fused_round`` — the fused stage-A kernel call, where it runs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import frontier as fr
from repro.core import pq as pqm
from repro.core.filter_store import CheckFn
from repro.core.neighbor_store import NeighborStore
from repro.kernels import fused_traversal as ftk
from repro.kernels import ref as kref
from repro.store.cache import CachedMaskFn
from repro.store.vector_store import RecordFetchFn

MODES = ("gate", "post", "early", "pre_naive", "unfiltered")


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    mode: str = "gate"
    search_l: int = 64  # frontier size L
    result_k: int = 10  # top-K
    beam_width: int = 8  # W — dispatch width / pipeline depth
    max_hops: int = 512  # safety bound on rounds
    use_kernel: bool = False  # route PQ scoring through the Pallas kernel
    # software-pipeline depth: max rounds whose slow-tier reads stay in
    # flight before the oldest is retired into the result heap.  1 = the
    # synchronous loop; >1 needs a store with submit/drain (disk tier) and
    # is bit-identical at any depth — only wall-clock changes.
    pipeline_depth: int = 1
    # run stage A (ADC + masks + beam select + frontier merge) as ONE
    # fused Pallas pass per round (kernels.fused_traversal) instead of
    # separate ops with HBM round-trips between them.  Bit-identical to
    # the unfused loop at any mode/tier/depth; shapes the kernel cannot
    # hold raise ValueError (kernels.fused_traversal.check_fused_supported).
    use_fused_kernel: bool = False

    def __post_init__(self):
        assert self.mode in MODES, self.mode
        assert self.pipeline_depth >= 1, self.pipeline_depth


class SearchStats(NamedTuple):
    n_ios: jax.Array  # (B,) records fetched from the slow (expensive) tier
    n_tunnels: jax.Array  # (B,) nodes traversed purely in memory
    n_exact: jax.Array  # (B,) exact distance computations
    n_hops: jax.Array  # (B,) dispatch rounds
    n_cache_hits: jax.Array  # (B,) record fetches served by the cache tier
    # (B,) result-candidate slots whose slow-tier read failed and was
    # served degraded (tunnel sentinel — see DiskRecordStore resilience):
    # traversal kept the node, the exact-ranked results dropped it.
    # Always zero unless the store runs with on_error="degrade" AND a
    # read actually failed.
    n_degraded: jax.Array


class SearchOutput(NamedTuple):
    ids: jax.Array  # (B, K) result ids (filter-passing, exact-ranked)
    dists: jax.Array  # (B, K)
    stats: SearchStats
    # (N,) per-node fetch-path visit counts accumulated on top of the
    # caller-supplied ``visit_counts`` array; None when counting is off.
    visit_counts: jax.Array | None = None


def _adc_ids(lut: jax.Array, codes: jax.Array, ids: jax.Array, use_kernel: bool) -> jax.Array:
    """PQ distances for gathered ids. lut (B,C,K), codes (N,C), ids (B,M).

    The jnp path is ``kref.pq_lookup_gathered_ref``: a gather and a fixed
    pairwise tree over chunks, the same arithmetic as the Pallas ADC
    kernels and the fused round, so all three agree bit for bit.
    """
    with jax.named_scope("adc"):
        got = codes[jnp.maximum(ids, 0)]  # (B, M, C)
        if use_kernel:
            from repro.kernels import ops as kops

            d = kops.pq_lookup_gathered(lut, got)
        else:
            d = kref.pq_lookup_gathered_ref(lut, got)
        # fence the reduction (same reason as _exact_dist): these distances
        # order the frontier, so an ULP of context-dependent fusion drift
        # would change traversal between the unfused and fused-kernel loops
        d = jax.lax.optimization_barrier(d)
        return jnp.where(ids >= 0, d, fr.INF)


def _exact_dist(queries: jax.Array, vecs: jax.Array, use_kernel: bool) -> jax.Array:
    """(B, D) queries vs (B, W, D) fetched rows -> (B, W) squared L2.

    Fenced with optimization barriers and summed by the fixed pairwise
    tree of ``kref.pairwise_sum`` rather than ``jnp.sum``: XLA's reduce
    accumulation order is implementation-defined and can differ between
    otherwise-identical modules, which showed up as 1-ULP drift between
    the sync / pipelined / fused-kernel loops (different graphs, same
    math).  Explicit adds are IEEE-strict.
    """
    queries, vecs = jax.lax.optimization_barrier((queries, vecs))
    if use_kernel:
        from repro.kernels import ops as kops

        return jax.lax.optimization_barrier(kops.l2_dist(queries, vecs))
    diff = vecs - queries[:, None, :]
    return jax.lax.optimization_barrier(kref.pairwise_sum(diff * diff))


@functools.partial(jax.jit, static_argnames=("config",))
def filtered_search(
    *,
    fetch: RecordFetchFn,
    neighbor_store: NeighborStore,
    filter_check: CheckFn,
    lut: jax.Array,  # (B, C, K) per-query ADC tables
    codes: jax.Array,  # (N, C) PQ codes (the in-memory compressed tier)
    entry: jax.Array,  # () int32 medoid (or (B,) per-query entries)
    queries: jax.Array,  # (B, D) full-precision queries
    config: SearchConfig,
    cached_mask: CachedMaskFn | None = None,  # (B, W) ids -> cache-hit mask
    visit_counts: jax.Array | None = None,  # (N,) f32 running fetch counters
    submit=None,  # async pair: (B, W) ids -> (token, nbrs (B, W, R))
    drain=None,  # (token, ids, flag) -> vecs (B, W, D)
) -> SearchOutput:
    b, d = queries.shape
    n = codes.shape[0]
    L, W, K = config.search_l, config.beam_width, config.result_k
    mode = config.mode
    r_max = neighbor_store.r_max

    if entry.ndim == 0:
        entry = jnp.broadcast_to(entry, (b,))

    frontier = fr.make_frontier(b, L)
    entry_d = _adc_ids(lut, codes, entry[:, None], config.use_kernel)[:, 0]
    frontier = frontier._replace(
        ids=frontier.ids.at[:, 0].set(entry),
        dists=frontier.dists.at[:, 0].set(entry_d),
    )
    results = fr.make_results(b, K)

    nw = (n + 31) // 32
    visited = jnp.zeros((b, nw), dtype=jnp.uint32)

    def set_visited(vis, idx):
        word = jnp.clip(idx // 32, 0, nw - 1)
        bit = jnp.where(idx >= 0, jnp.uint32(1) << (idx % 32).astype(jnp.uint32), 0)
        upd = jnp.zeros_like(vis)

        def body(c, upd):
            return upd.at[jnp.arange(b), word[:, c]].set(
                upd[jnp.arange(b), word[:, c]] | bit[:, c]
            )

        upd = jax.lax.fori_loop(0, idx.shape[1], body, upd)
        return vis | upd

    def is_visited(vis, idx):
        word = jnp.clip(idx // 32, 0, nw - 1)
        bit = jnp.uint32(1) << (idx % 32).astype(jnp.uint32)
        return (jnp.take_along_axis(vis, word, axis=1) & bit) != 0

    with jax.named_scope("visited"):
        visited = set_visited(visited, entry[:, None])

    stats0 = SearchStats(
        n_ios=jnp.zeros((b,), jnp.int32),
        n_tunnels=jnp.zeros((b,), jnp.int32),
        n_exact=jnp.zeros((b,), jnp.int32),
        n_hops=jnp.zeros((b,), jnp.int32),
        n_cache_hits=jnp.zeros((b,), jnp.int32),
        n_degraded=jnp.zeros((b,), jnp.int32),
    )
    # Optional online frequency counting for the adaptive cache: the (N,)
    # counter array is loop-carried device state — each round scatter-adds
    # the fetch-path dispatches (the population a record cache can serve).
    # ``None`` keeps the extra state out of the trace entirely.
    track_visits = visit_counts is not None
    vc0 = visit_counts if track_visits else jnp.zeros((0,), jnp.float32)

    def stage_a(frontier, visited, stats, vc):
        """One round of beam selection + masking + bookkeeping — everything
        except touching the record itself.  Shared verbatim by the
        synchronous and pipelined loops, so their traversal (and stats)
        cannot diverge."""
        with jax.named_scope("select"):
            sel_ids, slots, valid = fr.best_unexpanded(frontier, W)
            frontier = fr.mark_expanded(frontier, slots, valid)

            passes = filter_check(sel_ids) & valid  # in-memory predicate (filter store)

            # per-mode dispatch masks — shared with the fused kernel body and
            # its reference twin, so the three paths cannot drift
            fetch_mask, tunnel_mask, result_mask, exact_mask = ftk.mode_masks(
                mode, sel_ids, valid, passes, entry[:, None]
            )

        # ---- split fetches into cache hits and slow-tier reads
        with jax.named_scope("fetch"):
            if cached_mask is None:
                hit_mask = jnp.zeros_like(fetch_mask)
            else:
                hit_mask = cached_mask(sel_ids) & fetch_mask
            slow_mask = fetch_mask & (~hit_mask)

            if track_visits:
                vc = vc.at[jnp.maximum(sel_ids, 0).ravel()].add(
                    jnp.where(fetch_mask, 1.0, 0.0).ravel()
                )

            fetch_ids = jnp.where(fetch_mask, sel_ids, fr.INVALID)
        with jax.named_scope("select"):
            stats = SearchStats(
                n_ios=stats.n_ios + jnp.sum(slow_mask, axis=1).astype(jnp.int32),
                n_tunnels=stats.n_tunnels + jnp.sum(tunnel_mask, axis=1).astype(jnp.int32),
                n_exact=stats.n_exact + jnp.sum(exact_mask, axis=1).astype(jnp.int32),
                n_hops=stats.n_hops + 1,
                n_cache_hits=stats.n_cache_hits + jnp.sum(hit_mask, axis=1).astype(jnp.int32),
                n_degraded=stats.n_degraded,  # advanced by retire, not stage A
            )
        return frontier, stats, vc, sel_ids, fetch_ids, tunnel_mask, result_mask

    def expand(frontier, visited, sel_ids, tunnel_mask, disk_nbrs):
        """Frontier growth from this round's neighbor lists (fetch path:
        full-R disk adjacency; tunnel path: the in-memory r_max slice)."""
        with jax.named_scope("fetch"):
            if mode == "gate":
                tun_ids = jnp.where(tunnel_mask, sel_ids, fr.INVALID)
                tun_nbrs = neighbor_store.lookup(tun_ids)  # (B, W, R_max)
            else:
                tun_nbrs = jnp.full((b, W, r_max), fr.INVALID)

        with jax.named_scope("visited"):
            new = jnp.concatenate(
                [disk_nbrs.reshape(b, -1), tun_nbrs.reshape(b, -1)], axis=-1
            )
            fresh = (new >= 0) & (~is_visited(visited, jnp.maximum(new, 0)))
            new = jnp.where(fresh, new, fr.INVALID)
            visited = set_visited(visited, new)
        new_d = _adc_ids(lut, codes, new, config.use_kernel)  # PQ priority signal
        with jax.named_scope("merge"):
            return fr.insert(frontier, new, new_d), visited

    def retire(results, stats, sel_ids, result_mask, vecs, live):
        """Stage B: score one round's fetched records and push them into
        the result heap.  ``live=False`` turns it into a heap no-op (all
        ids INVALID / dists INF) for pipeline warmup/flush padding.

        A slot whose slow-tier read failed under ``on_error="degrade"``
        arrives with the +inf sentinel vector: it keeps its traversal
        role (neighbors were already served from the adjacency sidecar)
        but its exact-distance contribution is dropped — the INF
        distance maps the slot to INVALID in ``results_insert`` — and
        the loss is counted in ``stats.n_degraded``.  Real corpus
        vectors are finite, so with zero injected faults the sentinel
        never appears and this is bit-identical to the pre-resilience
        loop."""
        with jax.named_scope("rerank"):
            exact_d = _exact_dist(queries, vecs, config.use_kernel)
            deg = jnp.any(jnp.isinf(vecs), axis=-1) & result_mask & live
            ok = result_mask & live & ~deg
            exact_d = jnp.where(ok, exact_d, fr.INF)
            results = fr.results_insert(
                results, jnp.where(ok, sel_ids, fr.INVALID), exact_d
            )
            stats = stats._replace(
                n_degraded=stats.n_degraded + jnp.sum(deg, axis=1).astype(jnp.int32)
            )
            return results, stats

    @jax.named_scope("select")
    def cond(state):
        frontier, _, _, stats = state[0], state[1], state[2], state[3]
        return jnp.any(fr.has_unexpanded(frontier)) & jnp.all(stats.n_hops < config.max_hops)

    pipelined = config.pipeline_depth > 1 and submit is not None and drain is not None

    # ---- fused stage-A routing: one Pallas pass per round replaces the
    # best_unexpanded / filter / mode-mask / insert op chain.  The round
    # is rotated — each kernel call merges the previous round's candidates
    # AND selects the next beam — so the loop carries the kernel's output
    # (a FusedRound) instead of a bare frontier.  Results are bit-identical
    # (the kernel replicates the stable-sort semantics of frontier.insert /
    # best_unexpanded exactly).  Shapes the kernel cannot hold raise.
    use_fused = config.use_fused_kernel
    if use_fused:
        probe = (lambda i: submit(i)[1]) if pipelined else (lambda i: fetch(i)[1])
        nbrs_s = jax.eval_shape(probe, jax.ShapeDtypeStruct((b, W), jnp.int32))
        ftk.check_fused_supported(
            l=L, width=W, m=W * (int(nbrs_s.shape[-1]) + r_max), k=lut.shape[2]
        )

    # Trace-time dispatch accounting: this Python body runs once per jit
    # trace (shape/config change), not per call, so this counts *traces*
    # — which loop variant actually compiled — not query batches.
    # Per-call volume lives in the engine layer (``search.dispatch``).
    obs.default_registry().counter(
        "search.traces",
        mode=mode,
        fused="1" if use_fused else "0",
        pipelined="1" if pipelined else "0",
    ).inc()

    if use_fused:  # gatelint: disable=trace-host-branch — trace-static: a SearchConfig field
        # Pallas kernel on TPU, its bit-identical jnp twin elsewhere —
        # see fused_round_for_backend for why interpret mode stays out of
        # the serving loop
        round_fn = ftk.fused_round_for_backend()

        @jax.named_scope("fused_round")
        def fused_call(fids, fds, fexp, fpass, new_ids, new_codes, new_passes):
            return round_fn(
                fids, fds, fexp, fpass, new_ids, new_codes, new_passes,
                lut, entry, mode=mode, width=W,
            )

        def fused_account(rnd, stats, vc):
            """The non-kernel half of stage A: cache-tier split, visit
            counters, stats — same arithmetic as the unfused stage_a."""
            with jax.named_scope("fetch"):
                if cached_mask is None:
                    hit_mask = jnp.zeros_like(rnd.fetch_mask)
                else:
                    hit_mask = cached_mask(rnd.sel_ids) & rnd.fetch_mask
                slow_mask = rnd.fetch_mask & (~hit_mask)
                if track_visits:
                    vc = vc.at[jnp.maximum(rnd.sel_ids, 0).ravel()].add(
                        jnp.where(rnd.fetch_mask, 1.0, 0.0).ravel()
                    )
            with jax.named_scope("select"):
                stats = SearchStats(
                    n_ios=stats.n_ios + jnp.sum(slow_mask, axis=1).astype(jnp.int32),
                    n_tunnels=stats.n_tunnels
                    + jnp.sum(rnd.tunnel_mask, axis=1).astype(jnp.int32),
                    n_exact=stats.n_exact
                    + jnp.sum(rnd.exact_mask, axis=1).astype(jnp.int32),
                    n_hops=stats.n_hops + 1,
                    n_cache_hits=stats.n_cache_hits
                    + jnp.sum(hit_mask, axis=1).astype(jnp.int32),
                    n_degraded=stats.n_degraded,  # advanced by retire
                )
            return stats, vc

        def fused_new(sel_ids, tunnel_mask, visited, disk_nbrs):
            """This round's candidate batch for the next kernel call —
            identical to the head of the unfused ``expand``, plus the code
            gather and filter verdicts the kernel consumes as payload."""
            with jax.named_scope("fetch"):
                if mode == "gate":
                    tun_ids = jnp.where(tunnel_mask, sel_ids, fr.INVALID)
                    tun_nbrs = neighbor_store.lookup(tun_ids)  # (B, W, R_max)
                else:
                    tun_nbrs = jnp.full((b, W, r_max), fr.INVALID)
            with jax.named_scope("visited"):
                new = jnp.concatenate(
                    [disk_nbrs.reshape(b, -1), tun_nbrs.reshape(b, -1)], axis=-1
                )
                fresh = (new >= 0) & (~is_visited(visited, jnp.maximum(new, 0)))
                new = jnp.where(fresh, new, fr.INVALID)
                visited = set_visited(visited, new)
            with jax.named_scope("adc"):
                new_codes = codes[jnp.maximum(new, 0)]
            with jax.named_scope("select"):
                new_passes = filter_check(new)
            return new, new_codes, new_passes, visited

        @jax.named_scope("select")
        def fused_cond(state):
            rnd, stats = state[0], state[3]
            return jnp.any(rnd.valid) & jnp.all(stats.n_hops < config.max_hops)

        # pre-loop call (M=0): select round 0's beam from the entry-seeded
        # frontier.  any(valid) ≡ has_unexpanded, so the loop condition is
        # unchanged in substance.
        with jax.named_scope("select"):
            passes0 = filter_check(frontier.ids)
        rnd0 = fused_call(
            frontier.ids, frontier.dists, frontier.expanded,
            passes0,
            jnp.zeros((b, 0), jnp.int32),
            jnp.zeros((b, 0, codes.shape[1]), jnp.int32),
            jnp.zeros((b, 0), bool),
        )

        if not pipelined:
            def fused_body(state):
                rnd, results, visited, stats, vc = state
                stats, vc = fused_account(rnd, stats, vc)
                with jax.named_scope("fetch"):
                    vecs, disk_nbrs = fetch(rnd.fetch_ids)
                results, stats = retire(
                    results, stats, rnd.sel_ids, rnd.result_mask, vecs,
                    jnp.bool_(True),
                )
                new, new_codes, new_passes, visited = fused_new(
                    rnd.sel_ids, rnd.tunnel_mask, visited, disk_nbrs
                )
                rnd = fused_call(
                    rnd.frontier_ids, rnd.frontier_dists, rnd.frontier_expanded,
                    rnd.frontier_passes, new, new_codes, new_passes,
                )
                return rnd, results, visited, stats, vc

            rnd, results, visited, stats, vc = jax.lax.while_loop(
                fused_cond, fused_body, (rnd0, results, visited, stats0, vc0)
            )
            return SearchOutput(
                ids=results.ids,
                dists=results.dists,
                stats=stats,
                visit_counts=vc if track_visits else None,
            )

        # fused pipelined loop: same submit/drain rings and FIFO retirement
        # as the unfused pipeline below — the kernel call sits between this
        # round's submit and the oldest round's drain, preserving the host
        # callback order exactly.
        depth = config.pipeline_depth
        p_ids0 = jnp.full((depth, b, W), fr.INVALID)
        p_fids0 = jnp.full((depth, b, W), fr.INVALID)
        p_rm0 = jnp.zeros((depth, b, W), dtype=bool)
        p_tok0 = jnp.full((depth,), -1, jnp.int32)

        def fused_pbody(state):
            (rnd, results, visited, stats, vc,
             p_ids, p_fids, p_rm, p_tok) = state
            r = stats.n_hops[0]
            stats, vc = fused_account(rnd, stats, vc)
            with jax.named_scope("fetch"):
                token, disk_nbrs = submit(rnd.fetch_ids)
            new, new_codes, new_passes, visited = fused_new(
                rnd.sel_ids, rnd.tunnel_mask, visited, disk_nbrs
            )
            nrnd = fused_call(
                rnd.frontier_ids, rnd.frontier_dists, rnd.frontier_expanded,
                rnd.frontier_passes, new, new_codes, new_passes,
            )
            with jax.named_scope("fetch"):
                wp = jnp.mod(r, depth)
                p_ids = p_ids.at[wp].set(rnd.sel_ids)
                p_fids = p_fids.at[wp].set(rnd.fetch_ids)
                p_rm = p_rm.at[wp].set(rnd.result_mask)
                p_tok = p_tok.at[wp].set(token)
                live = r >= depth - 1
                dp = jnp.mod(r - (depth - 1), depth)
                vecs = drain(p_tok[dp], p_fids[dp], live)
            results, stats = retire(results, stats, p_ids[dp], p_rm[dp],
                                    vecs, live)
            return (nrnd, results, visited, stats, vc,
                    p_ids, p_fids, p_rm, p_tok)

        (rnd, results, visited, stats, vc,
         p_ids, p_fids, p_rm, p_tok) = jax.lax.while_loop(
            fused_cond, fused_pbody,
            (rnd0, results, visited, stats0, vc0,
             p_ids0, p_fids0, p_rm0, p_tok0),
        )
        n_hops = stats.n_hops[0]
        for j in range(depth - 1):
            rr = n_hops - (depth - 1) + j
            live = rr >= 0
            dp = jnp.mod(rr, depth)
            with jax.named_scope("fetch"):
                vecs = drain(p_tok[dp], p_fids[dp], live)
            results, stats = retire(results, stats, p_ids[dp], p_rm[dp],
                                    vecs, live)
        return SearchOutput(
            ids=results.ids,
            dists=results.dists,
            stats=stats,
            visit_counts=vc if track_visits else None,
        )

    if not pipelined:
        # ---- synchronous loop: fetch blocks, this round retires itself
        state0 = (frontier, results, visited, stats0, vc0)

        def body(state):
            frontier, results, visited, stats, vc = state
            frontier, stats, vc, sel_ids, fetch_ids, tunnel_mask, result_mask = (
                stage_a(frontier, visited, stats, vc)
            )
            with jax.named_scope("fetch"):
                vecs, disk_nbrs = fetch(fetch_ids)  # (B, W, D), (B, W, R)
            results, stats = retire(results, stats, sel_ids, result_mask,
                                    vecs, jnp.bool_(True))
            frontier, visited = expand(
                frontier, visited, sel_ids, tunnel_mask, disk_nbrs
            )
            return frontier, results, visited, stats, vc

        frontier, results, visited, stats, vc = jax.lax.while_loop(
            cond, body, state0
        )
        return SearchOutput(
            ids=results.ids,
            dists=results.dists,
            stats=stats,
            visit_counts=vc if track_visits else None,
        )

    # ---- two-stage software pipeline: up to `depth` rounds of slow-tier
    # reads stay in flight; stage A keeps traversing off the submit-time
    # neighbor lists, stage B retires the oldest round into the result
    # heap.  FIFO retirement == the synchronous insertion order, and the
    # heap is write-only state, so output is bit-identical at any depth.
    depth = config.pipeline_depth
    pend_ids0 = jnp.full((depth, b, W), fr.INVALID)  # sel_ids per round
    pend_fids0 = jnp.full((depth, b, W), fr.INVALID)  # fetch_ids per round
    pend_rm0 = jnp.zeros((depth, b, W), dtype=bool)  # result_mask per round
    pend_tok0 = jnp.full((depth,), -1, jnp.int32)
    state0 = (frontier, results, visited, stats0, vc0,
              pend_ids0, pend_fids0, pend_rm0, pend_tok0)

    def pbody(state):
        (frontier, results, visited, stats, vc,
         p_ids, p_fids, p_rm, p_tok) = state
        r = stats.n_hops[0]  # this round's index (all rows hop together)
        frontier, stats, vc, sel_ids, fetch_ids, tunnel_mask, result_mask = (
            stage_a(frontier, visited, stats, vc)
        )
        # stage A: dispatch this round's read; neighbors come back now
        with jax.named_scope("fetch"):
            token, disk_nbrs = submit(fetch_ids)
        frontier, visited = expand(
            frontier, visited, sel_ids, tunnel_mask, disk_nbrs
        )
        with jax.named_scope("fetch"):
            wp = jnp.mod(r, depth)
            p_ids = p_ids.at[wp].set(sel_ids)
            p_fids = p_fids.at[wp].set(fetch_ids)
            p_rm = p_rm.at[wp].set(result_mask)
            p_tok = p_tok.at[wp].set(token)
            # stage B: once the pipe is full, retire the oldest round (the
            # drain is issued every round; `live` gates the warmup no-ops so
            # the host interleaving stays fixed and deterministic)
            live = r >= depth - 1
            dp = jnp.mod(r - (depth - 1), depth)
            vecs = drain(p_tok[dp], p_fids[dp], live)
        results, stats = retire(results, stats, p_ids[dp], p_rm[dp],
                                vecs, live)
        return (frontier, results, visited, stats, vc,
                p_ids, p_fids, p_rm, p_tok)

    (frontier, results, visited, stats, vc,
     p_ids, p_fids, p_rm, p_tok) = jax.lax.while_loop(cond, pbody, state0)

    # flush: retire the (up to depth-1) rounds still in flight, oldest
    # first — same FIFO order, same heap insertions as the sync loop
    n_hops = stats.n_hops[0]
    for j in range(depth - 1):
        rr = n_hops - (depth - 1) + j  # round to retire
        live = rr >= 0
        dp = jnp.mod(rr, depth)
        with jax.named_scope("fetch"):
            vecs = drain(p_tok[dp], p_fids[dp], live)
        results, stats = retire(results, stats, p_ids[dp], p_rm[dp],
                                vecs, live)

    return SearchOutput(
        ids=results.ids,
        dists=results.dists,
        stats=stats,
        visit_counts=vc if track_visits else None,
    )
