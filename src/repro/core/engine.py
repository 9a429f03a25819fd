"""GateANN engine — the public API.

Build once from a corpus (+ optional metadata), then search with any
predicate and any mode.  The engine owns the storage tiers of §3:

  fast tier ("memory"):   PQ codes, neighbor store, filter store
  cache tier:             hot-node record cache (optional — see
                          ``EngineConfig.cache_budget_bytes``; static
                          policies pick the hot set once at build time,
                          ``cache_policy="adaptive"`` re-learns it online
                          from live visit counters, per filter bucket)
  slow tier ("SSD"):      record store (full vectors + full adjacency)

and exposes the paper's baselines through ``SearchConfig.mode``.
Tunneling removes slow-tier reads for filter-failing nodes; the cache
removes them for the hot filter-passing ones near the medoid.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import graph as graphm
from repro.core import pq as pqm
from repro.core import search as searchm
from repro.core.filter_store import CheckFn, EqualityFilter, RangeFilter, SubsetFilter, match_all
from repro.core.io_model import DEFAULT_COST_MODEL, IOCostModel
from repro.core.neighbor_store import NeighborStore
from repro.store import format as idx_format
from repro.store.adaptive import ADAPTIVE_POLICY, AdaptiveRecordCache, filter_bucket
from repro.store.cache import CachedRecordStore, select_hot_set
from repro.store.disk import DiskRecordStore, RetryPolicy
from repro.store.vector_store import HostOffloadRecordStore, InMemoryRecordStore


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    degree: int = 32  # graph degree R (paper: 96 at 100M, 128 at 1B)
    build_l: int = 64  # L_build
    alpha: float = 1.2
    pq_chunks: int = 16  # paper default 32 on 128-dim; scaled with D
    r_max: int = 16  # in-memory neighbors per node (runtime knob)
    store_tier: str = "memory"  # memory | host | disk (disk needs a path)
    # disk tier: bound on preadv gap bridging, in sectors — a merged read
    # never bridges a hole wider than this (it splits into another
    # vectored call instead).  None = derived from the file: holes up to
    # store.disk.MAX_BRIDGE_BYTES (128 KiB) over the sector size, 32
    # sectors at 4 KiB records; negative = unbounded (one call per round,
    # reading from its first record to its last); 0 = never bridge.  A
    # reading knob, not part of the saved index: load() ignores the
    # stored value and takes only its caller's.
    max_gap_sectors: int | None = None
    cache_budget_bytes: int = 0  # hot-record cache size (0 disables the tier)
    cache_policy: str = "visit_freq"  # visit_freq | bfs | adaptive
    refresh_every: int = 4  # adaptive: batches between hot-set refreshes
    ema_decay: float = 0.9  # adaptive: per-batch counter decay
    # adaptive: LRU capacity of per-filter hot sets.  Each materialized
    # partition holds its own cache_budget_bytes-sized block, so device
    # residency is up to (1 + cache_partitions) x the budget once several
    # filter buckets see traffic (memory_report's cache_device_bytes
    # shows the true footprint).
    cache_partitions: int = 4
    # engine-wide default for SearchConfig.use_fused_kernel: run stage-A
    # traversal as one fused Pallas pass per round.  Callers passing an
    # explicit search_config keep full control; results are bit-identical
    # either way, and shapes the kernel cannot hold raise ValueError.
    use_fused_kernel: bool = False
    # disk-tier resilience (store/disk.py): transient read errors (EIO /
    # EAGAIN / EINTR / ETIMEDOUT) retry up to io_retries times with
    # exponential backoff starting at io_retry_backoff_s; one fetch
    # round's reads may spend at most io_round_deadline_s in I/O
    # (0 = no deadline).  On exhaustion or a tripped deadline,
    # io_on_error="fail" raises (the historical behavior) while
    # "degrade" serves the failed slots as tunneled nodes — graph
    # connectivity intact, the slots dropped from exact-ranked results
    # and counted in SearchStats.n_degraded.
    io_retries: int = 0
    io_retry_backoff_s: float = 1e-3
    io_round_deadline_s: float = 0.0
    io_on_error: str = "fail"
    seed: int = 0


def _open_disk_store(path: str, config: EngineConfig, faults=None) -> DiskRecordStore:
    """Open the slow tier with the config's resilience knobs applied
    (build and load share this so the two paths can't drift)."""
    return DiskRecordStore.open(
        path,
        max_gap_sectors=config.max_gap_sectors,
        retry=RetryPolicy(
            max_retries=config.io_retries,
            backoff_s=config.io_retry_backoff_s,
            seed=config.seed,
        ),
        on_error=config.io_on_error,
        round_deadline_s=config.io_round_deadline_s,
        faults=faults,
    )


def _store_neighbors(store, expected_n: int | None = None) -> jax.Array:
    """Full adjacency of a record store, whatever its tier.

    The in-memory/host/disk tiers expose ``neighbors`` (the disk tier
    parses it from its sidecar section); the sharded tier only has its
    ``local_neighbors`` rows — acceptable only when they cover the whole
    corpus (``expected_n`` guards against wrapping a cache around a
    partial shard, whose rows are locally indexed).  Cache wiring
    threads adjacency through this helper instead of reaching for
    ``backing.neighbors`` directly.
    """
    nbrs = getattr(store, "neighbors", None)
    if nbrs is None:
        nbrs = getattr(store, "local_neighbors", None)
    if nbrs is None:
        raise TypeError(
            f"record store {type(store).__name__} exposes no adjacency "
            "(neighbors / local_neighbors)"
        )
    if expected_n is not None and int(nbrs.shape[0]) != int(expected_n):
        raise ValueError(
            f"record store {type(store).__name__} holds {int(nbrs.shape[0])} "
            f"adjacency rows but the corpus has {int(expected_n)} — a "
            "partial (sharded) backing cannot be wrapped here"
        )
    return nbrs


def _make_cache_tier(backing, *, vectors, neighbors, medoid: int, config: EngineConfig):
    """Wrap ``backing`` in the configured cache tier (or return it as-is)."""
    if config.cache_budget_bytes <= 0:
        return backing
    if config.cache_policy == ADAPTIVE_POLICY:
        cache = AdaptiveRecordCache.create(
            backing,
            vectors=vectors,
            neighbors=neighbors,
            budget_bytes=config.cache_budget_bytes,
            medoid=medoid,
            ema_decay=config.ema_decay,
            refresh_every=config.refresh_every,
            max_partitions=config.cache_partitions,
            seed=config.seed,
        )
        # a budget below one record leaves the tier off
        return cache if cache.n_slots > 0 else backing
    hot = select_hot_set(
        neighbors=neighbors,
        medoid=medoid,
        budget_bytes=config.cache_budget_bytes,
        policy=config.cache_policy,
        vectors=vectors,
        seed=config.seed,
    )
    if hot.size:  # a budget below one record leaves the tier off
        return CachedRecordStore.wrap(
            backing,
            vectors=vectors,
            neighbors=neighbors,
            hot_ids=hot,
            policy=config.cache_policy,
        )
    return backing


def _write_index_file(path, *, config, vectors, neighbors, codec, codes,
                      medoid: int, filters: dict, shards: int = 1) -> None:
    """Serialize every engine component into one page-aligned index file
    (plus one record segment per shard when ``shards > 1``)."""
    filter_arrays = {}
    if "label" in filters:
        filter_arrays["label"] = np.asarray(filters["label"].labels, np.int32)
    if "range" in filters:
        filter_arrays["range"] = np.asarray(filters["range"].values, np.float32)
    if "tags" in filters:
        filter_arrays["tags"] = np.asarray(filters["tags"].tag_bits, np.uint32)
    idx_format.write_index(
        path,
        vectors=np.asarray(vectors, np.float32),
        neighbors=np.asarray(neighbors, np.int32),
        pq_books=np.asarray(codec.books, np.float32),
        pq_codes=np.asarray(codes, np.int32),
        medoid=int(medoid),
        config=dataclasses.asdict(config),
        filters=filter_arrays,
        shards=shards,
    )


@dataclasses.dataclass
class GateANNEngine:
    config: EngineConfig
    # (N, D) full-precision corpus — ground-truth/debug only.  A device
    # array for memory/host tiers; a LAZY host memmap view for disk-tier
    # loads (np.asarray it on the explicit ground-truth path — the search
    # path never reads it, so the corpus stays on disk)
    vectors: Any
    record_store: Any
    neighbor_store: NeighborStore
    codec: pqm.PQCodec
    codes: jax.Array
    medoid: jax.Array
    filters: dict

    # -- construction ------------------------------------------------------
    @classmethod
    def build(
        cls,
        vectors: np.ndarray,
        *,
        config: EngineConfig | None = None,
        labels: np.ndarray | None = None,
        attributes: np.ndarray | None = None,
        tag_bits: np.ndarray | None = None,
        graph: graphm.VamanaGraph | None = None,
        index_path: str | None = None,
    ) -> "GateANNEngine":
        config = config or EngineConfig()
        if config.store_tier == "disk" and index_path is None:
            raise ValueError(
                "store_tier='disk' needs index_path=... (the index file to "
                "write and serve from) — or build in memory and save()/load()"
            )
        vecs = jnp.asarray(vectors, dtype=jnp.float32)
        n, d = vecs.shape
        if graph is None:
            graph = graphm.build_vamana(
                vecs,
                degree=config.degree,
                build_l=config.build_l,
                alpha=config.alpha,
                seed=config.seed,
            )
        pq_chunks = min(config.pq_chunks, d)
        while d % pq_chunks:
            pq_chunks -= 1
        codec = pqm.train_pq(vecs, n_chunks=pq_chunks, key=jax.random.PRNGKey(config.seed))
        codes = pqm.encode_pq(codec, vecs)
        nbr_store = NeighborStore.from_graph(graph.neighbors, config.r_max)
        filters = {}
        if labels is not None:
            filters["label"] = EqualityFilter(labels=jnp.asarray(labels, dtype=jnp.int32))
        if attributes is not None:
            filters["range"] = RangeFilter(values=jnp.asarray(attributes, dtype=jnp.float32))
        if tag_bits is not None:
            filters["tags"] = SubsetFilter(tag_bits=jnp.asarray(tag_bits))
        if config.store_tier == "disk":
            # persist first, then serve the slow tier straight off the file
            _write_index_file(
                index_path, config=config, vectors=vecs,
                neighbors=graph.neighbors, codec=codec, codes=codes,
                medoid=int(graph.medoid), filters=filters,
            )
            record_store = _open_disk_store(index_path, config)
        elif config.store_tier == "host":
            record_store = HostOffloadRecordStore.create(vecs, graph.neighbors)
        else:
            record_store = InMemoryRecordStore(vectors=vecs, neighbors=graph.neighbors)
        record_store = _make_cache_tier(
            record_store,
            vectors=vecs,
            neighbors=graph.neighbors,
            medoid=int(graph.medoid),
            config=config,
        )
        return cls(
            config=config,
            vectors=vecs,
            record_store=record_store,
            neighbor_store=nbr_store,
            codec=codec,
            codes=codes,
            medoid=graph.medoid,
            filters=filters,
        )

    # -- persistence -------------------------------------------------------
    def save(self, path: str, *, shards: int = 1) -> None:
        """Write the whole index (records, graph, PQ, filters, config) to
        one page-aligned file (``repro.store.format``).

        ``load`` restores it without rebuilding the graph or retraining
        PQ; a disk-tier load serves records straight off this file.

        ``shards=k`` splits the record sectors into one page-aligned
        segment file per ``model``-axis shard (``<path>.seg<i>`` + a
        manifest in the header) — a mesh host then opens only its own
        shard's rows (``core.distributed_search.load_shard_records``),
        and a single-host disk load serves all segments through one
        coalesced reader.
        """
        backing = self.record_store
        while isinstance(backing, (CachedRecordStore, AdaptiveRecordCache)):
            backing = backing.backing
        _write_index_file(
            path, config=self.config, vectors=self.vectors,
            neighbors=_store_neighbors(backing, int(self.vectors.shape[0])),
            codec=self.codec, codes=self.codes, medoid=int(self.medoid),
            filters=self.filters, shards=shards,
        )

    @classmethod
    def load(
        cls,
        path: str,
        config_overrides: dict | None = None,
        *,
        warm_disk: bool = False,
        faults=None,
        **overrides,
    ) -> "GateANNEngine":
        """Restore an engine from a saved index file — no graph build, no
        PQ retraining, bit-identical search results.

        The saved ``EngineConfig`` is the default; ``config_overrides``
        (or keyword overrides) change the *runtime* knobs — e.g.
        ``store_tier="disk"`` serves records off the file with measured
        I/O, ``r_max`` re-slices the neighbor store, ``cache_*`` attaches
        a cache tier.  ``max_gap_sectors`` is the exception: it comes only
        from the overrides, else it is derived from the file's sector size.

        ``warm_disk=True`` starts a background sequential re-read of the
        record segment files right after the disk store opens, so the OS
        page cache is re-populated while the caller is still compiling
        its first search (no-op on non-disk tiers; see
        ``DiskRecordStore.warm``).

        ``faults=`` attaches a ``store.FaultPlan`` to the disk tier's
        read path (testing / chaos benchmarking only — runtime state,
        never persisted, so it is an explicit keyword rather than a
        config override).  Requires ``store_tier="disk"``.
        """
        idx = idx_format.read_index(path)
        h = idx.header
        known = {f.name for f in dataclasses.fields(EngineConfig)}
        user = {**(config_overrides or {}), **overrides}
        unknown = set(user) - known
        if unknown:
            raise ValueError(
                f"unknown EngineConfig override(s) {sorted(unknown)}; "
                f"valid fields: {sorted(known)}"
            )
        # stored configs may carry fields from other format versions —
        # tolerate those, but never silently drop an explicit override.
        # The gap bound is how the disk tier reads, not a property of the
        # index: indexes saved with the old unbounded default store -1.
        cfg = {k: v for k, v in (h.config or {}).items()
               if k in known and k != "max_gap_sectors"}
        cfg.update(user)
        config = EngineConfig(**cfg)
        neighbors = jnp.asarray(idx.neighbors(), jnp.int32)
        books = jnp.asarray(idx.pq_books(), jnp.float32)
        codec = pqm.PQCodec(
            books=books, n_chunks=int(books.shape[0]),
            n_centroids=int(books.shape[1]),
        )
        codes = jnp.asarray(idx.pq_codes(), jnp.int32)
        if config.store_tier == "disk":
            record_store = _open_disk_store(path, config, faults=faults)
            if warm_disk:
                record_store.warm(background=True)
            # the store's LAZY host memmap view — no device transfer, no
            # copy.  The engine's ``vectors`` field is ground-truth/debug
            # state the disk search path never reads; cache selection
            # gathers only hot rows host-side (select_hot_set degrades
            # visit_freq to BFS rather than materialize the corpus)
            vectors = record_store.vectors
        elif faults is not None:
            raise ValueError(
                "faults= wraps the disk tier's read path; this load "
                f"resolves to store_tier={config.store_tier!r}"
            )
        elif config.store_tier == "host":
            vectors = jnp.asarray(idx.vectors(), jnp.float32)
            record_store = HostOffloadRecordStore.create(vectors, neighbors)
        else:
            vectors = jnp.asarray(idx.vectors(), jnp.float32)
            record_store = InMemoryRecordStore(vectors=vectors, neighbors=neighbors)
        record_store = _make_cache_tier(
            record_store, vectors=vectors, neighbors=neighbors,
            medoid=h.medoid, config=config,
        )
        filters = {}
        for kind in idx.filter_kinds():
            arr = idx.filter_array(kind)
            if kind == "label":
                filters[kind] = EqualityFilter(labels=jnp.asarray(arr, jnp.int32))
            elif kind == "range":
                filters[kind] = RangeFilter(values=jnp.asarray(arr, jnp.float32))
            elif kind == "tags":
                filters[kind] = SubsetFilter(tag_bits=jnp.asarray(arr, jnp.uint32))
        return cls(
            config=config,
            vectors=vectors,
            record_store=record_store,
            neighbor_store=NeighborStore.from_graph(neighbors, config.r_max),
            codec=codec,
            codes=codes,
            medoid=jnp.int32(h.medoid),
            filters=filters,
        )

    # -- cache tier --------------------------------------------------------
    def with_cache(
        self,
        budget_bytes: int,
        *,
        policy: str | None = None,
        refresh_every: int | None = None,
        ema_decay: float | None = None,
        cache_partitions: int | None = None,
    ) -> "GateANNEngine":
        """Re-wrap the slow tier at a new cache budget — no index rebuild.

        Like ``r_max``, the cache is a runtime knob: the graph, PQ codes
        and filter stores are shared with ``self``.  ``budget_bytes=0``
        returns an engine with the cache tier removed.  ``policy`` may be
        a static policy (``visit_freq`` / ``bfs``) or ``adaptive``; the
        remaining keywords override the adaptive knobs of ``EngineConfig``.
        """
        backing = self.record_store
        if isinstance(backing, (CachedRecordStore, AdaptiveRecordCache)):
            backing = backing.backing
        cfg = dataclasses.replace(
            self.config,
            cache_budget_bytes=budget_bytes,
            cache_policy=policy or self.config.cache_policy,
            refresh_every=(
                self.config.refresh_every if refresh_every is None else refresh_every
            ),
            ema_decay=self.config.ema_decay if ema_decay is None else ema_decay,
            cache_partitions=(
                self.config.cache_partitions
                if cache_partitions is None
                else cache_partitions
            ),
        )
        store = _make_cache_tier(
            backing,
            vectors=self.vectors,
            neighbors=_store_neighbors(backing, int(self.vectors.shape[0])),
            medoid=int(self.medoid),
            config=cfg,
        )
        return dataclasses.replace(self, config=cfg, record_store=store)

    # -- search ------------------------------------------------------------
    def make_filter(self, kind: str | None, params) -> CheckFn:
        if kind is None:
            return match_all(int(self.codes.shape[0]))
        return self.filters[kind].bind(*params) if isinstance(params, tuple) else self.filters[
            kind
        ].bind(params)

    def search(
        self,
        queries: np.ndarray | jax.Array,
        *,
        filter_kind: str | None = None,
        filter_params=None,
        search_config: searchm.SearchConfig | None = None,
    ) -> searchm.SearchOutput:
        cfg = search_config or searchm.SearchConfig(
            use_fused_kernel=self.config.use_fused_kernel
        )
        q = jnp.asarray(queries, dtype=jnp.float32)
        lut = pqm.build_lut(self.codec, q)
        check = self.make_filter(filter_kind, filter_params)
        store = self.record_store
        cached_mask = None
        visit_counts = None
        bucket = None
        adaptive = isinstance(store, AdaptiveRecordCache)
        if adaptive:
            # between-batch refresh: if the cadence came due and no caller
            # (e.g. RAGServer) already refreshed, catch up before serving
            store.maybe_refresh()
            # route through the partition snapshot for this filter bucket
            # and carry live visit counters through the loop
            bucket = filter_bucket(filter_kind, filter_params)
            store = store.store_for(bucket)
            visit_counts = jnp.zeros((int(self.codes.shape[0]),), jnp.float32)
        if isinstance(store, CachedRecordStore):
            cached_mask = store.cached_mask_fn()
        # pipelined disk search: resolve the async submit/drain pair when
        # the depth asks for overlap AND the (possibly cache-wrapped)
        # store bottoms out at a tier that can serve it (the disk tier).
        # Stores without the pair silently run the synchronous loop —
        # results are bit-identical either way.
        submit = drain = None
        if cfg.pipeline_depth > 1:
            sf = getattr(store, "submit_fn", None)
            df = getattr(store, "drain_fn", None)
            if sf is not None and df is not None:
                submit, drain = sf(), df()
                if submit is None or drain is None:
                    submit = drain = None
        reg = obs.default_registry()
        reg.counter(
            "search.dispatch",
            mode=cfg.mode,
            tier=self.config.store_tier,
            pipelined="1" if submit is not None else "0",
        ).inc()
        try:
            with obs.trace.span("engine.search", mode=cfg.mode):
                out = searchm.filtered_search(
                    fetch=store.fetch_fn(),
                    neighbor_store=self.neighbor_store,
                    filter_check=check,
                    lut=lut,
                    codes=self.codes,
                    entry=self.medoid,
                    queries=q,
                    config=cfg,
                    cached_mask=cached_mask,
                    visit_counts=visit_counts,
                    submit=submit,
                    drain=drain,
                )
                if reg.enabled:
                    # materializes the stats arrays (forcing the ordered
                    # host callbacks to completion) so the span covers
                    # actual I/O, not async dispatch
                    obs.stats.record_search_stats(
                        reg, out.stats,
                        mode=cfg.mode, tier=self.config.store_tier,
                    )
        except BaseException:
            # mid-search failure while a pipelined round is in flight: its
            # submitted-but-undrained token would pin a reader slot and a
            # completion-queue entry until close().  Drain-or-cancel here
            # so a failed search never leaks executor capacity.
            if submit is not None:
                self.abandon_pending_io()
            raise
        if adaptive:
            # fold this batch's counters; the refresh itself runs between
            # batches — either here at the next search's entry, or earlier
            # via a serving layer calling maybe_refresh() off the critical
            # path (RAGServer does, after every batch)
            self.record_store.observe(bucket, out.visit_counts)
        return out

    def warm(
        self,
        queries: np.ndarray | jax.Array,
        *,
        filter_kind: str | None = None,
        filter_params=None,
        search_config: searchm.SearchConfig | None = None,
    ) -> searchm.SearchOutput:
        """Prime the adaptive cache: search, then refresh immediately.

        On a static-cache (or uncached) engine this is just ``search``.
        """
        out = self.search(
            queries,
            filter_kind=filter_kind,
            filter_params=filter_params,
            search_config=search_config,
        )
        if isinstance(self.record_store, AdaptiveRecordCache):
            self.record_store.refresh()
        return out

    def maybe_refresh(self) -> bool:
        """Refresh the adaptive hot sets if the cadence is due."""
        if isinstance(self.record_store, AdaptiveRecordCache):
            return self.record_store.maybe_refresh()
        return False

    # -- measured I/O plumbing ---------------------------------------------
    def measured_store(self) -> DiskRecordStore | None:
        """The slow tier under any cache wrappers, if it measures real
        I/O — serving layers reconcile their modeled accounting against
        its counters.  None when the slow tier only models I/O."""
        store = self.record_store
        while isinstance(store, (CachedRecordStore, AdaptiveRecordCache)):
            store = store.backing
        return store if isinstance(store, DiskRecordStore) else None

    def io_counters(self) -> dict:
        """Measured read counters of the slow tier ({} on modeled tiers)."""
        store = self.measured_store()
        return store.io_counters() if store is not None else {}

    def abandon_pending_io(self) -> int:
        """Drain-or-cancel submitted-but-undrained pipelined disk rounds
        (``DiskRecordStore.abandon_pending``); 0 on non-disk tiers."""
        store = self.measured_store()
        return store.abandon_pending() if store is not None else 0

    # -- reporting ---------------------------------------------------------
    def memory_report(self) -> dict:
        n, d = self.vectors.shape
        rep = {
            "n": n,
            "dim": d,
            "pq_bytes": int(self.codes.shape[0] * self.codes.shape[1]),
            "neighbor_store_bytes": self.neighbor_store.memory_bytes(),
            "filter_store_bytes": {k: f.memory_bytes() for k, f in self.filters.items()},
        }
        store = self.record_store
        if isinstance(store, (CachedRecordStore, AdaptiveRecordCache)):
            rep["cache_nodes"] = store.n_cached
            rep["cache_bytes"] = store.cache_bytes()
            rep["cache_device_bytes"] = store.device_bytes()
            rep["cache_policy"] = store.policy
            if isinstance(store, AdaptiveRecordCache):
                rep["cache_slots"] = store.n_slots
                rep["cache_partitions"] = len(store.partitions)
                rep["cache_refreshes"] = store.n_refreshes
            store = store.backing
        if isinstance(store, InMemoryRecordStore):
            rep["record_tier"] = "memory"
            rep["record_tier_bytes"] = store.record_bytes()
        elif isinstance(store, DiskRecordStore):
            # on-disk footprint + measured (not modeled) read counters
            rep["record_tier"] = "disk"
            rep["record_tier_bytes"] = store.record_bytes()
            rep["disk_path"] = store.path
            rep["disk_index_bytes"] = store.index_bytes()
            rep["disk_sector_bytes"] = store.sector_bytes
            rep["disk_pages_read"] = store.pages_read
            rep["disk_bytes_read"] = store.bytes_read
            rep["disk_io_mode"] = store.io_mode
            rep["disk_shards"] = store.n_shards
            rep["disk_syscalls"] = store.syscalls
            rep["disk_unique_sectors_read"] = store.unique_sectors_read
            rep["disk_inflight_depth_max"] = store.inflight_depth_max
            rep["disk_overlapped_rounds"] = store.overlapped_rounds
            rep["disk_warmed_bytes"] = store.warmed_bytes
            rep["disk_max_gap_sectors"] = store.max_gap_sectors
        elif isinstance(store, HostOffloadRecordStore):
            rep["record_tier"] = "host"
        return rep

    def _refresh_amortized_us(
        self, stats: searchm.SearchStats, cost_model: IOCostModel
    ) -> float:
        """Per-query share of adaptive hot-set refresh cost (0 if static)."""
        store = self.record_store
        if not isinstance(store, AdaptiveRecordCache):
            return 0.0
        return cost_model.refresh_amortized_us(
            store.n_slots * store.last_refresh_sets,
            store.refresh_every,
            int(stats.n_ios.shape[0]),
        )

    def modeled_qps(
        self, stats: searchm.SearchStats, *, n_threads: int = 32,
        cost_model: IOCostModel = DEFAULT_COST_MODEL,
    ) -> float:
        return cost_model.qps(
            float(jnp.mean(stats.n_ios)),
            float(jnp.mean(stats.n_tunnels)),
            n_threads=n_threads,
            n_exact=float(jnp.mean(stats.n_exact)),
            n_cache_hits=float(jnp.mean(stats.n_cache_hits)),
            refresh_amortized_us=self._refresh_amortized_us(stats, cost_model),
        )

    def modeled_latency_us(
        self, stats: searchm.SearchStats, *,
        cost_model: IOCostModel = DEFAULT_COST_MODEL, pipeline_depth: int | None = None,
        overlap_depth: int = 1,
    ) -> float:
        """Modeled per-query latency.  ``pipeline_depth`` is W (in-flight
        reads within a round); ``overlap_depth`` is the software-pipeline
        depth across rounds (``SearchConfig.pipeline_depth``) — device
        read time amortizes across overlapped rounds."""
        return cost_model.latency_us(
            float(jnp.mean(stats.n_ios)),
            float(jnp.mean(stats.n_tunnels)),
            float(jnp.mean(stats.n_exact)),
            pipeline_depth=pipeline_depth,
            n_cache_hits=float(jnp.mean(stats.n_cache_hits)),
            refresh_amortized_us=self._refresh_amortized_us(stats, cost_model),
            overlap_depth=overlap_depth,
        )


def recall_at_k(result_ids: jax.Array, gt_ids: np.ndarray, k: int = 10) -> float:
    """Recall@k against exact filtered ground truth (rows -1-padded).

    Vectorized broadcast membership count — a (B, k, k) equality mask
    instead of per-row Python sets (this is the hot path of the recall
    regression suite and every benchmark sweep).  Ground-truth rows hold
    unique ids, so counting each matched gt id once is exactly the set
    intersection of the old implementation.
    """
    res = np.asarray(result_ids)[:, :k]
    gt = np.asarray(gt_ids)[:, :k]
    gt_valid = gt >= 0
    found = (gt[:, :, None] == res[:, None, :]) & (res[:, None, :] >= 0)
    hits = int((found.any(axis=2) & gt_valid).sum())
    return hits / max(int(gt_valid.sum()), 1)
