"""File-backed slow tier — the record store that actually does I/O.

``DiskRecordStore`` serves ``(B, W)`` id beams straight off the
page-aligned record section of an index file (store/format.py) through
``jax.experimental.io_callback``: the jitted search loop dispatches a
beam, the host callback reads the corresponding 4 KB-aligned sectors,
and the result re-enters the trace.  Same ``RecordFetchFn`` contract as
the in-memory/host/sharded stores, so the cache tiers
(``CachedRecordStore`` / ``AdaptiveRecordCache``) wrap it unchanged — a
cache hit masks the id to -1 before the callback, so a hit costs zero
file reads.

The read path is **coalesced**, the way PipeANN keeps W reads in flight
instead of issuing them one by one: each round's beam is sorted,
deduplicated, and merged into contiguous sector ranges, then fetched as

  * ``io_mode="preadv"`` (default where available) — vectored
    ``os.preadv`` calls per round and segment: wanted ranges scatter
    directly into the output buffer, and a hole between two of them
    that is cheaper to read than to skip lands in a reusable discard
    buffer (counted in ``gap_sectors_read``); a wider hole starts
    another call (counted in ``split_gaps``).  Groups wider than
    ``IOV_MAX`` split into further counted calls.
  * ``io_mode="pread"`` — one ``os.pread`` per merged range (no
    over-read; ``syscalls == ranges_read``).
  * ``io_mode="gather"`` — the legacy per-record memmap fancy-gather
    (page faults, no explicit syscalls; kept as the parity oracle).

Results are scattered back to beam order, so search output is
bit-identical across all three modes.

Unlike every other tier, this one *measures* its I/O instead of modeling
it.  Two counter families advance inside the host callback, guarded by a
``threading.Lock`` (engines sharing one store — every ``with_cache``
re-wrap does — must not lose updates):

  * logical  — ``records_read`` / ``pages_read`` / ``bytes_read``: the
    sectors the search loop *requested* (duplicates included).  These
    reconcile EXACTLY with summed ``SearchStats.n_ios`` — the mask
    discipline check (cache hits and filter-gated nodes never reach the
    file).
  * physical — ``unique_sectors_read`` / ``ranges_read`` / ``syscalls``
    / ``gap_sectors_read`` / ``read_rounds`` / ``split_gaps``: what the
    coalesced reader actually did.  Contract: ``unique_sectors_read <=
    records_read`` with equality when a round has no intra-round
    duplicates, and on the preadv path, without short reads or
    ``IOV_MAX`` splits, one vectored read per touched segment per round
    plus one per hole left unbridged — ``syscalls == read_rounds +
    split_gaps`` on an unsharded index.

Bridged holes are bounded by ``max_gap_sectors``: a hole wider than the
bound is not read through; the round starts another vectored call after
it.  By default (``None``) the bound is derived from the file: bridge a
hole only while reading it costs less than one more random read, taken
as ``MAX_BRIDGE_BYTES`` (128 KiB, the kernel's default read-ahead
window) over the record sector size — 32 sectors at 4 KiB records.  A
negative bound bridges every hole (one call per round and segment, at
the cost of reading from a round's first record to its last); ``0``
never bridges (one call per merged range, no over-read).  The bound is
how this tier reads, not a property of a saved index, so
``GateANNEngine.load`` takes it only from its caller.

**Asynchronous pipeline interface** (the PipeANN overlap, done host-side):
``submit(ids) -> (token, nbrs)`` enqueues the round's coalesced sector
read on a background reader pool and returns immediately with the
neighbor lists served from the index file's full-adjacency *sidecar* —
traversal needs only neighbor lists and PQ distances, never the
full-precision record, so the search loop can dispatch round r+1's beam
while round r's ``preadv`` is still in flight.  ``drain(token) ->
records`` blocks until that round's read completes and returns the
record vectors for the exact-distance result pool.  Reads stay
bit-identical to the synchronous ``fetch_fn`` path (same coalesced
reader, same counters); two extra counters measure the overlap actually
achieved: ``inflight_depth_max`` (peak submitted-but-undrained tokens)
and ``overlapped_rounds`` (submissions issued while an earlier read was
still undrained).

A sharded index (``engine.save(shards=k)``) opens one reader per record
segment; only the segments a round's beam touches are read (and on a
mesh, ``core.distributed_search.load_shard_records`` opens just the
local shard's file).

``warm(background=True)`` sequentially re-reads the segment files on a
daemon thread to re-populate the OS page cache after a load (counted in
``warmed_bytes``); ``close()`` only signals it to stop — it never blocks
on the warmer.

**Resilience** (``RetryPolicy`` / ``on_error`` / ``round_deadline_s``):
transient read errors (``TRANSIENT_ERRNOS``) retry with bounded
exponential backoff + seeded jitter (``retried_ios``/``retry_exhausted``
counters, ``disk.retry`` obs spans); a per-round deadline bounds how
long one fetch round may spend in I/O (``deadline_trips``).  When
retries exhaust or the deadline trips, ``on_error="degrade"`` marks the
failed records instead of raising: their vectors come back as the +inf
tunnel sentinel and their neighbor lists from the adjacency sidecar, so
the search loop keeps full graph connectivity and simply drops the slots
from the exact-ranked results — GateANN's own tunneling, repurposed as
the degraded mode (``degraded_records``; ``SearchStats.n_degraded``
carries the per-query view).  Logical counters keep counting every
*requested* record under faults, so n_ios reconciliation is fault-proof.
``store/faults.py`` injects deterministic faults underneath all of this
for tests and the chaos-matrix nightly.

Counter discipline: jax dispatch is asynchronous, so read the counters
only after materializing the search outputs (``np.asarray(out.ids)`` or
``jax.block_until_ready``) — every fetch feeds the loop-carried state, so
output materialization implies all callbacks ran (a drain blocks on its
round's read, so retired rounds have fully-counted I/O).
"""
from __future__ import annotations

import dataclasses
import errno
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback
from jax.tree_util import Partial

from repro import obs
from repro.store.format import (
    PAGE_BYTES,
    SEC_NEIGHBORS,
    SEGMENT_HEADER_PAGES,
    IndexFile,
    record_dtype,
    read_header,
)
from repro.store.vector_store import is_lazy_host  # re-export (home base)

_HAVE_PREADV = hasattr(os, "preadv")
_HAVE_PREAD = hasattr(os, "pread")
_IOV_MAX = 1000  # stay under the kernel's 1024-iovec ceiling
_GAP_CHUNK = 1 << 20  # discard-buffer granularity for bridged gaps
# the widest hole the preadv path reads through by default: past the
# kernel's default read-ahead window, one more random read is cheaper
MAX_BRIDGE_BYTES = 128 << 10

IO_MODES = ("preadv", "pread", "gather")

# error taxonomy: these errnos are worth retrying — the device/page-cache
# path can transiently fail (EIO on a flaky link, EAGAIN under pressure,
# EINTR on a signal, ETIMEDOUT from network-backed block devices) and
# succeed on the reattempt.  Everything else (EBADF, ENOENT, EFAULT, a
# short-read EOF, ...) means the request itself is wrong or the file is
# gone: retrying cannot help, so those raise immediately whatever the
# policy says.
TRANSIENT_ERRNOS = frozenset(
    {errno.EIO, errno.EAGAIN, errno.EINTR, errno.ETIMEDOUT}
)

ON_ERROR_POLICIES = ("fail", "degrade")


def is_transient(exc: BaseException) -> bool:
    """True for OSErrors a bounded retry may fix (see TRANSIENT_ERRNOS)."""
    return isinstance(exc, OSError) and exc.errno in TRANSIENT_ERRNOS


class ReadDeadlineError(OSError):
    """The per-round read deadline tripped before this read completed.

    Carries ``errno.ETIMEDOUT`` so the degrade path treats it like any
    other exhausted transient error (the round's remaining slots degrade
    instead of failing the query)."""

    def __init__(self, msg: str):
        super().__init__(errno.ETIMEDOUT, msg)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry with exponential backoff + deterministic jitter for
    transient read errors.  ``max_retries=0`` (the default) preserves the
    historical fail-fast behavior exactly."""

    max_retries: int = 0
    backoff_s: float = 1e-3  # first backoff; doubles (backoff_mult) after
    backoff_mult: float = 2.0
    jitter: float = 0.5  # +/- fraction of each backoff, seeded, not wall-clock
    seed: int = 0

    def backoff(self, attempt: int) -> float:
        """Backoff before retry ``attempt`` (1-based), jitter applied.

        Deterministic: the jitter draw is a pure function of
        ``(seed, attempt)``, so a scripted fault test sleeps the same
        amount every run."""
        delay = self.backoff_s * self.backoff_mult ** (attempt - 1)
        if self.jitter > 0.0:
            u = float(np.random.default_rng((self.seed, attempt)).random())
            delay *= 1.0 + self.jitter * (2.0 * u - 1.0)
        return max(delay, 0.0)


def default_io_mode() -> str:
    if _HAVE_PREADV:
        return "preadv"
    if _HAVE_PREAD:
        return "pread"
    return "gather"


def merge_ranges(sectors: np.ndarray) -> np.ndarray:
    """Sorted unique sector ids -> (R, 2) [start, count) contiguous runs."""
    sectors = np.asarray(sectors, np.int64)
    if sectors.size == 0:
        return np.zeros((0, 2), np.int64)
    breaks = np.flatnonzero(np.diff(sectors) != 1)
    starts = np.concatenate([[0], breaks + 1])
    ends = np.concatenate([breaks, [sectors.size - 1]])
    return np.stack([sectors[starts], ends - starts + 1], axis=1)


def _preadv_full(readv, views, offset) -> int:
    """Vectored read of ``views`` at ``offset``, resuming short reads and
    chunking at IOV_MAX; returns the number of preadv calls issued.

    ``readv(batch, off) -> int`` is an ``os.preadv``-compatible callable
    with the fd bound — the raw syscall, the fault injector's wrapper,
    or the store's retrying wrapper."""
    calls = 0
    pending = list(views)
    off = int(offset)
    while pending:
        batch = pending[:_IOV_MAX]
        want = sum(len(v) for v in batch)
        got = readv(batch, off)
        calls += 1
        if got <= 0:
            raise IOError(f"preadv: unexpected EOF at offset {off}")
        off += got
        if got == want:
            pending = pending[_IOV_MAX:]
            continue
        # short read (EOF excluded by validation; signals can still truncate)
        k = 0
        while got >= len(batch[k]):
            got -= len(batch[k])
            k += 1
        rest = list(batch[k:])
        if got:
            rest[0] = rest[0][got:]
        pending = rest + pending[_IOV_MAX:]
    return calls


def _pread_full(read, view, offset) -> int:
    """Plain positional read into ``view``; returns syscalls issued.

    ``read(n, off) -> bytes`` is an ``os.pread``-compatible callable
    with the fd bound."""
    calls = 0
    off = int(offset)
    mv = memoryview(view)
    while len(mv):
        data = read(len(mv), off)
        calls += 1
        if not data:
            raise IOError(f"pread: unexpected EOF at offset {off}")
        mv[: len(data)] = data
        mv = mv[len(data):]
        off += len(data)
    return calls


def _passthrough_gather(fn):
    """The uninjected gather entry point: just run the memmap gather."""
    return fn()


@dataclasses.dataclass
class _Segment:
    """One open record file: fd for coalesced reads, lazy memmap for the
    gather oracle and the lazy ``vectors`` view."""

    path: str
    row_start: int
    n_rows: int
    data_offset: int  # file offset of sector 0 (row ``row_start``)
    rec_dtype: np.dtype
    fd: int = -1  # guarded by _open_lock
    _mmap: np.memmap | None = None  # guarded by _open_lock
    # first-open is lazy and stores are shared across threads — an
    # unsynchronized double-open would leak the losing thread's fd
    _open_lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)

    def open_fd(self) -> int:
        if self.fd < 0:
            with self._open_lock:
                if self.fd < 0:
                    self.fd = os.open(self.path, os.O_RDONLY)
        return self.fd

    def records(self) -> np.memmap:
        if self._mmap is None:
            with self._open_lock:
                if self._mmap is None:
                    self._mmap = np.memmap(
                        self.path, dtype=self.rec_dtype, mode="r",
                        offset=self.data_offset, shape=(self.n_rows,),
                    )
        return self._mmap

    def close(self) -> None:
        with self._open_lock:
            if self.fd >= 0:
                os.close(self.fd)
                self.fd = -1
            self._mmap = None


class LazySegmentVectors:
    """Read-only lazy ``(N, D)`` corpus view over per-segment record
    memmaps — the sharded counterpart of the single-segment memmap view.

    Row indexing (int / slice / integer- or boolean-array) gathers ONLY
    the touched rows off the touched segments; ``np.asarray`` is the
    explicit materialization (ground-truth/debug) path.  Flagged
    ``__lazy_host__`` so ``is_lazy_host`` keeps cache wiring host-side
    regardless of segment count.
    """

    __lazy_host__ = True

    def __init__(self, segments: list[_Segment], dim: int):
        self._segments = segments
        self._row_starts = np.asarray([s.row_start for s in segments], np.int64)
        self._n = segments[-1].row_start + segments[-1].n_rows
        self._dim = int(dim)

    @property
    def shape(self) -> tuple:
        return (self._n, self._dim)

    @property
    def dtype(self):
        return np.dtype(np.float32)

    ndim = 2

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, idx):
        if isinstance(idx, (int, np.integer)):
            if not -self._n <= idx < self._n:
                raise IndexError(f"row {idx} out of range [0, {self._n})")
            return self[np.asarray([idx], np.int64)][0]
        if isinstance(idx, slice):
            idx = np.arange(*idx.indices(self._n), dtype=np.int64)
        idx = np.asarray(idx)
        if idx.dtype == bool:
            idx = np.flatnonzero(idx)
        if idx.ndim != 1:
            raise TypeError(
                "LazySegmentVectors supports 1-D row indexing only; "
                "np.asarray(...) it for anything fancier"
            )
        rows = np.where(idx < 0, idx + self._n, idx).astype(np.int64)
        out = np.empty((rows.size, self._dim), np.float32)
        seg_of = np.searchsorted(self._row_starts, rows, side="right") - 1
        for si in np.unique(seg_of):
            seg = self._segments[si]
            mask = seg_of == si
            out[mask] = seg.records()["vec"][rows[mask] - seg.row_start]
        return out

    def __array__(self, dtype=None, copy=None):  # noqa: D105 — np protocol
        out = np.concatenate([s.records()["vec"] for s in self._segments])
        return out.astype(dtype) if dtype is not None else out


class DiskRecordStore:
    """Slow-tier record store backed by an on-disk index file."""

    def __init__(
        self,
        path: str,
        *,
        io_mode: str = "auto",
        max_gap_sectors: int | None = None,
        reader_threads: int = 4,
        faults=None,  # FaultPlan (store/faults.py) — testing/chaos only
        retry: RetryPolicy | None = None,
        on_error: str = "fail",
        round_deadline_s: float = 0.0,
    ):
        header = read_header(path)
        self.path = path
        self.header = header
        self.n = header.n
        self.dim = header.dim
        self.degree = header.degree
        self.sector_bytes = header.sector_bytes
        self.pages_per_record = header.sector_bytes // PAGE_BYTES
        if io_mode == "auto":
            io_mode = default_io_mode()
        if io_mode not in IO_MODES:
            raise ValueError(f"io_mode={io_mode!r} not in {IO_MODES}")
        if io_mode == "preadv" and not _HAVE_PREADV:
            io_mode = "pread" if _HAVE_PREAD else "gather"
        if io_mode == "pread" and not _HAVE_PREAD:
            io_mode = "gather"
        self.io_mode = io_mode
        # preadv gap-bridging bound, in sectors: None = derived from the
        # sector size, negative = unbounded (resolved to -1), 0 = never
        if max_gap_sectors is None:
            max_gap_sectors = MAX_BRIDGE_BYTES // self.sector_bytes
        self.max_gap_sectors = max(int(max_gap_sectors), -1)
        self.reader_threads = max(int(reader_threads), 1)
        # resilience policy: how transient read errors are retried and what
        # happens when retries exhaust / the round deadline trips.  All
        # three knobs may be retuned at runtime (configure_resilience).
        if on_error not in ON_ERROR_POLICIES:
            raise ValueError(f"on_error={on_error!r} not in {ON_ERROR_POLICIES}")
        self.retry_policy = retry if retry is not None else RetryPolicy()
        self.on_error = on_error
        self.round_deadline_s = float(round_deadline_s)  # 0 = no deadline
        # fault injection (store/faults.py): the injector wraps the three
        # os-level read entry points; every io_mode and the async reader
        # pool flow through them, nothing else changes.  With faults=None
        # the raw os calls are bound directly — zero overhead.
        self._injector = faults.injector() if faults is not None else None
        if self._injector is not None:
            self._io_preadv = self._injector.preadv
            self._io_pread = self._injector.pread
            self._io_gather = self._injector.gather
        else:
            self._io_preadv = os.preadv if _HAVE_PREADV else None
            self._io_pread = os.pread if _HAVE_PREAD else None
            self._io_gather = _passthrough_gather
        # measured, monotonic I/O counters (advanced by the host callback,
        # guarded by _lock — stores are shared across with_cache re-wraps
        # and may serve several engines/threads at once)
        self._lock = threading.Lock()
        self._reset_counters_locked()
        # telemetry: mirror the measured counters into registry families
        # (captured at construction — tests swap in private registries via
        # obs.use_registry).  Registry counters are MONOTONIC for the
        # registry's lifetime: reset_io_counters() resets only the store
        # attributes above, so cross-reset contracts compare registry
        # totals against registry totals (search.ios vs disk.records_read).
        self._obs = obs.default_registry()
        self._obs_label = os.path.basename(path)
        mk = lambda name: self._obs.counter(name, store=self._obs_label)  # noqa: E731
        self._obs_counters = {
            "records_read": mk("disk.records_read"),
            "pages_read": mk("disk.pages_read"),
            "bytes_read": mk("disk.bytes_read"),
            "unique_sectors_read": mk("disk.unique_sectors_read"),
            "ranges_read": mk("disk.ranges_read"),
            "syscalls": mk("disk.syscalls"),
            "gap_sectors_read": mk("disk.gap_sectors_read"),
            "split_gaps": mk("disk.split_gaps"),
            "fetch_rounds": mk("disk.fetch_rounds"),
            "read_rounds": mk("disk.read_rounds"),
            "overlapped_rounds": mk("disk.overlapped_rounds"),
            "submits": mk("disk.submits"),
            "drains": mk("disk.drains"),
            "abandoned_tokens": mk("disk.abandoned_tokens"),
            "abandon_events": mk("disk.abandon_events"),
            "warmed_bytes": mk("disk.warmed_bytes"),
            "retried_ios": mk("disk.retried_ios"),
            "retry_exhausted": mk("disk.retry_exhausted"),
            "deadline_trips": mk("disk.deadline_trips"),
            "degraded_records": mk("disk.degraded_records"),
            "warm_errors": mk("disk.warm_errors"),
        }
        self._obs_inflight = self._obs.gauge(
            "disk.inflight_depth", store=self._obs_label
        )
        rd = record_dtype(header.dim, header.degree)
        idx = IndexFile(header)
        if header.shards:
            self._segments = []
            for i, seg in enumerate(header.shards["segments"]):
                idx.segment_records(i)  # validates the GSEG header now
                self._segments.append(_Segment(
                    path=header.segment_path(i),
                    row_start=seg["row_start"], n_rows=seg["n_rows"],
                    data_offset=SEGMENT_HEADER_PAGES * PAGE_BYTES,
                    rec_dtype=rd,
                ))
        else:
            self._segments = [_Segment(
                path=path, row_start=0, n_rows=header.n,
                data_offset=header.sections["records"]["offset"],
                rec_dtype=rd,
            )]
        self._row_starts = np.asarray(
            [s.row_start for s in self._segments], np.int64
        )
        self._scratch = bytearray(0)  # discard buffer for bridged gaps
        self._neighbors = None  # lazy full-adjacency parse (host convenience)
        self._nbrs_host = None  # lazy host memmap of the adjacency sidecar
        self._vectors_view = None  # lazy host view — never a device array
        # async submission/completion state: a background reader pool plus
        # the completion queue (token -> in-flight Future), all under _lock
        self._pool: ThreadPoolExecutor | None = None
        self._pending: dict[int, object] = {}  # guarded by _lock
        self._next_token = 0  # guarded by _lock
        self._inflight = 0  # submitted-but-undrained tokens, live not reset; guarded by _lock
        # background page-cache warmer (non-blocking close: stop is an event)
        self._warm_stop = threading.Event()
        self._warm_thread: threading.Thread | None = None
        # one Partial per store: stable pytree identity, so repeated
        # searches against the same store never retrace the jitted loop
        self._fetch = Partial(self._traced_fetch)
        self._submit = Partial(self._traced_submit)
        self._drain = Partial(self._traced_drain)

    @classmethod
    def open(cls, path: str, **kwargs) -> "DiskRecordStore":
        return cls(path, **kwargs)

    def close(self) -> None:
        self._warm_stop.set()  # signal only — never blocks on the warmer
        # tokens nobody will ever drain are leaks — retire them first so
        # close() is also the backstop that makes them visible
        self.abandon_pending()
        pool = self._pool
        if pool is not None:
            # let queued reads finish against still-open fds, then drop
            # whatever results nobody will drain
            pool.shutdown(wait=True)
            self._pool = None
        with self._lock:
            self._pending.clear()
            self._inflight = 0
        for seg in self._segments:
            seg.close()

    def abandon_pending(self) -> int:
        """Drain-or-cancel every submitted-but-undrained round.

        The pipelined search loop issues one drain per submit, so on the
        happy path the completion queue runs dry by itself.  If the caller
        dies between stage A and stage B (a search error surfacing at
        materialization, a serving batch failing mid-flight), the rounds
        still in flight would otherwise pin executor slots and queue
        entries until ``close()``.  This is the ``finally`` path: cancel
        what hasn't started, block out what has (the reads run against
        still-open fds and their I/O is already counted), and count every
        retired token in ``abandoned_tokens`` — asserted zero by the
        happy-path tests, so a leak is a test failure, not a slow death.
        """
        with self._lock:
            orphans = list(self._pending.values())
            self._pending.clear()
            self._inflight = 0
        for fut in orphans:
            if not fut.cancel():
                try:
                    fut.result()  # already running: let the read finish
                except Exception:  # gatelint: disable=silent-except — the abandoning caller is already unwinding with its own exception; this read's I/O is counted and its result unwanted
                    pass
        if orphans:
            with self._lock:
                self.abandoned_tokens += len(orphans)
            if self._obs.enabled:
                self._obs_counters["abandoned_tokens"].inc(len(orphans))
                self._obs_counters["abandon_events"].inc()
                self._obs_inflight.set(0)
        return len(orphans)

    def __del__(self):  # best-effort fd cleanup
        try:
            self.close()
        except Exception:  # gatelint: disable=silent-except — interpreter-teardown destructor; attributes may already be collected and there is no caller to report to
            pass

    # -- the coalesced physical read ---------------------------------------
    def _gap_views(self, gap_bytes: int) -> list:
        """Discard iovecs bridging ``gap_bytes`` (reused buffer — preadv
        overwrites it per gap, and the contents are never looked at)."""
        chunk = min(gap_bytes, _GAP_CHUNK)
        if len(self._scratch) < chunk:
            self._scratch = bytearray(chunk)
        views = []
        mv = memoryview(self._scratch)
        while gap_bytes:
            take = min(gap_bytes, _GAP_CHUNK)
            views.append(mv[:take])
            gap_bytes -= take
        return views

    def _with_retries(self, fn, *, deadline, tally):
        """Run one raw read call with the resilience policy applied.

        Transient OSErrors (see ``TRANSIENT_ERRNOS``) retry up to
        ``retry_policy.max_retries`` times with exponential backoff +
        seeded jitter; each reattempt is counted in the round tally's
        ``retried_ios`` and timed under a ``disk.retry`` span.  Fatal
        errors raise immediately.  A tripped ``deadline`` (absolute
        ``perf_counter`` seconds, None = no deadline) raises
        :class:`ReadDeadlineError` before issuing further I/O; backoffs
        are clipped so a retry never sleeps past it."""
        rp = self.retry_policy
        attempt = 0
        while True:
            if deadline is not None and time.perf_counter() >= deadline:
                raise ReadDeadlineError(
                    f"round deadline ({self.round_deadline_s:.4f}s) tripped"
                )
            try:
                return fn()
            except OSError as e:
                if not is_transient(e):
                    raise
                if attempt >= rp.max_retries:
                    tally["retry_exhausted"] += 1
                    raise
                attempt += 1
                tally["retried_ios"] += 1
                delay = rp.backoff(attempt)
                if deadline is not None:
                    delay = min(delay, max(deadline - time.perf_counter(), 0.0))
                with obs.trace.span("disk.retry", store=self._obs_label,
                                    errno=str(e.errno)):
                    time.sleep(delay)

    def _fail_span(self, ok, tally, lo, hi, exc) -> None:
        """One read group (a vectored call / merged range / segment
        gather) failed after retries.  Under ``on_error="degrade"`` and a
        transient cause, mark the group's wanted-record span failed — the
        whole group, conservatively, since a mid-group error leaves the
        buffer's valid prefix unknown — and keep reading the rest of the
        round.  Fatal errors and the ``"fail"`` policy re-raise."""
        if isinstance(exc, ReadDeadlineError):
            tally["deadline_trips"] = 1  # once per round, not per group
        if self.on_error != "degrade" or not is_transient(exc):
            raise exc
        ok[lo:hi] = False

    def _read_unique(self, uniq: np.ndarray, io: dict) -> Tuple[np.ndarray, np.ndarray]:
        """Read the (sorted, unique) record sectors ``uniq`` coalesced.

        ``io`` is the caller's physical-I/O tally for this round
        (syscalls / ranges / gap sectors / retry counters) — advanced
        in place so the evidence of completed calls and exhausted
        retries survives even when a fatal/``"fail"``-policy error
        unwinds this read.  Returns the (U,) structured records and a
        (U,) bool mask of which records were actually read — all-True
        unless ``on_error="degrade"`` absorbed a failed group (those
        records' buffer contents are garbage and must not be served).
        """
        sector = self.sector_bytes
        u = int(uniq.size)
        buf = np.empty(u * sector, np.uint8)
        out_mv = memoryview(buf)
        ok = np.ones(u, bool)
        deadline = None
        if self.round_deadline_s > 0.0:
            deadline = time.perf_counter() + self.round_deadline_s
        seg_of = np.searchsorted(self._row_starts, uniq, side="right") - 1
        bounds = np.searchsorted(seg_of, np.arange(len(self._segments) + 1))
        pos = 0  # output cursor: sorted ids -> contiguous output slices
        for si in range(len(self._segments)):
            lo, hi = int(bounds[si]), int(bounds[si + 1])
            if lo == hi:
                continue
            seg = self._segments[si]
            local = uniq[lo:hi] - seg.row_start
            ranges = merge_ranges(local)
            io["ranges"] += int(ranges.shape[0])
            if self.io_mode == "gather":
                try:
                    got = self._with_retries(
                        lambda: self._io_gather(lambda: seg.records()[local]),
                        deadline=deadline, tally=io,
                    )
                    buf.view(self._segments[0].rec_dtype)[pos : pos + local.size] = got
                except OSError as e:
                    self._fail_span(ok, io, pos, pos + local.size, e)
                pos += local.size
                continue
            fd = seg.open_fd()
            readv = lambda batch, off: self._with_retries(  # noqa: E731
                lambda: self._io_preadv(fd, batch, off),
                deadline=deadline, tally=io,
            )
            read1 = lambda n, off: self._with_retries(  # noqa: E731
                lambda: self._io_pread(fd, n, off),
                deadline=deadline, tally=io,
            )
            if self.io_mode == "pread":
                for start, count in ranges:
                    nb = int(count) * sector
                    try:
                        io["syscalls"] += _pread_full(
                            read1, out_mv[pos * sector : pos * sector + nb],
                            seg.data_offset + int(start) * sector,
                        )
                    except OSError as e:
                        self._fail_span(ok, io, pos, pos + int(count), e)
                    pos += int(count)
                continue
            # preadv: wanted ranges scatter straight into the output,
            # bridged gaps land in the discard buffer.  A gap wider than
            # max_gap_sectors is never bridged: the round splits into
            # another vectored call there instead, trading a syscall for
            # the over-read.  Groups are collected first, then issued, so
            # a failed group maps cleanly to its wanted-record span.
            max_gap = self.max_gap_sectors
            groups = []  # (views, group_start_sector, pos_lo, pos_hi)
            views = []
            prev_end = None
            group_start = 0
            gpos_lo = pos
            for start, count in ranges:
                gap = 0 if prev_end is None else int(start - prev_end)
                if views and 0 <= max_gap < gap:
                    io["split_gaps"] += 1
                    groups.append((views, group_start, gpos_lo, pos))
                    views = []
                    prev_end = None
                    gap = 0
                if prev_end is None:
                    group_start = int(start)
                    gpos_lo = pos
                elif gap:
                    io["gap_sectors"] += gap
                    views.extend(self._gap_views(gap * sector))
                nb = int(count) * sector
                views.append(out_mv[pos * sector : pos * sector + nb])
                pos += int(count)
                prev_end = int(start + count)
            groups.append((views, group_start, gpos_lo, pos))
            for g_views, g_start, g_lo, g_hi in groups:
                try:
                    io["syscalls"] += _preadv_full(
                        readv, g_views, seg.data_offset + g_start * sector
                    )
                except OSError as e:
                    self._fail_span(ok, io, g_lo, g_hi, e)
        return buf.view(self._segments[0].rec_dtype), ok

    # -- the measured host read --------------------------------------------
    def _host_fetch(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Serve record sectors for ``ids`` (>= 0); count what was read."""
        ids = np.asarray(ids)
        valid = ids >= 0
        flat = np.clip(ids, 0, self.n - 1).reshape(-1)
        vmask = valid.reshape(-1)
        vecs = np.zeros(ids.shape + (self.dim,), np.float32)
        nbrs = np.full(ids.shape + (self.degree,), -1, np.int32)
        m = int(vmask.sum())
        io = {"syscalls": 0, "ranges": 0, "gap_sectors": 0, "split_gaps": 0,
              "retried_ios": 0, "retry_exhausted": 0, "deadline_trips": 0}
        u = 0
        n_degraded = 0
        if m:
            uniq, inv = np.unique(flat[vmask], return_inverse=True)
            u = int(uniq.size)
            try:
                with obs.trace.span("disk.preadv", store=self._obs_label,
                                    io_mode=self.io_mode):
                    recs, ok_u = self._read_unique(uniq, io)
            except OSError:
                # the raise unwinds this fetch, but completed syscalls and
                # exhausted retries already happened — fold the physical
                # evidence before propagating so a "fail"-policy error
                # never hides its retry history from the counters (no
                # records served, so the logical counters stay untouched)
                with self._lock:
                    self.ranges_read += io["ranges"]
                    self.syscalls += io["syscalls"]
                    self.gap_sectors_read += io["gap_sectors"]
                    self.split_gaps += io["split_gaps"]
                    self.retried_ios += io["retried_ios"]
                    self.retry_exhausted += io["retry_exhausted"]
                    self.deadline_trips += io["deadline_trips"]
                    self.fetch_rounds += 1
                    self.read_rounds += 1
                if self._obs.enabled:
                    c = self._obs_counters
                    c["ranges_read"].inc(io["ranges"])
                    c["syscalls"].inc(io["syscalls"])
                    c["gap_sectors_read"].inc(io["gap_sectors"])
                    c["split_gaps"].inc(io["split_gaps"])
                    c["retried_ios"].inc(io["retried_ios"])
                    c["retry_exhausted"].inc(io["retry_exhausted"])
                    c["deadline_trips"].inc(io["deadline_trips"])
                    c["fetch_rounds"].inc()
                    c["read_rounds"].inc()
                raise
            got = recs[inv]  # scatter back to beam order (dups included)
            gvec = got["vec"]
            gnbr = got["nbrs"]
            if not ok_u.all():
                # degraded slots: the buffer bytes for a failed group are
                # garbage.  Replace the vector with the +inf sentinel (the
                # search loop drops the exact-distance contribution — the
                # GateANN tunnel semantics) and serve the neighbor list
                # from the adjacency sidecar, so traversal/connectivity is
                # IDENTICAL to a successful fetch.  fancy-indexing ``recs``
                # already copied, so in-place writes are safe.
                bad = ~ok_u[inv]
                n_degraded = int(bad.sum())
                gvec[bad] = np.inf
                gnbr[bad] = self._adjacency_host()[flat[vmask][bad]]
            vecs.reshape(-1, self.dim)[vmask] = gvec
            nbrs.reshape(-1, self.degree)[vmask] = gnbr
        with self._lock:
            # logical counters keep counting every REQUESTED record —
            # degraded reads included — so n_ios reconciliation holds
            # under faults; degraded_records carries the failure tally
            self.records_read += m
            self.pages_read += m * self.pages_per_record
            self.bytes_read += m * self.sector_bytes
            self.unique_sectors_read += u
            self.ranges_read += io["ranges"]
            self.syscalls += io["syscalls"]
            self.gap_sectors_read += io["gap_sectors"]
            self.split_gaps += io["split_gaps"]
            self.fetch_rounds += 1
            self.read_rounds += int(u > 0)
            self.retried_ios += io["retried_ios"]
            self.retry_exhausted += io["retry_exhausted"]
            self.deadline_trips += io["deadline_trips"]
            self.degraded_records += n_degraded
        if self._obs.enabled:
            c = self._obs_counters
            # records BEFORE unique: a registry snapshot taken between the
            # two increments under-counts unique, so the mid-flight
            # invariant unique_sectors_read <= records_read always holds
            c["records_read"].inc(m)
            c["pages_read"].inc(m * self.pages_per_record)
            c["bytes_read"].inc(m * self.sector_bytes)
            c["unique_sectors_read"].inc(u)
            c["ranges_read"].inc(io["ranges"])
            c["syscalls"].inc(io["syscalls"])
            c["gap_sectors_read"].inc(io["gap_sectors"])
            c["split_gaps"].inc(io["split_gaps"])
            c["fetch_rounds"].inc()
            c["read_rounds"].inc(int(u > 0))
            if io["retried_ios"]:
                c["retried_ios"].inc(io["retried_ios"])
            if io["retry_exhausted"]:
                c["retry_exhausted"].inc(io["retry_exhausted"])
            if io["deadline_trips"]:
                c["deadline_trips"].inc(io["deadline_trips"])
            if n_degraded:
                c["degraded_records"].inc(n_degraded)
        return vecs, nbrs

    def _traced_fetch(self, ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
        out_shapes = (
            jax.ShapeDtypeStruct(ids.shape + (self.dim,), jnp.float32),
            jax.ShapeDtypeStruct(ids.shape + (self.degree,), jnp.int32),
        )
        # ordered: fetches must all execute (and in program order) so the
        # measured counters reconcile exactly with SearchStats.n_ios
        return io_callback(self._host_fetch_sync, out_shapes, ids, ordered=True)

    def _host_fetch_sync(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """``_host_fetch`` as the search loop's synchronous callback, under
        the ``disk.fetch`` span (the reader pool's calls are not: their
        reads are ``disk.preadv`` spans and their wait ``disk.drain_wait``)."""
        with obs.trace.span("disk.fetch", store=self._obs_label):
            return self._host_fetch(ids)

    def fetch_fn(self):
        return self._fetch

    # -- the asynchronous submission/completion pair -----------------------
    def _adjacency_host(self) -> np.ndarray:
        """Host view of the full-adjacency sidecar section (N, R) int32.

        This is what makes the pipeline bit-identical: the sidecar holds
        the exact array the record sectors' ``nbrs`` fields were packed
        from, so serving neighbor lists here instead of from the in-flight
        record read changes nothing but the wait."""
        if self._nbrs_host is None:
            with self._lock:
                if self._nbrs_host is None:
                    self._nbrs_host = IndexFile(self.header).section(SEC_NEIGHBORS)
        return self._nbrs_host

    def _host_submit(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Enqueue the round's coalesced sector read; return (token, nbrs).

        The neighbor lists come from the adjacency sidecar immediately —
        the caller can expand the frontier and dispatch the next beam
        while this round's record read is still in flight on the pool.
        The ``disk.submit`` span covers the whole body."""
        with obs.trace.span("disk.submit", store=self._obs_label):
            return self._submit_body(ids)

    def _submit_body(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        ids = np.asarray(ids)
        valid = ids >= 0
        flat = np.clip(ids, 0, self.n - 1).reshape(-1)
        nbrs = np.full(ids.shape + (self.degree,), -1, np.int32)
        vmask = valid.reshape(-1)
        if vmask.any():
            adj = self._adjacency_host()
            nbrs.reshape(-1, self.degree)[vmask] = adj[flat[vmask]]
        job_ids = np.array(ids, copy=True)  # the callback buffer is reused
        with self._lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.reader_threads,
                    thread_name_prefix="gateann-reader",
                )
            token = self._next_token
            self._next_token = (self._next_token + 1) % (1 << 30)
            self._pending[token] = self._pool.submit(self._host_fetch, job_ids)
            self._inflight += 1
            inflight = self._inflight
            self.inflight_depth_max = max(self.inflight_depth_max, self._inflight)
            overlapped = self._inflight >= 2
            if overlapped:
                self.overlapped_rounds += 1
        if self._obs.enabled:
            self._obs_counters["submits"].inc()
            if overlapped:
                self._obs_counters["overlapped_rounds"].inc()
            self._obs_inflight.set(inflight)
        return np.int32(token), nbrs

    def _host_drain(self, token: np.ndarray, ids: np.ndarray, flag: np.ndarray):
        """Retire one submitted round: block until its read completed and
        return the record vectors.  ``flag=False`` is the pipeline-warmup
        no-op (the loop issues a fixed drain per round; early rounds have
        nothing to retire) — it returns zeros without touching the queue.
        A live drain runs under the ``disk.drain`` span, the wait for the
        read under ``disk.drain_wait`` inside it."""
        if not bool(flag):
            return np.zeros(np.asarray(ids).shape + (self.dim,), np.float32)
        with obs.trace.span("disk.drain", store=self._obs_label):
            return self._drain_body(token)

    def _drain_body(self, token: np.ndarray) -> np.ndarray:
        with self._lock:
            fut = self._pending.pop(int(token), None)
            if fut is not None:
                self._inflight -= 1
                inflight = self._inflight
        if fut is None:
            raise KeyError(
                f"drain of unknown token {int(token)} — not submitted, "
                "already drained, or the store was closed"
            )
        with obs.trace.span("disk.drain_wait", store=self._obs_label):
            got_vecs, _got_nbrs = fut.result()
        if self._obs.enabled:
            self._obs_counters["drains"].inc()
            self._obs_inflight.set(inflight)
        return got_vecs

    def _traced_submit(self, ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
        out_shapes = (
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct(ids.shape + (self.degree,), jnp.int32),
        )
        # ordered like the synchronous fetch: submissions and drains must
        # interleave in program order so FIFO retirement (and counter
        # reconciliation) is deterministic
        return io_callback(self._host_submit, out_shapes, ids, ordered=True)

    def _traced_drain(
        self, token: jax.Array, ids: jax.Array, flag: jax.Array
    ) -> jax.Array:
        out_shape = jax.ShapeDtypeStruct(ids.shape + (self.dim,), jnp.float32)
        return io_callback(self._host_drain, out_shape, token, ids, flag,
                           ordered=True)

    def submit_fn(self):
        return self._submit

    def drain_fn(self):
        return self._drain

    # -- background page-cache re-warm -------------------------------------
    def warm(self, *, background: bool = True, chunk_bytes: int = 4 << 20):
        """Sequentially re-read the segment files to re-populate the OS
        page cache (the post-``load`` warm-up of a freshly booted server).

        ``background=True`` runs on a daemon thread and returns it;
        ``close()`` signals the thread to stop but never joins it (the
        warmer reads through its own fds, so the store's fds close
        immediately).  Bytes actually read land in ``warmed_bytes``.

        Re-entrant calls serialize: a still-running warmer is stopped
        and joined first, so two overlapping warms never double-count
        ``warmed_bytes`` (and ``warm_wait`` always tracks the live one)."""
        prev = self._warm_thread
        if prev is not None and prev.is_alive():
            self._warm_stop.set()
            prev.join()
        self._warm_stop.clear()
        if not background:
            self._warm_run(chunk_bytes)
            return None
        t = threading.Thread(
            target=self._warm_run, args=(chunk_bytes,),
            name="gateann-warm", daemon=True,
        )
        self._warm_thread = t
        t.start()
        return t

    def _warm_run(self, chunk_bytes: int) -> None:
        for seg in self._segments:
            if self._warm_stop.is_set():
                return
            try:
                fd = os.open(seg.path, os.O_RDONLY)
            except OSError:
                # re-saved/swept segment — nothing to warm, but a vanished
                # file is still evidence (a sweep race, a bad mount):
                # count it instead of discarding it
                with self._lock:
                    self.warm_errors += 1
                if self._obs.enabled:
                    self._obs_counters["warm_errors"].inc()
                continue
            try:
                size = os.fstat(fd).st_size
                off = 0
                while off < size and not self._warm_stop.is_set():
                    data = os.pread(fd, min(chunk_bytes, size - off), off)
                    if not data:
                        break
                    off += len(data)
                    with self._lock:
                        self.warmed_bytes += len(data)
                    if self._obs.enabled:
                        self._obs_counters["warmed_bytes"].inc(len(data))
            finally:
                os.close(fd)

    def warm_wait(self, timeout: float | None = None) -> bool:
        """Join the background warmer (tests/benchmarks); True if done."""
        t = self._warm_thread
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    def drop_page_cache(self) -> None:
        """Advise the kernel to evict this index's pages (cold-cache
        benchmarking — ``posix_fadvise(DONTNEED)``; no-op if unsupported)."""
        if not hasattr(os, "posix_fadvise"):
            return
        paths = {self.path} | {seg.path for seg in self._segments}
        for p in paths:
            try:
                fd = os.open(p, os.O_RDONLY)
            except OSError:
                # a cold-cache benchmark that silently fails to drop the
                # cache reports warm numbers as cold — count the miss
                with self._lock:
                    self.warm_errors += 1
                if self._obs.enabled:
                    self._obs_counters["warm_errors"].inc()
                continue
            try:
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)

    # -- measured-I/O reporting --------------------------------------------
    def _reset_counters_locked(self) -> None:
        # logical: what the search loop requested (reconciles with n_ios)
        self.records_read = 0
        self.pages_read = 0
        self.bytes_read = 0
        # physical: what the coalesced reader actually did
        self.unique_sectors_read = 0
        self.ranges_read = 0
        self.syscalls = 0
        self.gap_sectors_read = 0
        self.split_gaps = 0  # holes the gap bound declined to bridge
        self.fetch_rounds = 0
        self.read_rounds = 0
        # pipeline overlap (advanced by submit; _inflight itself is live
        # state, not a counter, and survives resets)
        self.inflight_depth_max = 0
        self.overlapped_rounds = 0
        # submitted rounds retired by abandon_pending instead of a drain —
        # zero on every happy path (the pipeline drains what it submits)
        self.abandoned_tokens = 0
        # background warmer
        self.warmed_bytes = 0
        # resilience: transient-error retries, exhaustions after bounded
        # retry, per-round deadline trips, and record slots served
        # degraded (tunnel sentinel) instead of failing the query
        self.retried_ios = 0
        self.retry_exhausted = 0
        self.deadline_trips = 0
        self.degraded_records = 0
        # warm/drop-page-cache paths that hit an OSError (previously a
        # silent swallow — see the silent-except gatelint rule)
        self.warm_errors = 0

    def io_counters(self) -> dict:
        with self._lock:
            return {
                "records_read": self.records_read,
                "pages_read": self.pages_read,
                "bytes_read": self.bytes_read,
                "unique_sectors_read": self.unique_sectors_read,
                "ranges_read": self.ranges_read,
                "syscalls": self.syscalls,
                "gap_sectors_read": self.gap_sectors_read,
                "split_gaps": self.split_gaps,
                "fetch_rounds": self.fetch_rounds,
                "read_rounds": self.read_rounds,
                "inflight_depth_max": self.inflight_depth_max,
                "overlapped_rounds": self.overlapped_rounds,
                "abandoned_tokens": self.abandoned_tokens,
                "warmed_bytes": self.warmed_bytes,
                "retried_ios": self.retried_ios,
                "retry_exhausted": self.retry_exhausted,
                "deadline_trips": self.deadline_trips,
                "degraded_records": self.degraded_records,
                "warm_errors": self.warm_errors,
            }

    def configure_resilience(
        self,
        *,
        retry: RetryPolicy | None = None,
        on_error: str | None = None,
        round_deadline_s: float | None = None,
    ) -> None:
        """Retune the resilience policy at runtime (the serve layer's
        ``FaultPolicy`` knob and per-batch deadline budgets map here).
        Takes effect on the next read round; safe to call between
        batches while reads are quiescent."""
        if on_error is not None and on_error not in ON_ERROR_POLICIES:
            raise ValueError(f"on_error={on_error!r} not in {ON_ERROR_POLICIES}")
        with self._lock:
            if retry is not None:
                self.retry_policy = retry
            if on_error is not None:
                self.on_error = on_error
            if round_deadline_s is not None:
                self.round_deadline_s = float(round_deadline_s)

    def fault_counters(self) -> dict:
        """The fault injector's tally ({} when no FaultPlan is attached)."""
        return self._injector.counters() if self._injector is not None else {}

    def reset_io_counters(self) -> None:
        """Zero the store-local counters.  The mirrored ``disk.*``
        registry families are NOT reset — registry counters stay
        monotonic so telemetry contracts hold across benchmark resets."""
        with self._lock:
            self._reset_counters_locked()

    def index_bytes(self) -> int:
        """Total on-disk footprint: main file plus any record segments."""
        total = int(os.path.getsize(self.path))
        if self.header.shards:
            total += sum(int(os.path.getsize(s.path)) for s in self._segments)
        return total

    def record_bytes(self) -> int:
        """Slow-tier record-section bytes (same pricing as the other tiers)."""
        return self.n * self.sector_bytes

    @property
    def n_shards(self) -> int:
        return len(self._segments) if self.header.shards else 1

    # -- host-side passthroughs (cache wiring, tests, ground truth) --------
    @property
    def neighbors(self) -> jax.Array:
        if self._neighbors is None:
            self._neighbors = jnp.asarray(
                IndexFile(self.header).neighbors(), jnp.int32
            )
        return self._neighbors

    @property
    def vectors(self) -> np.ndarray:
        """Full-precision vectors as a LAZY host view of the record file.

        No device transfer and (for the single-segment case) no copy —
        the paper-scale corpus must stay on disk until an explicit
        ground-truth/debug path asks (``device_vectors``); at 1B x
        128-dim the old eager materialization was the disk tier's undoing.
        """
        if self._vectors_view is None:
            if len(self._segments) == 1:
                self._vectors_view = self._segments[0].records()["vec"]
            else:  # lazy across segments too — gathers only touched rows
                self._vectors_view = LazySegmentVectors(self._segments, self.dim)
        return self._vectors_view

    def device_vectors(self) -> jax.Array:
        """EXPLICIT full-corpus device materialization (ground truth/debug)."""
        return jnp.asarray(np.ascontiguousarray(self.vectors), jnp.float32)
