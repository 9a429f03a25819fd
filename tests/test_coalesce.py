"""The coalesced disk reader: parity, counters, concurrency.

Contract under test (store/disk.py):

  * All three io_modes — ``preadv`` (one vectored syscall per round,
    gap-bridged), ``pread`` (one syscall per merged range), ``gather``
    (the legacy per-record memmap fancy-gather, kept as the oracle) —
    return byte-identical records for any beam, duplicates and -1 pads
    included, so search output is bit-identical across them.  (The
    default disk engine is already pinned against the in-memory engine
    across all five modes in test_persist; here the gather oracle pins
    the other read paths at the fetch level, where parity is
    mode-independent, plus full-search spot checks.)
  * Logical counters (``records_read``/``pages_read``/``bytes_read``)
    count what the loop requested; physical counters
    (``unique_sectors_read``/``ranges_read``/``syscalls``/
    ``gap_sectors_read``) count what the reader did.
    ``unique_sectors_read <= records_read`` with equality iff the round
    had no duplicates; preadv spends ``syscalls == read_rounds +
    split_gaps`` (per segment; ``split_gaps`` counts the holes wider than
    ``max_gap_sectors``, left unbridged), pread ``syscalls ==
    ranges_read``, gather 0.
  * The gap bound defaults to ``MAX_BRIDGE_BYTES`` over the sector size,
    and ``GateANNEngine.load`` takes it from its caller alone, never from
    the config stored in the index.
  * Counters are guarded by a lock — concurrent fetches through one
    shared store must not lose updates, and reset is atomic.
"""
import shutil
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GateANNEngine, SearchConfig
from repro.store import DiskRecordStore, is_lazy_host, merge_ranges
from repro.store.disk import MAX_BRIDGE_BYTES
from repro.store.format import read_header

RECORD = 4096  # tiny-corpus records round up to one 4 KB sector
DERIVED = MAX_BRIDGE_BYTES // RECORD  # the default gap bound, in sectors
STORES = {  # case -> DiskRecordStore.open keywords
    "preadv": dict(io_mode="preadv", max_gap_sectors=-1),  # unbounded
    "preadv-derived": dict(io_mode="preadv"),  # the default bound
    "pread": dict(io_mode="pread"),
    "gather": dict(io_mode="gather"),
}


@pytest.fixture(scope="module")
def index_path(tiny_engine, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("coalesce") / "tiny.gann")
    tiny_engine.save(path)
    return path


@pytest.fixture(scope="module")
def stores(index_path):
    return {k: DiskRecordStore.open(index_path, **kw) for k, kw in STORES.items()}


def _beam(n, rng, b=7, w=9):
    """A duplicate-heavy beam: repeats within rows, across rows, -1 pads,
    an all-invalid row, and both boundary ids."""
    ids = rng.integers(-1, n, size=(b, w)).astype(np.int32)
    ids[:, 1] = ids[:, 0]  # intra-row duplicate
    ids[1] = ids[0]  # whole-row duplicate (cross-query, same round)
    ids[2] = -1  # a query with nothing dispatched
    ids[3, :3] = (0, n - 1, 0)  # boundary sectors, duplicated again
    return ids


def _holes(ids) -> np.ndarray:
    """Sizes, in sectors, of the holes between a beam's merged ranges."""
    r = merge_ranges(np.unique(ids[ids >= 0]))
    return r[1:, 0] - (r[:-1, 0] + r[:-1, 1])


def test_merge_ranges_unit():
    got = merge_ranges(np.asarray([0, 1, 2, 5, 7, 8, 9]))
    np.testing.assert_array_equal(got, [[0, 3], [5, 1], [7, 3]])
    assert merge_ranges(np.asarray([], np.int64)).shape == (0, 2)
    np.testing.assert_array_equal(merge_ranges(np.asarray([4])), [[4, 1]])


@pytest.mark.parametrize("io_mode", tuple(STORES))
def test_duplicate_heavy_fetch_parity_and_counters(stores, tiny_engine, io_mode):
    store = stores[io_mode]
    ref_fetch = tiny_engine.record_store.fetch_fn()
    rng = np.random.default_rng(7)
    for trial in range(4):
        ids = _beam(store.n, rng)
        before = store.io_counters()
        vecs, nbrs = store._host_fetch(ids)
        after = store.io_counters()
        want_v, want_n = ref_fetch(jnp.asarray(ids))
        np.testing.assert_array_equal(vecs, np.asarray(want_v), err_msg=io_mode)
        np.testing.assert_array_equal(nbrs, np.asarray(want_n), err_msg=io_mode)
        d = {k: after[k] - before[k] for k in after}
        m = int((ids >= 0).sum())
        u = int(np.unique(ids[ids >= 0]).size)
        assert d["records_read"] == m
        assert d["pages_read"] == m * store.pages_per_record
        assert d["bytes_read"] == m * store.sector_bytes
        assert d["unique_sectors_read"] == u < m  # the beam is dup-heavy
        assert d["fetch_rounds"] == 1 and d["read_rounds"] == 1
        if store.io_mode == "preadv":
            # every hole up to the bound is read through, every wider one
            # starts another vectored call
            holes = _holes(ids)
            bound = store.max_gap_sectors if store.max_gap_sectors >= 0 else np.inf
            assert d["gap_sectors_read"] == int(holes[holes <= bound].sum())
            assert d["split_gaps"] == int((holes > bound).sum())
            assert d["syscalls"] == d["read_rounds"] + d["split_gaps"]
            if io_mode == "preadv":  # unbounded
                assert d["syscalls"] == 1  # ONE vectored read for the round
        elif store.io_mode == "pread":
            assert d["syscalls"] == d["ranges_read"]
        else:
            assert d["syscalls"] == 0 and d["gap_sectors_read"] == 0


def test_unique_equals_requested_without_duplicates(stores):
    store = stores["preadv"]
    ids = np.asarray([[3, 9, 27, 81, -1]], np.int32)  # no dups
    before = store.io_counters()
    store._host_fetch(ids)
    d = {k: v - before[k] for k, v in store.io_counters().items()}
    assert d["unique_sectors_read"] == d["records_read"] == 4


def test_all_invalid_beam_reads_nothing(stores):
    for io_mode, store in stores.items():
        before = store.io_counters()
        vecs, nbrs = store._host_fetch(np.full((3, 4), -1, np.int32))
        d = {k: v - before[k] for k, v in store.io_counters().items()}
        assert (vecs == 0).all() and (nbrs == -1).all()
        assert d["records_read"] == d["syscalls"] == d["unique_sectors_read"] == 0
        assert d["fetch_rounds"] == 1 and d["read_rounds"] == 0, io_mode


@pytest.mark.parametrize("io_mode", ("pread", "gather", "preadv"))
def test_search_bit_identical_across_io_modes(index_path, tiny_corpus, io_mode):
    """Full loop: the non-default read paths (and preadv unbounded) return
    the same search output as the default disk engine, uncached and
    cached."""
    import dataclasses

    _, _, queries = tiny_corpus
    base = GateANNEngine.load(index_path, store_tier="disk")
    alt = dataclasses.replace(
        base, record_store=DiskRecordStore.open(index_path, **STORES[io_mode])
    )
    cfg = SearchConfig(mode="gate", search_l=48, beam_width=4)
    tgt = np.zeros(queries.shape[0], np.int32)
    out_b = base.search(queries, filter_kind="label", filter_params=tgt,
                        search_config=cfg)
    out_a = alt.search(queries, filter_kind="label", filter_params=tgt,
                       search_config=cfg)
    np.testing.assert_array_equal(np.asarray(out_a.ids), np.asarray(out_b.ids))
    np.testing.assert_array_equal(np.asarray(out_a.dists), np.asarray(out_b.dists))
    for f in out_b.stats._fields:
        np.testing.assert_array_equal(np.asarray(getattr(out_a.stats, f)),
                                      np.asarray(getattr(out_b.stats, f)))
    # and with a cache tier in front: the file only sees the misses
    cached = alt.with_cache(48 * RECORD)
    out_c = cached.search(queries, filter_kind="label", filter_params=tgt,
                          search_config=cfg)
    np.testing.assert_array_equal(np.asarray(out_c.ids), np.asarray(out_b.ids))
    np.testing.assert_array_equal(
        np.asarray(out_c.stats.n_ios) + np.asarray(out_c.stats.n_cache_hits),
        np.asarray(out_b.stats.n_ios))


def test_counters_locked_under_concurrency(index_path):
    """Concurrent fetches through one shared store lose no counter
    updates (two engines sharing a store do exactly this)."""
    store = DiskRecordStore.open(index_path)
    rng = np.random.default_rng(11)
    beams = [rng.integers(-1, store.n, size=(4, 6)).astype(np.int32)
             for _ in range(8)]
    n_threads, iters = 8, 12
    errs = []

    def hammer(tid):
        try:
            for i in range(iters):
                store._host_fetch(beams[(tid + i) % len(beams)])
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    per_pass = sum(int((b >= 0).sum()) for b in beams) // len(beams)
    want = sum(int((beams[(t + i) % len(beams)] >= 0).sum())
               for t in range(n_threads) for i in range(iters))
    c = store.io_counters()
    assert c["records_read"] == want, (c["records_read"], want, per_pass)
    assert c["fetch_rounds"] == n_threads * iters
    assert c["bytes_read"] == want * store.sector_bytes
    store.reset_io_counters()
    assert all(v == 0 for v in store.io_counters().values())


def test_max_gap_sectors_bounds_bridging(index_path, stores):
    """The gap-bridging bound trades syscalls for read amplification:
    unbounded = one vectored call, all gaps bridged; 0 = one call per
    merged range, zero over-read; a finite bound bridges only gaps <= it.
    All three return byte-identical records."""
    ref_v, ref_n = stores["gather"]._host_fetch(
        np.asarray([[0, 2, 10, -1]], np.int32))
    # ranges (0,1) (2,1) (10,1): gaps of 1 and 7 sectors
    cases = {
        -1: dict(syscalls=1, gap=8),     # bridge everything, one preadv
        7: dict(syscalls=1, gap=8),      # bound == widest gap: still one
        2: dict(syscalls=2, gap=1),      # bridge the 1-gap, split at the 7
        0: dict(syscalls=3, gap=0),      # never bridge: one call per range
    }
    for bound, want in cases.items():
        store = DiskRecordStore.open(index_path, io_mode="preadv",
                                     max_gap_sectors=bound)
        vecs, nbrs = store._host_fetch(np.asarray([[0, 2, 10, -1]], np.int32))
        c = store.io_counters()
        np.testing.assert_array_equal(vecs, ref_v, err_msg=str(bound))
        np.testing.assert_array_equal(nbrs, ref_n, err_msg=str(bound))
        assert c["syscalls"] == want["syscalls"], (bound, c)
        assert c["gap_sectors_read"] == want["gap"], (bound, c)
        assert c["ranges_read"] == 3, (bound, c)
        assert c["split_gaps"] == want["syscalls"] - 1, (bound, c)
        store.close()
    # any negative bound is unbounded, resolved to -1
    assert DiskRecordStore.open(index_path, max_gap_sectors=-5).max_gap_sectors == -1


@pytest.mark.parametrize("hole, split", [(DERIVED, 0), (DERIVED + 1, 1)],
                         ids=["at-bound", "one-wider"])
def test_derived_bound_bridges_up_to_max_bridge_bytes(index_path, stores,
                                                      hole, split):
    """The default bound is MAX_BRIDGE_BYTES over the sector size: a hole
    of exactly the bound is read through, one sector wider is split."""
    store = DiskRecordStore.open(index_path, io_mode="preadv")
    assert store.max_gap_sectors == DERIVED == 32
    ids = np.asarray([[0, hole + 1, -1]], np.int32)
    vecs, nbrs = store._host_fetch(ids)
    ref_v, ref_n = stores["gather"]._host_fetch(ids)
    np.testing.assert_array_equal(vecs, ref_v)
    np.testing.assert_array_equal(nbrs, ref_n)
    c = store.io_counters()
    assert c["split_gaps"] == split
    assert c["gap_sectors_read"] == (0 if split else hole)
    assert c["syscalls"] == c["read_rounds"] + c["split_gaps"] == 1 + split
    store.close()


@pytest.mark.parametrize("override, want", [({}, DERIVED),
                                            ({"max_gap_sectors": -1}, -1),
                                            ({"max_gap_sectors": 0}, 0)],
                         ids=["derived", "unbounded", "never"])
def test_load_takes_gap_bound_from_caller_only(tiny_engine, tmp_path,
                                               override, want):
    """An index saved with the old unbounded default (-1 in its stored
    config) loads with the derived bound; an explicit override wins."""
    import dataclasses

    path = str(tmp_path / "old.gann")
    old = dataclasses.replace(
        tiny_engine,
        config=dataclasses.replace(tiny_engine.config, max_gap_sectors=-1))
    old.save(path)
    assert read_header(path).config["max_gap_sectors"] == -1
    eng = GateANNEngine.load(path, store_tier="disk", **override)
    assert eng.config.max_gap_sectors == override.get("max_gap_sectors")
    assert eng.record_store.max_gap_sectors == want
    assert eng.memory_report()["disk_max_gap_sectors"] == want
    eng.record_store.close()


def test_max_gap_search_parity(index_path, tiny_corpus):
    """Full loop at the zero-bridge extreme: identical search output, and
    every bridged gap stays within the bound (here: no gaps at all)."""
    import dataclasses

    _, _, queries = tiny_corpus
    base = GateANNEngine.load(index_path, store_tier="disk")
    tight = dataclasses.replace(
        base,
        record_store=DiskRecordStore.open(index_path, max_gap_sectors=0),
    )
    cfg = SearchConfig(mode="gate", search_l=48, beam_width=4)
    tgt = np.zeros(queries.shape[0], np.int32)
    out_b = base.search(queries, filter_kind="label", filter_params=tgt,
                        search_config=cfg)
    out_t = tight.search(queries, filter_kind="label", filter_params=tgt,
                         search_config=cfg)
    np.testing.assert_array_equal(np.asarray(out_t.ids), np.asarray(out_b.ids))
    c = tight.record_store.io_counters()
    assert c["gap_sectors_read"] == 0
    assert c["syscalls"] == c["ranges_read"]  # one call per merged range
    tight.record_store.close()


def test_warm_repopulates_page_cache_counter(index_path):
    """warm() sequentially re-reads every segment file: warmed_bytes ends
    at the full on-disk footprint (foreground), the background variant
    reaches the same count, and close() mid-warm neither blocks nor
    crashes (the warmer reads through its own fds)."""
    store = DiskRecordStore.open(index_path)
    total = store.index_bytes()
    store.warm(background=False)
    assert store.warmed_bytes == total
    store.reset_io_counters()
    store.warm(background=True, chunk_bytes=1 << 16)
    assert store.warm_wait(timeout=30.0)
    assert store.warmed_bytes == total
    # re-entrant warm: an overlapping call stops+joins the live warmer
    # first, so warmed_bytes never double-counts past one full pass + a
    # fresh one (the first pass is cut short, never duplicated)
    store.reset_io_counters()
    store.warm(background=True, chunk_bytes=1 << 12)
    store.warm(background=True, chunk_bytes=1 << 16)
    assert store.warm_wait(timeout=30.0)
    assert total <= store.warmed_bytes < 2 * total
    # non-blocking close path: closing mid-warm just signals the thread
    store.reset_io_counters()
    store.warm(background=True, chunk_bytes=1 << 12)
    store.close()
    assert store.warm_wait(timeout=30.0)  # stops promptly, no EBADF
    assert store.warmed_bytes <= total
    # engine.load(warm_disk=True) wires it up after a disk-tier load
    eng = GateANNEngine.load(index_path, store_tier="disk", warm_disk=True)
    assert eng.record_store.warm_wait(timeout=30.0)
    assert eng.record_store.warmed_bytes == eng.record_store.index_bytes()
    assert eng.memory_report()["disk_warmed_bytes"] == eng.record_store.warmed_bytes
    eng.record_store.close()


def test_lazy_vectors_view(stores, tiny_engine):
    """The vectors passthrough is a host memmap view — never a device
    array, and equal to the corpus byte-for-byte."""
    store = stores["preadv"]
    v = store.vectors
    assert isinstance(v, np.ndarray) and not isinstance(v, jax.Array)
    assert is_lazy_host(v)
    np.testing.assert_array_equal(np.asarray(v),
                                  np.asarray(tiny_engine.vectors, np.float32))
    # the explicit debug path is the only device transfer
    dv = store.device_vectors()
    assert isinstance(dv, jax.Array)
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(v))
