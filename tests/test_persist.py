"""Persistence: save/load roundtrip, the disk tier, and measured I/O.

The contract under test:

  * ``save`` -> ``load`` returns an engine whose search output (ids,
    dists, every stats counter) is bit-identical to the freshly built
    in-memory engine, in all five modes, for both the memory and the
    disk record tier — load never rebuilds the graph or retrains PQ.
    ``save(shards=k)`` (per-shard record segments + manifest) preserves
    the same contract, and v1 files (monolithic records, no manifest)
    still read.
  * The disk tier *measures* its reads: ``DiskRecordStore.pages_read``
    deltas reconcile exactly with summed ``SearchStats.n_ios`` (x pages
    per record), gate reads strictly fewer pages than post on a
    selective filter, the coalesced reader never reads more unique
    sectors than requested, and the cache tier composes on top
    unchanged.  A disk-tier load keeps ``engine.vectors`` a lazy host
    view — no device materialization of the corpus.
  * The format rejects bad magic, newer versions, truncated files, and
    lying/stale shard manifests or segments.
"""
import os
import shutil

import numpy as np
import pytest

from repro.core import GateANNEngine, SearchConfig
from repro.store import (
    FORMAT_VERSION,
    PAGE_BYTES,
    DiskRecordStore,
    IndexFormatError,
    is_lazy_host,
    read_header,
    read_index,
)
from repro.store.format import pack_records, record_sector_bytes

MODES = ("gate", "post", "early", "pre_naive", "unfiltered")
RECORD = 4096  # tiny-corpus records round up to one 4 KB sector


def _search(engine, queries, mode, L=64, W=4):
    kind = None if mode == "unfiltered" else "label"
    params = None if mode == "unfiltered" else np.zeros(queries.shape[0], np.int32)
    return engine.search(
        queries, filter_kind=kind, filter_params=params,
        search_config=SearchConfig(mode=mode, search_l=L, beam_width=W),
    )


@pytest.fixture(scope="module")
def index_path(tiny_engine, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("index") / "tiny.gann")
    tiny_engine.save(path)
    return path


@pytest.fixture(scope="module")
def mem_engine(index_path):
    return GateANNEngine.load(index_path)


@pytest.fixture(scope="module")
def disk_engine(index_path):
    return GateANNEngine.load(index_path, store_tier="disk")


# -- roundtrip --------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_roundtrip_bit_identical(tiny_engine, tiny_corpus, mem_engine,
                                 disk_engine, mode):
    """Loaded engines (both tiers) match the freshly built one exactly."""
    _, _, queries = tiny_corpus
    base = _search(tiny_engine, queries, mode)
    for name, eng in (("memory", mem_engine), ("disk", disk_engine)):
        out = _search(eng, queries, mode)
        msg = f"tier={name} mode={mode}"
        np.testing.assert_array_equal(np.asarray(out.ids),
                                      np.asarray(base.ids), err_msg=msg)
        np.testing.assert_array_equal(np.asarray(out.dists),
                                      np.asarray(base.dists), err_msg=msg)
        for f in base.stats._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out.stats, f)),
                np.asarray(getattr(base.stats, f)), err_msg=f"{msg} stats.{f}")


def test_load_never_rebuilds(index_path, monkeypatch):
    """load must not touch the graph builder or the PQ trainer."""
    from repro.core import engine as enginem

    def boom(*a, **k):
        raise AssertionError("load rebuilt index state")

    monkeypatch.setattr(enginem.graphm, "build_vamana", boom)
    monkeypatch.setattr(enginem.pqm, "train_pq", boom)
    eng = GateANNEngine.load(index_path)
    assert eng.codes.shape[0] == eng.vectors.shape[0]


def test_loaded_components_match(tiny_engine, mem_engine):
    np.testing.assert_array_equal(np.asarray(mem_engine.vectors),
                                  np.asarray(tiny_engine.vectors))
    np.testing.assert_array_equal(np.asarray(mem_engine.codes),
                                  np.asarray(tiny_engine.codes))
    np.testing.assert_array_equal(np.asarray(mem_engine.codec.books),
                                  np.asarray(tiny_engine.codec.books))
    np.testing.assert_array_equal(
        np.asarray(mem_engine.neighbor_store.neighbors),
        np.asarray(tiny_engine.neighbor_store.neighbors))
    assert int(mem_engine.medoid) == int(tiny_engine.medoid)
    assert set(mem_engine.filters) == set(tiny_engine.filters)
    assert mem_engine.config == tiny_engine.config


def test_load_config_overrides(index_path):
    eng = GateANNEngine.load(index_path, r_max=4)
    assert eng.neighbor_store.r_max == 4
    eng2 = GateANNEngine.load(index_path, {"r_max": 6})
    assert eng2.neighbor_store.r_max == 6
    # misspelled overrides must raise, not silently no-op
    with pytest.raises(ValueError, match="cache_budget"):
        GateANNEngine.load(index_path, cache_budget=1 << 20)


def test_save_over_live_disk_engine(index_path, tmp_path, tiny_corpus):
    """Re-saving onto the file backing a live disk engine must not corrupt
    the mapping mid-search (write-then-rename keeps the old inode)."""
    _, _, queries = tiny_corpus
    path = str(tmp_path / "live.gann")
    shutil.copyfile(index_path, path)
    disk = GateANNEngine.load(path, store_tier="disk")
    base = _search(disk, queries[:4], "gate")
    disk.save(path)  # overwrites the very file the memmap is backed by
    out = _search(disk, queries[:4], "gate")
    np.testing.assert_array_equal(np.asarray(out.ids), np.asarray(base.ids))
    # and a fresh load of the re-saved file agrees too
    out2 = _search(GateANNEngine.load(path, store_tier="disk"), queries[:4], "gate")
    np.testing.assert_array_equal(np.asarray(out2.ids), np.asarray(base.ids))


# -- measured I/O -----------------------------------------------------------

def test_disk_pages_reconcile_and_gate_lt_post(disk_engine, tiny_corpus):
    """Measured sector reads == modeled n_ios; tunneling saves real pages."""
    _, _, queries = tiny_corpus
    store = disk_engine.record_store
    assert isinstance(store, DiskRecordStore)
    pages = {}
    for mode in ("gate", "post"):
        before = store.pages_read
        out = _search(disk_engine, queries, mode)
        ids = np.asarray(out.ids)  # materialize => all callbacks ran
        assert ids.shape[0] == queries.shape[0]
        measured = store.pages_read - before
        modeled = int(np.sum(np.asarray(out.stats.n_ios))) * store.pages_per_record
        assert measured == modeled, mode
        pages[mode] = measured
    assert pages["gate"] < pages["post"]
    assert store.bytes_read == store.pages_read * PAGE_BYTES
    assert store.records_read * store.pages_per_record == store.pages_read


def test_cache_tier_composes_on_disk(disk_engine, tiny_corpus):
    """A cache in front of the disk tier: identical ids, I/O conservation,
    and the file only ever sees the misses (measured)."""
    _, _, queries = tiny_corpus
    store = disk_engine.record_store
    base = _search(disk_engine, queries, "gate")
    base_ids = np.asarray(base.ids)
    base_ios = np.asarray(base.stats.n_ios)
    cached = disk_engine.with_cache(64 * RECORD)
    before = store.pages_read
    out = _search(cached, queries, "gate")
    ids = np.asarray(out.ids)
    measured = store.pages_read - before
    np.testing.assert_array_equal(ids, base_ids)
    ios = np.asarray(out.stats.n_ios)
    hits = np.asarray(out.stats.n_cache_hits)
    np.testing.assert_array_equal(ios + hits, base_ios)
    assert int(hits.sum()) > 0
    assert measured == int(ios.sum()) * store.pages_per_record


def test_adaptive_cache_composes_on_disk(disk_engine, tiny_corpus):
    _, _, queries = tiny_corpus
    base = _search(disk_engine, queries, "gate")
    eng = disk_engine.with_cache(64 * RECORD, policy="adaptive", refresh_every=1)
    for _ in range(2):
        out = _search(eng, queries, "gate")
        np.testing.assert_array_equal(np.asarray(out.ids), np.asarray(base.ids))
        np.testing.assert_array_equal(
            np.asarray(out.stats.n_ios) + np.asarray(out.stats.n_cache_hits),
            np.asarray(base.stats.n_ios))


def test_memory_report_disk_lines(disk_engine, index_path):
    rep = disk_engine.memory_report()
    assert rep["record_tier"] == "disk"
    assert rep["disk_path"] == index_path
    assert rep["disk_index_bytes"] == os.path.getsize(index_path)
    assert rep["record_tier_bytes"] == rep["n"] * rep["disk_sector_bytes"]
    assert rep["disk_pages_read"] >= 0
    assert rep["disk_bytes_read"] == rep["disk_pages_read"] * PAGE_BYTES


# -- sharded record segments ------------------------------------------------

@pytest.fixture(scope="module")
def sharded_path(tiny_engine, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sharded") / "tiny_sharded.gann")
    tiny_engine.save(path, shards=3)
    return path


def test_sharded_save_layout(sharded_path, tiny_engine):
    h = read_header(sharded_path)
    n = int(tiny_engine.vectors.shape[0])
    assert h.shards is not None and h.n_shards == 3
    assert h.shards["rows_per_shard"] == -(-n // 3)
    assert "records" not in h.sections  # records live in the segments
    covered = 0
    for i, seg in enumerate(h.shards["segments"]):
        assert os.path.exists(h.segment_path(i))
        assert seg["row_start"] == covered
        covered += seg["n_rows"]
    assert covered == n
    assert f"3 shards" in h.describe()
    # the monolithic accessor must fail loudly, not serve garbage
    with pytest.raises(IndexFormatError, match="sharded"):
        read_index(sharded_path).records()


@pytest.mark.parametrize("tier", ["memory", "disk"])
def test_sharded_roundtrip_bit_identical(sharded_path, tiny_engine,
                                         tiny_corpus, tier):
    _, _, queries = tiny_corpus
    eng = GateANNEngine.load(
        sharded_path, **({"store_tier": "disk"} if tier == "disk" else {})
    )
    for mode in ("gate", "post"):
        base = _search(tiny_engine, queries, mode)
        out = _search(eng, queries, mode)
        np.testing.assert_array_equal(np.asarray(out.ids), np.asarray(base.ids),
                                      err_msg=f"{tier} {mode}")
        np.testing.assert_array_equal(np.asarray(out.dists),
                                      np.asarray(base.dists))


def test_sharded_disk_counters(sharded_path, tiny_corpus):
    """Coalescing works per segment: unique <= requested still holds and
    preadv spends one vectored call per touched segment per round, plus
    one per hole the gap bound left unbridged."""
    _, _, queries = tiny_corpus
    eng = GateANNEngine.load(sharded_path, store_tier="disk")
    store = eng.record_store
    assert store.n_shards == 3
    touched = []  # segments each read round touches
    read_unique = store._read_unique

    def counting_read(uniq, io):
        seg = np.searchsorted(store._row_starts, uniq, side="right") - 1
        touched.append(np.unique(seg).size)
        return read_unique(uniq, io)

    store._read_unique = counting_read
    out = _search(eng, queries, "gate")
    np.asarray(out.ids)  # materialize => all callbacks ran
    c = store.io_counters()
    assert c["records_read"] == int(np.sum(np.asarray(out.stats.n_ios)))
    assert 0 < c["unique_sectors_read"] <= c["records_read"]
    assert len(touched) == c["read_rounds"]
    assert c["read_rounds"] <= sum(touched) <= c["read_rounds"] * 3
    if store.io_mode == "preadv":
        assert c["syscalls"] == sum(touched) + c["split_gaps"]
    # footprint spans the main file plus every segment
    assert store.index_bytes() > os.path.getsize(sharded_path)


def test_shard_loader_parity(sharded_path, index_path, tiny_engine):
    """core.distributed_search loaders == ShardedRecordStore.shard_arrays
    over the live arrays — segment files feed the mesh byte-identically."""
    from repro.core.distributed_search import (
        load_shard_records,
        load_sharded_record_arrays,
    )
    from repro.store import ShardedRecordStore

    vecs = np.asarray(tiny_engine.vectors, np.float32)
    nbrs = np.asarray(tiny_engine.record_store.neighbors, np.int32)
    want_v, want_n, want_rows = ShardedRecordStore.shard_arrays(vecs, nbrs, 3)
    got_v, got_n, rows = load_sharded_record_arrays(sharded_path)
    assert rows == want_rows
    np.testing.assert_array_equal(got_v, want_v.astype(np.float32))
    np.testing.assert_array_equal(got_n, want_n.astype(np.int32))
    # one shard alone, off the sharded index and off the monolithic one
    for path, kw in ((sharded_path, {}), (index_path, {"n_shards": 3})):
        v1, n1, r1 = load_shard_records(path, 1, **kw)
        assert r1 == want_rows
        np.testing.assert_array_equal(v1, want_v[want_rows : 2 * want_rows])
        np.testing.assert_array_equal(n1, want_n[want_rows : 2 * want_rows])
    with pytest.raises(ValueError, match="out of range"):
        load_shard_records(sharded_path, 5)
    with pytest.raises(ValueError, match="n_shards"):
        load_shard_records(index_path, 0)


def test_sharded_segment_corruption_rejected(sharded_path, tmp_path):
    seg_names = [s["name"] for s in read_header(sharded_path).shards["segments"]]
    names = [os.path.basename(sharded_path)] + seg_names
    src_dir = os.path.dirname(sharded_path)

    def fresh(into):
        d = tmp_path / into
        d.mkdir()
        for nm in names:
            shutil.copyfile(os.path.join(src_dir, nm), str(d / nm))
        return str(d), str(d / names[0])

    # a missing segment file must fail the disk load loudly
    dd, p = fresh("missing")
    os.remove(os.path.join(dd, seg_names[1]))
    with pytest.raises(IndexFormatError, match="seg1"):
        GateANNEngine.load(p, store_tier="disk")
    # a truncated segment is caught before it serves short sectors
    dd, p = fresh("trunc")
    seg2 = os.path.join(dd, seg_names[2])
    os.truncate(seg2, os.path.getsize(seg2) // 2)
    with pytest.raises(IndexFormatError, match="truncated segment"):
        GateANNEngine.load(p, store_tier="disk")
    # a swapped/stale segment (header disagrees with the manifest slot)
    dd, p = fresh("swapped")
    shutil.copyfile(os.path.join(dd, seg_names[0]), os.path.join(dd, seg_names[1]))
    with pytest.raises(IndexFormatError, match="wrong/stale segment"):
        GateANNEngine.load(p, store_tier="disk")


def test_sharded_save_over_live_engine(sharded_path, tiny_corpus, tmp_path):
    """Re-saving a sharded index over itself must never touch the
    committed generation's segment files: the live engine keeps serving
    off its old inodes, a fresh load serves the new generation, and the
    superseded segments are swept after the commit."""
    _, _, queries = tiny_corpus
    d = tmp_path / "live_sharded"
    d.mkdir()
    names = [os.path.basename(sharded_path)] + [
        s["name"] for s in read_header(sharded_path).shards["segments"]
    ]
    for nm in names:
        shutil.copyfile(os.path.join(os.path.dirname(sharded_path), nm),
                        str(d / nm))
    path = str(d / names[0])
    live = GateANNEngine.load(path, store_tier="disk")
    base = _search(live, queries[:4], "gate")
    old_segs = set(names[1:])
    live.save(path, shards=2)  # different shard count, same index path
    # the live engine's generation was never overwritten
    out = _search(live, queries[:4], "gate")
    np.testing.assert_array_equal(np.asarray(out.ids), np.asarray(base.ids))
    # a fresh load serves the new 2-shard generation, bit-identically
    fresh = GateANNEngine.load(path, store_tier="disk")
    assert fresh.record_store.n_shards == 2
    out2 = _search(fresh, queries[:4], "gate")
    np.testing.assert_array_equal(np.asarray(out2.ids), np.asarray(base.ids))
    # stale segments were swept once the new manifest committed
    new_segs = {s["name"] for s in read_header(path).shards["segments"]}
    on_disk = {f for f in os.listdir(str(d)) if ".seg" in f}
    assert on_disk == new_segs
    assert not (old_segs & on_disk)


def test_lazy_vectors_on_disk_load(disk_engine, mem_engine):
    """A disk-tier load must NOT materialize the corpus on device: the
    engine's vectors stay a lazy host view, cache wiring gathers only hot
    rows, and only the explicit debug path transfers."""
    import jax

    v = disk_engine.vectors
    assert isinstance(v, np.ndarray) and not isinstance(v, jax.Array)
    assert is_lazy_host(v)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(mem_engine.vectors))
    cached = disk_engine.with_cache(32 * RECORD)
    assert is_lazy_host(cached.vectors)  # still lazy behind the cache
    assert isinstance(cached.record_store.cache_vectors, jax.Array)
    assert int(cached.record_store.cache_vectors.shape[0]) <= 32
    adaptive = disk_engine.with_cache(32 * RECORD, policy="adaptive")
    assert is_lazy_host(adaptive.record_store.vectors)
    dv = disk_engine.record_store.device_vectors()
    assert isinstance(dv, jax.Array)
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(v))


def test_lazy_vectors_on_sharded_disk_load(sharded_path, mem_engine):
    """The lazy-vectors guarantee must survive sharding: the multi-segment
    view stays host-side, row gathers touch only the asked rows, and the
    cache tier still ships only the hot set to device."""
    import jax

    eng = GateANNEngine.load(sharded_path, store_tier="disk")
    v = eng.vectors
    assert not isinstance(v, (jax.Array, np.memmap))
    assert is_lazy_host(v)
    ref = np.asarray(mem_engine.vectors)
    assert v.shape == ref.shape and len(v) == ref.shape[0]
    # row gathers cross segment boundaries correctly (rows_per_shard
    # boundaries for n=2000 over 3 shards fall at 667 and 1334)
    picks = np.asarray([0, 1, 666, 667, 1333, 1334, 1999, 5])
    np.testing.assert_array_equal(v[picks], ref[picks])
    np.testing.assert_array_equal(v[3], ref[3])
    np.testing.assert_array_equal(v[10:20], ref[10:20])
    np.testing.assert_array_equal(np.asarray(v), ref)
    cached = eng.with_cache(32 * RECORD)
    assert is_lazy_host(cached.vectors)
    assert isinstance(cached.record_store.cache_vectors, jax.Array)
    assert int(cached.record_store.cache_vectors.shape[0]) <= 32


# -- the format itself ------------------------------------------------------

def test_header_layout(index_path, tiny_engine):
    h = read_header(index_path)
    n, d = tiny_engine.vectors.shape
    assert h.version == FORMAT_VERSION
    assert (h.n, h.dim) == (n, d)
    assert h.medoid == int(tiny_engine.medoid)
    assert h.sector_bytes == record_sector_bytes(h.dim, h.degree)
    assert h.config["r_max"] == tiny_engine.config.r_max
    for name, s in h.sections.items():
        assert s["offset"] % PAGE_BYTES == 0, name
        assert s["offset"] + s["nbytes"] <= h.file_bytes, name
    for expect in ("records", "neighbors", "pq_books", "pq_codes",
                   "filter_label", "filter_range"):
        assert expect in h.sections
    assert "tiny.gann" in h.describe()


def test_record_sectors_page_aligned(tiny_engine):
    vecs = np.asarray(tiny_engine.vectors[:5])
    nbrs = np.asarray(tiny_engine.record_store.neighbors[:5])
    rec = pack_records(vecs, nbrs)
    assert rec.dtype.itemsize % PAGE_BYTES == 0
    np.testing.assert_array_equal(rec["vec"], vecs.astype("<f4"))
    np.testing.assert_array_equal(rec["nbrs"], nbrs.astype("<i4"))
    np.testing.assert_array_equal(rec["deg"], (nbrs >= 0).sum(1))


def test_disk_fetch_matches_memory(disk_engine, tiny_engine):
    """The host callback returns the same bytes as the in-memory store."""
    import jax.numpy as jnp

    ids = jnp.asarray([[0, 1, 7, -1, 1999]], jnp.int32)
    vecs_d, nbrs_d = disk_engine.record_store.fetch_fn()(ids)
    vecs_m, nbrs_m = tiny_engine.record_store.fetch_fn()(ids)
    np.testing.assert_array_equal(np.asarray(vecs_d), np.asarray(vecs_m))
    np.testing.assert_array_equal(np.asarray(nbrs_d), np.asarray(nbrs_m))


def test_bad_magic_rejected(index_path, tmp_path):
    bad = str(tmp_path / "bad_magic.gann")
    shutil.copyfile(index_path, bad)
    with open(bad, "r+b") as f:
        f.write(b"NOPE")
    with pytest.raises(IndexFormatError, match="magic"):
        read_header(bad)
    with pytest.raises(IndexFormatError):
        GateANNEngine.load(bad)


def test_v1_file_still_reads(index_path, tmp_path, tiny_corpus, tiny_engine):
    """Back-compat: a v1 file (monolithic records, no shard manifest) must
    load and search bit-identically under the v2 reader.  An unsharded v2
    layout is byte-compatible with v1, so pinning the version field back
    to 1 reconstructs a genuine v1 file."""
    _, _, queries = tiny_corpus
    v1 = str(tmp_path / "v1.gann")
    shutil.copyfile(index_path, v1)
    with open(v1, "r+b") as f:
        f.seek(4)
        f.write(np.uint32(1).tobytes())
    h = read_header(v1)
    assert h.version == 1 and h.shards is None
    base = _search(tiny_engine, queries, "gate")
    for kw in ({}, {"store_tier": "disk"}):
        out = _search(GateANNEngine.load(v1, **kw), queries, "gate")
        np.testing.assert_array_equal(np.asarray(out.ids), np.asarray(base.ids))


def test_newer_version_rejected(index_path, tmp_path):
    bad = str(tmp_path / "vnext.gann")
    shutil.copyfile(index_path, bad)
    with open(bad, "r+b") as f:
        f.seek(4)
        f.write(np.uint32(FORMAT_VERSION + 1).tobytes())
    with pytest.raises(IndexFormatError, match="version"):
        GateANNEngine.load(bad)


def test_truncated_file_rejected(index_path, tmp_path):
    bad = str(tmp_path / "trunc.gann")
    shutil.copyfile(index_path, bad)
    h = read_header(index_path)
    os.truncate(bad, h.file_bytes // 2)
    with pytest.raises(IndexFormatError, match="truncat"):
        read_header(bad)
    with pytest.raises(IndexFormatError):
        GateANNEngine.load(bad, store_tier="disk")


def _write_raw_header(path, meta, pad_bytes=0):
    """A syntactically valid header with arbitrary (possibly bogus) meta."""
    import json

    from repro.store.format import HEADER_PAGES, _PRELUDE, FORMAT_MAGIC

    blob = json.dumps(meta).encode()
    prelude = np.zeros((), dtype=_PRELUDE)
    prelude["magic"] = FORMAT_MAGIC
    prelude["version"] = FORMAT_VERSION
    prelude["json_len"] = len(blob)
    with open(path, "wb") as f:
        f.write(prelude.tobytes())
        f.write(blob)
        f.write(b"\0" * (HEADER_PAGES * PAGE_BYTES - _PRELUDE.itemsize - len(blob)))
        f.write(b"\0" * pad_bytes)


@pytest.mark.parametrize("meta", [
    {},  # everything missing
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {"records": {"offset": 16384}}},  # section missing nbytes
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 0, "medoid": 0,
     "sections": {}},  # zero sector size (would div-by-zero downstream)
    {"n": 4, "dim": -1, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {}},  # nonsensical geometry
    {"n": "lots", "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {}},  # ill-typed field
    {"n": 100000, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {"records": {"offset": 16384, "nbytes": 4096,
                              "dtype": "record", "shape": [1]}}},
    # ^ lying records shape: nbytes fits the file but not n x sector
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {"pq_codes": {"offset": 16384, "nbytes": 99,
                               "dtype": "<i4", "shape": [4, 8]}}},
    # ^ dtype x shape inconsistent with nbytes (would mmap wrong bytes)
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {"neighbors": {"offset": 16384, "nbytes": -5000,
                                "dtype": "<i4", "shape": [4, 2]}}},
    # ^ negative section size
    {"n": 4, "dim": 2000, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {}},
    # ^ sector_bytes inconsistent with dim/degree (record dtype would
    #   read past the section at the wrong pages_per_record)
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 10 ** 9,
     "sections": {}},  # medoid out of [0, n)
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {"pq_codes": {"offset": 0, "nbytes": 0,
                               "dtype": "<i4", "shape": [0, 0]}}},
    # ^ section claiming the header pages as data
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {"pq_codes": {"offset": 16384, "nbytes": 4096,
                               "dtype": "<u1", "shape": [4096]},
                  "neighbors": {"offset": 16384, "nbytes": 4096,
                                "dtype": "<u1", "shape": [4096]}}},
    # ^ overlapping sections
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {},
     "shards": {"n_shards": 2, "rows_per_shard": 2, "segments": [
         {"name": "../evil.seg0", "row_start": 0, "n_rows": 2, "nbytes": 8192},
         {"name": "x.seg1", "row_start": 2, "n_rows": 2, "nbytes": 8192}]}},
    # ^ segment name escaping the index directory
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {},
     "shards": {"n_shards": 2, "rows_per_shard": 2, "segments": [
         {"name": "x.seg0", "row_start": 0, "n_rows": 3, "nbytes": 12288},
         {"name": "x.seg1", "row_start": 3, "n_rows": 1, "nbytes": 4096}]}},
    # ^ segment rows disagree with rows_per_shard
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {},
     "shards": {"n_shards": 2, "rows_per_shard": 2, "segments": [
         {"name": "x.seg0", "row_start": 0, "n_rows": 2, "nbytes": 999},
         {"name": "x.seg1", "row_start": 2, "n_rows": 2, "nbytes": 8192}]}},
    # ^ segment nbytes inconsistent with rows x sector
    {"n": 4, "dim": 2, "degree": 2, "sector_bytes": 4096, "medoid": 0,
     "sections": {"records": {"offset": 16384, "nbytes": 16384,
                              "dtype": "record", "shape": [4]}},
     "shards": {"n_shards": 2, "rows_per_shard": 2, "segments": [
         {"name": "x.seg0", "row_start": 0, "n_rows": 2, "nbytes": 8192},
         {"name": "x.seg1", "row_start": 2, "n_rows": 2, "nbytes": 8192}]}},
    # ^ both a monolithic records section AND a shard manifest
])
def test_corrupt_parseable_header_rejected(tmp_path, meta):
    """JSON that parses but lies must still come out as IndexFormatError."""
    p = str(tmp_path / "corrupt.gann")
    _write_raw_header(p, meta, pad_bytes=8192)
    with pytest.raises(IndexFormatError):
        read_header(p)


def test_not_an_index_rejected(tmp_path):
    p = str(tmp_path / "tiny.gann")
    with open(p, "wb") as f:
        f.write(b"hello world")
    with pytest.raises(IndexFormatError):
        read_header(p)
    with pytest.raises(IndexFormatError):
        read_index(os.path.join(str(tmp_path), "missing.gann"))
