"""Multi-tenant serving front end: admission, batching, tracing, attribution.

Contract under test (serve/server.py + serve/rag.py):

  * Served results are bit-identical to direct ``engine.search`` over the
    tenant's namespace — batching, bucketing, and padding never change
    what a request retrieves (the recall-parity contract nightly also
    enforces end-to-end via serve_bench).
  * Admission is bounded and explicit: ``max_inflight`` covers queued +
    in-service requests, waits time out into ``AdmissionError``, and
    ``close()`` fails undispatched requests with ``ServerClosed`` instead
    of hanging their handles.
  * A mid-batch engine failure fails THAT batch's handles, abandons any
    in-flight pipelined disk round, and leaves the server serving.
  * Accounting stays exact under concurrency: the measured slow-tier
    delta reconciles against served + padding dispatches (drift == 0),
    per-tenant attribution sums to the store totals, and the physical
    counter families (``unique_sectors_read <= records_read``,
    ``syscalls`` vs ``read_rounds``) hold with many clients in flight.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import GateANNEngine, SearchConfig
from repro.serve import (
    AdmissionError,
    RAGServer,
    ServeFrontend,
    ServerClosed,
    TenantSpec,
)

RECORD = 4096


def _rag(engine, *, bucket_sizes=(4,), depth=1):
    return RAGServer(
        engine=engine, cfg=None, params=None, layout=None,
        passage_tokens=np.zeros((int(engine.vectors.shape[0]), 4), np.int32),
        search_config=SearchConfig(mode="gate", search_l=32, beam_width=4,
                                   pipeline_depth=depth),
        bucket_sizes=bucket_sizes,
    )


def _tenants(n=2, max_inflight=32):
    return [TenantSpec(f"t{i}", "label", np.int32(i), max_inflight=max_inflight)
            for i in range(n)]


@pytest.fixture(scope="module")
def serve_index(tiny_engine, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("serve") / "tiny.gann")
    tiny_engine.save(path)
    return path


def test_served_results_match_direct_search(tiny_engine, tiny_corpus):
    _, _, queries = tiny_corpus
    rag = _rag(tiny_engine)
    with ServeFrontend(rag, _tenants(), max_batch=4,
                       batch_window_s=0.005) as srv:
        handles = [(i % 2, i, srv.submit(f"t{i % 2}", queries[i]))
                   for i in range(8)]
        got = {(t, qi): h.result(timeout=120.0) for t, qi, h in handles}
        rep = srv.io_report()
    for tenant in (0, 1):
        qis = [qi for t, qi in got if t == tenant]
        out = tiny_engine.search(
            queries[qis], filter_kind="label",
            filter_params=np.full(len(qis), tenant, np.int32),
            search_config=rag.search_config,
        )
        direct = np.asarray(out.ids)[:, : rag.search_config.result_k]
        for row, qi in enumerate(qis):
            np.testing.assert_array_equal(got[(tenant, qi)], direct[row])
    # traces are populated and the report's families agree
    for _, _, h in handles:
        tr = h.trace
        assert h.done() and tr.batch_size >= 1
        assert tr.queue_wait >= 0 and tr.search > 0 and tr.total > 0
        assert tr.n_ios + tr.n_cache_hits > 0
    assert rep["admitted"] == rep["completed"] == 8
    assert rep["failed"] == rep["rejected"] == 0
    assert sum(t["queries"] for t in rep["per_tenant"].values()) == 8
    assert set(rep["spans_mean_s"]) == {"queue_wait", "batch_form",
                                        "search", "drain"}


def test_tenant_validation(tiny_engine, tiny_corpus):
    _, _, queries = tiny_corpus
    with pytest.raises(ValueError, match="at least one TenantSpec"):
        ServeFrontend(_rag(tiny_engine), [])
    with pytest.raises(ValueError, match="duplicate tenant"):
        ServeFrontend(_rag(tiny_engine), [TenantSpec("a"), TenantSpec("a")])
    with ServeFrontend(_rag(tiny_engine), _tenants()) as srv:
        with pytest.raises(KeyError, match="unknown tenant"):
            srv.submit("nope", queries[0])


def test_admission_timeout_backpressure(tiny_engine, tiny_corpus):
    """max_inflight=1: while one request is in service, the next submit
    must block and then reject with AdmissionError, not queue unbounded."""
    _, _, queries = tiny_corpus
    rag = _rag(tiny_engine)
    inner = rag.retrieve
    gate = threading.Event()

    def slow_retrieve(reqs):
        gate.wait(timeout=10.0)
        return inner(reqs)

    rag.retrieve = slow_retrieve
    srv = ServeFrontend(rag, _tenants(max_inflight=1), max_batch=4,
                        batch_window_s=0.0)
    try:
        h = srv.submit("t0", queries[0])
        t0 = time.perf_counter()
        with pytest.raises(AdmissionError, match="max_inflight"):
            srv.submit("t0", queries[1], timeout=0.05)
        assert time.perf_counter() - t0 < 5.0  # timed out, didn't hang
        assert srv.rejected == 1
        # the OTHER tenant's budget is untouched by t0's backpressure
        h2 = srv.submit("t1", queries[2], timeout=0.05)
        gate.set()
        assert h.result(timeout=120.0) is not None
        assert h2.result(timeout=120.0) is not None
    finally:
        gate.set()
        srv.close()


def test_close_fails_queued_requests(tiny_engine, tiny_corpus):
    _, _, queries = tiny_corpus
    rag = _rag(tiny_engine)
    inner = rag.retrieve
    gate = threading.Event()

    def slow_retrieve(reqs):
        gate.wait(timeout=10.0)
        return inner(reqs)

    rag.retrieve = slow_retrieve
    srv = ServeFrontend(rag, _tenants(), max_batch=1, batch_window_s=0.0)
    first = srv.submit("t0", queries[0])
    time.sleep(0.05)  # let the dispatcher take the first into service
    queued = [srv.submit("t0", queries[i]) for i in range(1, 4)]
    # close() drains the queue immediately, then blocks joining the
    # dispatcher (still gated inside the first batch's retrieve)
    closer = threading.Thread(target=srv.close)
    closer.start()
    for h in queued:
        with pytest.raises(ServerClosed):
            h.result(timeout=10.0)
    gate.set()  # release the in-service batch so close() can finish
    closer.join(timeout=30.0)
    assert not closer.is_alive()
    assert first.result(timeout=120.0) is not None  # in-service: completes
    with pytest.raises(ServerClosed):
        srv.submit("t0", queries[0])
    srv.close()  # idempotent


def test_batch_failure_contained(tiny_engine, tiny_corpus):
    """An engine failure fails that batch's handles with the original
    exception and the server keeps serving the next batch."""
    _, _, queries = tiny_corpus
    rag = _rag(tiny_engine)
    inner = rag.retrieve
    # fail the first TWO requests regardless of how the dispatcher
    # batched them (one batch of 2 or two of 1 — both are legal timings)
    fail_budget = [2]

    def flaky_retrieve(reqs):
        if fail_budget[0] > 0:
            fail_budget[0] -= len(reqs)
            raise RuntimeError("injected mid-search failure")
        return inner(reqs)

    rag.retrieve = flaky_retrieve
    with ServeFrontend(rag, _tenants(), max_batch=4,
                       batch_window_s=0.005) as srv:
        bad = [srv.submit("t0", queries[i]) for i in range(2)]
        for h in bad:
            with pytest.raises(RuntimeError, match="injected"):
                h.result(timeout=120.0)
        good = srv.submit("t0", queries[0])
        assert good.result(timeout=120.0) is not None
        rep = srv.io_report()
    assert rep["failed"] == 2 and rep["completed"] == 1
    assert rep["per_tenant"]["t0"]["failed"] == 2
    # memory tier: abandon is a no-op that must still be callable
    assert tiny_engine.abandon_pending_io() == 0


def test_empty_and_unfiltered_tenants(tiny_engine, tiny_corpus):
    """A filter-less tenant serves the whole corpus; empty batches never
    reach the engine (close with nothing submitted is clean)."""
    _, _, queries = tiny_corpus
    rag = _rag(tiny_engine)
    with ServeFrontend(rag, [TenantSpec("all")], max_batch=4) as srv:
        h = srv.submit("all", queries[0])
        ids = h.result(timeout=120.0)
        out = tiny_engine.search(queries[:1], search_config=rag.search_config)
        np.testing.assert_array_equal(
            ids, np.asarray(out.ids)[0, : rag.search_config.result_k]
        )
    with ServeFrontend(_rag(tiny_engine), _tenants()) as srv:
        pass  # no traffic: close() must not hang or call the engine


def test_padding_reconciles_measured_disk_adaptive(serve_index, tiny_corpus):
    """Satellite regression: cache tier (adaptive) above the disk store +
    bucketed batches — the modeled served/padding split must reconcile
    EXACTLY against the store's measured records_read, batch after batch."""
    _, _, queries = tiny_corpus
    engine = GateANNEngine.load(
        serve_index, store_tier="disk", cache_budget_bytes=48 * RECORD,
        cache_policy="adaptive", refresh_every=1,
    )
    rag = _rag(engine, bucket_sizes=(4, 8), depth=2)
    from repro.serve.rag import RAGRequest

    def reqs(idxs, tenant=0):
        return [RAGRequest(query_vec=queries[i],
                           prompt_tokens=np.zeros(4, np.int32),
                           filter_kind="label",
                           filter_params=np.int32(tenant)) for i in idxs]

    # odd group sizes force padding; repeated batches move rows between
    # tiers as the adaptive hot set refreshes after every batch
    for batch in ([0, 1, 2], [3, 4, 5, 6, 7], [1, 2], [0, 1, 2, 3, 4]):
        rag.retrieve(reqs(batch))
    assert rag.padded_rows > 0
    assert rag.reconcile_drift == 0
    assert rag.measured_reads == rag.served_ios + rag.padding_ios
    rep = rag.io_report()
    assert rep["measured_slow_reads"] == rag.measured_reads
    assert rep["reconcile_drift"] == 0
    assert rep["abandoned_tokens"] == 0
    assert rep["padding_cache_hits"] >= 0
    engine.measured_store().close()


def test_concurrent_hammer_pipelined_disk(serve_index, tiny_corpus):
    """Satellite: mixed-tenant client threads through the pipelined disk
    path.  Results stay correct, every counter family holds under
    concurrency, and MID-FLIGHT registry snapshots (taken by a sampler
    thread while searches are in progress) satisfy the physical
    invariants — counter-snapshot atomicity, not just final totals.

    The whole run executes under the lockdep recorder: the store's
    counter lock and every segment's fd-open lock are proxy-wrapped, and
    the end of the test asserts no lock-order inversion was observed
    between ``_lock`` and ``_open_lock`` (the store's no-nesting
    invariant: fd opening happens before counter accounting)."""
    from repro import obs
    from repro.analysis import LockOrderRecorder, instrument_disk_store

    _, _, queries = tiny_corpus
    reg = obs.MetricsRegistry(enabled=True)
    with obs.use_registry(reg):
        engine = GateANNEngine.load(
            serve_index, store_tier="disk", cache_budget_bytes=48 * RECORD,
            cache_policy="adaptive", refresh_every=2,
        )
    store = engine.measured_store()
    lockdep = LockOrderRecorder()
    instrument_disk_store(lockdep, store)
    rag = _rag(engine, bucket_sizes=(4, 8), depth=2)
    n_threads, per_thread = 6, 4
    results, errs = {}, []
    snaps, stop = [], threading.Event()

    def client(tid):
        try:
            for j in range(per_thread):
                tenant = (tid + j) % 2
                qi = (tid * per_thread + j) % queries.shape[0]
                h = srv.submit(f"t{tenant}", queries[qi], timeout=30.0)
                results[(tid, j, tenant, qi)] = h.result(timeout=120.0)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    def sampler():
        while not stop.is_set():
            snaps.append(reg.snapshot())
            time.sleep(0.01)

    with obs.use_registry(reg), \
            ServeFrontend(rag, _tenants(), max_batch=8,
                          batch_window_s=0.002) as srv:
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        smp = threading.Thread(target=sampler)
        smp.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        smp.join(timeout=10.0)
        assert not errs, errs
        rep = srv.io_report()
    assert rep["completed"] == n_threads * per_thread
    assert rep["failed"] == 0
    # accounting invariants under concurrency
    assert rep["reconcile_drift"] == 0
    assert rep["abandoned_tokens"] == 0
    assert rag.measured_reads == rag.served_ios + rag.padding_ios
    c = store.io_counters()
    assert c["unique_sectors_read"] <= c["records_read"]

    # snapshot atomicity: EVERY mid-flight snapshot (registry state with
    # reads in flight) keeps the physical invariant — never more unique
    # sectors than requested records
    def fam_total(snap, name):
        fam = snap.get(name)
        return fam["total"] if fam else 0

    assert snaps, "sampler took no snapshots"
    for snap in snaps:
        assert fam_total(snap, "disk.unique_sectors_read") <= \
            fam_total(snap, "disk.records_read")
    # final registry totals reconcile bit-exactly with the store's own
    # measured counters (no reset ran, so the monotonic families match)
    for key in ("records_read", "pages_read", "unique_sectors_read",
                "syscalls", "read_rounds", "split_gaps"):
        assert reg.family_total(f"disk.{key}") == c[key], key
    assert reg.family_total("disk.abandoned_tokens") == 0
    # registry search-side total == store-side total (drift == 0 in
    # registry form: slow-tier dispatches are exactly the records read)
    assert reg.family_total("search.ios", tier="disk") == c["records_read"]
    if store.io_mode == "preadv":
        # one vectored read per round (one segment), plus one per hole
        # the gap bound left unbridged
        assert store.n_shards == 1
        assert c["syscalls"] == c["read_rounds"] + c["split_gaps"]
    assert sum(t["queries"] for t in rep["per_tenant"].values()) == \
        rep["completed"]
    # served ids match direct filtered search for every request
    for (tid, j, tenant, qi), ids in sorted(results.items()):
        out = engine.search(
            queries[qi][None], filter_kind="label",
            filter_params=np.asarray([tenant], np.int32),
            search_config=rag.search_config,
        )
        np.testing.assert_array_equal(
            ids, np.asarray(out.ids)[0, : rag.search_config.result_k],
            err_msg=str((tid, j, tenant, qi)),
        )
    store.close()
    # lock-order hygiene across the whole hammer (including close):
    # the counter lock and the segment open locks never nest in either
    # direction, so no inversion — and therefore no deadlock — is possible
    lockdep.assert_no_inversions()
    edges = lockdep.edges()
    counter, seg = "DiskRecordStore._lock", "_Segment._open_lock"
    assert (counter, seg) not in edges and (seg, counter) not in edges, \
        f"unexpected _lock/_open_lock nesting: {edges}"
