"""Telemetry subsystem: registry, histograms, tracer, exports, contracts.

Tier-1 coverage for ``src/repro/obs``:

  * registry semantics — families are (name, kind, labels); mismatched
    kinds/label sets raise; ``name`` is a reserved label key; a
    DISABLED registry's write path is an early-out (pinned structurally
    and by the overhead guard below).
  * histogram percentiles — log-bucket p50/p99 land within one bucket
    ratio of the exact sample percentiles; sum/count/mean are exact.
  * exporters — Prometheus text renders identically from the live
    registry and from its JSON snapshot (the scrape-vs-artifact
    bit-exactness the nightly ``obs-contracts`` job relies on).
  * tracer — perf_counter spans land in per-thread rings and the
    ``trace.span_seconds`` histogram family; sampling keeps 1-in-N;
    disabled tracing returns the shared no-op context manager.
  * store reconciliation — a disk-tier search's registry families
    agree bit-exactly with ``DiskRecordStore.io_counters()`` and with
    the summed ``SearchStats``.
  * monotonic timing (satellite) — serving-path span math never reads
    ``time.time()``: a wall-clock step backwards mid-request cannot
    produce a negative span.
  * overhead guard (satellite) — with telemetry disabled, the
    instrumented search path must stay within noise of a no-op stub:
    the stats-recording hook is proven unreachable, and the disabled
    counter/span primitives stay within an order of magnitude of an
    empty call (generous bound — CI timing noise, not a benchmark).
"""
import json
import math
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import export, registry as regm, tracer as tracerm


# ---------------------------------------------------------------- registry
def test_counter_gauge_families():
    reg = obs.MetricsRegistry(enabled=True)
    reg.counter("req.total", tenant="a").inc()
    reg.counter("req.total", tenant="a").inc(2)
    reg.counter("req.total", tenant="b").inc(5)
    assert reg.counter("req.total", tenant="a").value == 3
    assert reg.family_total("req.total") == 8
    assert reg.family_total("req.total", tenant="b") == 5
    g = reg.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3
    # same name, different kind or label set => error
    with pytest.raises(TypeError, match="is a counter"):
        reg.gauge("req.total", tenant="a")
    with pytest.raises(ValueError, match="has labels"):
        reg.counter("req.total", shard="0")
    # the `name` label key collides with the positional family name —
    # reserved by the API (use another key, e.g. `span`)
    with pytest.raises(TypeError):
        reg.counter("x", name="y")


def test_disabled_registry_records_nothing():
    reg = obs.MetricsRegistry(enabled=False)
    c = reg.counter("n")
    h = reg.histogram("h")
    c.inc(100)
    h.observe(1.0)
    assert c.value == 0 and h.count == 0
    reg.enable()
    c.inc(1)
    assert c.value == 1
    reg.disable()
    c.inc(1)
    assert c.value == 1


def test_registry_snapshot_shape():
    reg = obs.MetricsRegistry(enabled=True)
    reg.counter("a.b", mode="gate").inc(7)
    reg.histogram("lat").observe(0.5)
    snap = reg.snapshot()
    assert snap["a.b"]["kind"] == "counter"
    assert snap["a.b"]["total"] == 7
    assert snap["a.b"]["children"][0]["labels"] == {"mode": "gate"}
    h = snap["lat"]
    assert h["kind"] == "histogram"
    child = h["children"][0]
    assert child["count"] == 1 and child["sum"] == 0.5
    assert child["min"] == child["max"] == 0.5
    json.dumps(snap)  # JSON-serializable as-is


# -------------------------------------------------------------- histograms
def test_histogram_percentiles_within_bucket_error():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-5.0, sigma=1.0, size=20_000)
    reg = obs.MetricsRegistry(enabled=True)
    h = reg.histogram("lat")
    for v in samples:
        h.observe(v)
    assert h.count == samples.size
    assert h.sum == pytest.approx(float(samples.sum()))
    assert h.mean == pytest.approx(float(samples.mean()))
    # worst-case relative error is one bucket ratio (~26% at 10/decade);
    # allow a bit of slack for the interpolation at the bucket ends
    ratio = 10 ** (1 / regm.HIST_PER_DECADE)
    for q in (0.50, 0.99, 0.999):
        exact = float(np.quantile(samples, q))
        got = h.quantile(q)
        assert exact / (ratio * 1.1) <= got <= exact * (ratio * 1.1), \
            f"q={q}: got {got}, exact {exact}"
    # quantiles never extrapolate outside the observed range
    assert h.quantile(0.0) >= float(samples.min())
    assert h.quantile(1.0) <= float(samples.max())


def test_histogram_concurrent_observe_exact_count():
    reg = obs.MetricsRegistry(enabled=True)
    h = reg.histogram("lat")
    n_threads, per = 8, 2000

    def work():
        for i in range(per):
            h.observe(1e-4 * (1 + i % 7))

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.count == n_threads * per


# --------------------------------------------------------------- exporters
def test_prometheus_identical_from_registry_and_snapshot():
    reg = obs.MetricsRegistry(enabled=True)
    reg.counter("disk.records_read", store="x.gann").inc(42)
    reg.gauge("disk.inflight_depth", store="x.gann").set(3)
    h = reg.histogram("trace.span_seconds", span="disk.preadv")
    for v in (1e-4, 2e-4, 5e-3):
        h.observe(v)
    live = export.to_prometheus(reg)
    snap = export.to_json(reg, tracerm.Tracer())
    again = export.to_prometheus(snap)
    assert live == again
    assert 'gateann_disk_records_read{store="x.gann"} 42' in live
    assert "# TYPE gateann_trace_span_seconds histogram" in live
    # cumulative buckets end at +Inf == count
    assert 'le="+Inf"' in live
    assert "gateann_trace_span_seconds_count" in live
    doc = export.to_json(reg, tracerm.Tracer())
    assert doc["schema_version"] == export.SCHEMA_VERSION
    assert doc["families"]["disk.records_read"]["total"] == 42


def test_write_obs_json_sections(tmp_path):
    reg = obs.MetricsRegistry(enabled=True)
    reg.counter("serve.admitted", tenant="t0").inc(5)
    path = tmp_path / "obs.json"
    payload = export.write_obs_json(
        str(path), sections={"serve": (reg, tracerm.Tracer())}
    )
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(payload))
    assert on_disk["serve"]["families"]["serve.admitted"]["total"] == 5
    assert "process" in on_disk


# ------------------------------------------------------------------ tracer
def test_tracer_spans_ring_and_histogram():
    reg = obs.MetricsRegistry(enabled=True)
    tr = tracerm.Tracer(registry=reg)
    assert tr.span("x") is tracerm._NOP  # disabled => shared no-op
    tr.enable()
    with tr.span("stage.a", k="v"):
        pass
    tr.record("stage.b", 0.25)
    snap = tr.snapshot()
    spans = [s for ring in snap.values() for s in ring]
    names = sorted(s["name"] for s in spans)
    assert names == ["stage.a", "stage.b"]
    for s in spans:
        assert s["dur_s"] >= 0
    b = next(s for s in spans if s["name"] == "stage.b")
    assert b["dur_s"] == 0.25
    hist = reg.children("trace.span_seconds")
    assert {c.labels["span"] for c in hist} == {"stage.a", "stage.b"}


def test_tracer_sampling_keeps_one_in_n():
    reg = obs.MetricsRegistry(enabled=True)
    tr = tracerm.Tracer(registry=reg)
    tr.enable(sample_rate=0.25)  # keep 1 in 4 per thread
    for _ in range(100):
        with tr.span("s"):
            pass
    kept = reg.histogram("trace.span_seconds", span="s").count
    assert kept == 25
    with pytest.raises(ValueError, match="sample_rate"):
        tr.enable(sample_rate=0.0)


def test_tracer_ring_overwrites_oldest():
    tr = tracerm.Tracer(ring_size=4)
    tr.enable()
    for i in range(10):
        tr.record(f"s{i}", 0.0)
    spans = [s for ring in tr.snapshot().values() for s in ring]
    assert [s["name"] for s in spans] == ["s6", "s7", "s8", "s9"]


class _Annotations:
    """A stand-in for ``jax.profiler.TraceAnnotation`` that logs what the
    tracer enters and leaves."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


@pytest.mark.parametrize("enabled", [True, False])
def test_span_enters_a_profiler_annotation_only_when_enabled(monkeypatch, enabled):
    import jax

    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    monkeypatch.setattr(tracerm, "_Annotation", None)  # re-import the patched one
    tr = tracerm.Tracer(registry=obs.MetricsRegistry(enabled=True))
    if enabled:
        tr.enable()
    with tr.span("disk.drain", store="x"):
        ann.log.append(("body", None))
    expect = [("enter", "disk.drain"), ("body", None), ("exit", "disk.drain")]
    assert ann.log == (expect if enabled else [("body", None)])
    tr.record("serve.queue_wait", 0.5)  # a backdated span is ring-only
    assert ann.log == (expect if enabled else [("body", None)])


# ----------------------------------------------- store/search reconciliation
def test_disk_search_reconciles_registry(tiny_engine, tiny_corpus, tmp_path):
    """Registry families == measured store counters == summed SearchStats,
    bit-exact, for a real disk-tier search."""
    from repro.core import GateANNEngine, SearchConfig

    _, _, queries = tiny_corpus
    path = str(tmp_path / "obs.gann")
    tiny_engine.save(path)
    reg = obs.MetricsRegistry(enabled=True)
    with obs.use_registry(reg):
        engine = GateANNEngine.load(path, store_tier="disk")
        out = engine.search(
            queries, filter_kind="label",
            filter_params=np.zeros(queries.shape[0], np.int32),
            search_config=SearchConfig(mode="gate", search_l=32, beam_width=4),
        )
        ios = int(np.sum(np.asarray(out.stats.n_ios)))
    store = engine.measured_store()
    c = store.io_counters()
    # three-way: registry == measured == modeled
    assert reg.family_total("disk.records_read") == c["records_read"] == ios
    for key in ("pages_read", "bytes_read", "unique_sectors_read",
                "ranges_read", "syscalls", "fetch_rounds", "read_rounds",
                "split_gaps", "gap_sectors_read"):
        assert reg.family_total(f"disk.{key}") == c[key], key
    if store.io_mode == "preadv":
        assert c["syscalls"] == c["read_rounds"] + c["split_gaps"]
    assert reg.family_total("search.ios", tier="disk", mode="gate") == ios
    assert reg.family_total("search.queries") == queries.shape[0]
    # fetched-vs-tunneled split is non-trivial in gate mode
    assert reg.family_total("search.tunnels", mode="gate") > 0
    # a store-side reset must NOT reset the registry (monotonic families)
    store.reset_io_counters()
    assert store.io_counters()["records_read"] == 0
    assert reg.family_total("disk.records_read") == ios
    store.close()


@pytest.mark.parametrize("depth", [1, 2], ids=["sync", "pipelined"])
def test_disk_callback_spans_cover_the_callbacks(tiny_engine, tiny_corpus,
                                                 tmp_path, depth):
    """The disk tier's callbacks run under their spans: a live drain under
    ``disk.drain`` with ``disk.drain_wait`` inside it on the same thread,
    one ``disk.submit`` per submission, one ``disk.fetch`` per synchronous
    fetch round with its ``disk.preadv`` inside."""
    from repro.core import GateANNEngine, SearchConfig

    _, _, queries = tiny_corpus
    path = str(tmp_path / "spans.gann")
    tiny_engine.save(path)
    reg = obs.MetricsRegistry(enabled=True)
    tracer = obs.trace.default_tracer()
    tracer.reset()
    try:
        with obs.use_registry(reg):
            tracer.enable()
            engine = GateANNEngine.load(path, store_tier="disk")
            out = engine.search(
                queries, filter_kind="label",
                filter_params=np.zeros(queries.shape[0], np.int32),
                search_config=SearchConfig(mode="gate", search_l=32, beam_width=4,
                                           pipeline_depth=depth),
            )
            np.asarray(out.ids)
            tracer.disable()
        snap = tracer.snapshot()
    finally:
        tracer.disable()
        tracer.reset()
    spans = {t: [(s["name"], s["start"], s["start"] + s["dur_s"]) for s in ring]
             for t, ring in snap.items()}
    names = [n for ring in spans.values() for n, _, _ in ring]

    def inside(inner, outer):
        found = 0
        for ring in spans.values():
            outs = [(a, b) for n, a, b in ring if n == outer]
            for n, a, b in ring:
                if n == inner:
                    assert any(x <= a and b <= y for x, y in outs), (inner, outer)
                    found += 1
        return found

    if depth == 1:
        assert names.count("disk.fetch") == reg.family_total("disk.fetch_rounds") > 0
        assert inside("disk.preadv", "disk.fetch") > 0
        assert "disk.submit" not in names and "disk.drain" not in names
    else:
        assert names.count("disk.submit") == reg.family_total("disk.submits") > 0
        assert names.count("disk.drain") == reg.family_total("disk.drains")
        assert inside("disk.drain_wait", "disk.drain") == names.count("disk.drain") > 0
        assert "disk.fetch" not in names
    engine.measured_store().close()


# ------------------------------------------------------- monotonic timing
def test_serving_spans_immune_to_wall_clock_steps(tiny_engine, tiny_corpus,
                                                  monkeypatch):
    """Satellite: span math uses perf_counter, so a wall clock stepping
    BACKWARDS mid-request cannot produce a negative span.  time.time is
    patched to run backwards; any timing code still reading it would go
    negative."""
    from repro.serve import RAGServer, ServeFrontend, TenantSpec
    from repro.core import SearchConfig

    # serving-layer sources must not read the wall clock at all
    import inspect
    from repro.serve import server as server_mod
    from repro.obs import tracer as tracer_mod
    for mod in (server_mod, tracer_mod):
        assert "time.time(" not in inspect.getsource(mod), mod.__name__

    t0 = time.time()
    steps = [0.0]

    def backwards():
        steps[0] -= 60.0  # one minute back per read
        return t0 + steps[0]

    monkeypatch.setattr(time, "time", backwards)
    _, _, queries = tiny_corpus
    rag = RAGServer(
        engine=tiny_engine, cfg=None, params=None, layout=None,
        passage_tokens=np.zeros((int(tiny_engine.vectors.shape[0]), 4),
                                np.int32),
        search_config=SearchConfig(mode="gate", search_l=32, beam_width=4),
    )
    with ServeFrontend(rag, [TenantSpec("t0", "label", np.int32(0))],
                       max_batch=4, batch_window_s=0.0) as srv:
        hs = [srv.submit("t0", queries[i]) for i in range(4)]
        for h in hs:
            h.result(timeout=120.0)
        rep = srv.io_report()
    for h in hs:
        tr = h.trace
        for k in ("queue_wait", "batch_form", "search", "drain"):
            assert getattr(tr, k) >= 0.0, k
        assert tr.search > 0.0
    for k, v in rep["spans_mean_s"].items():
        assert v >= 0.0, k


# ---------------------------------------------------------- overhead guard
def test_disabled_telemetry_is_structurally_off(tiny_engine, tiny_corpus,
                                                monkeypatch):
    """With the registry disabled, the stats-recording hook on the search
    path must be UNREACHABLE — not just cheap.  Raising from it proves
    the guarded branch never runs."""
    from repro.core import SearchConfig

    def boom(*a, **k):  # pragma: no cover - reaching it is the failure
        raise AssertionError("record_search_stats ran with obs disabled")

    monkeypatch.setattr(obs.stats, "record_search_stats", boom)
    _, _, queries = tiny_corpus
    reg = obs.MetricsRegistry(enabled=False)
    with obs.use_registry(reg):
        out = tiny_engine.search(
            queries[:4], filter_kind="label",
            filter_params=np.zeros(4, np.int32),
            search_config=SearchConfig(mode="gate", search_l=32,
                                       beam_width=4),
        )
    assert np.asarray(out.ids).shape[0] == 4
    assert reg.families() in ([], ["search.dispatch"])  # counters stayed 0
    assert reg.family_total("search.dispatch") == 0


def test_disabled_primitives_overhead_guard():
    """Tier-1 overhead guard: the disabled counter/span fast path stays
    within an order of magnitude of a no-op stub (min-of-N timing — this
    pins the early-out structure, not absolute speed)."""
    reg = obs.MetricsRegistry(enabled=False)
    c = reg.counter("hot")
    tr = tracerm.Tracer(registry=reg)  # disabled

    def stub():
        pass

    n = 20_000

    def best_of(fn, reps=5):
        best = math.inf
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_stub = best_of(stub)
    t_inc = best_of(lambda: c.inc())
    t_span = best_of(lambda: tr.span("s"))
    # generous 10x bound over an empty python call: the disabled paths
    # are one attribute read + branch (plus arg passing).  A lock or
    # histogram touch on the disabled path would blow far past this.
    assert t_inc < 10 * t_stub + 0.05, (t_inc, t_stub)
    assert t_span < 10 * t_stub + 0.05, (t_span, t_stub)
