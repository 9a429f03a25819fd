#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration file and a
traffic file; ``bench/spec.py`` finds them by name.  One process, in
order:

  1. the chip: JAX must see a TPU and as many chips as the cell asks
     for, or the run exits non-zero and prints no result;
  2. set-up: the corpus (``bench/corpora/``) and the records' attributes
     (the configuration's filter schema, ``bench/filters/``) from the
     configuration's ``data_seed``, the request pool (query vectors and
     predicates) from ``--seed``; the engine's ``SearchConfig`` from the
     whole ``search`` group and the reference plan
     (``bench/reference/plans/``), both refused by name before any index
     is built if unknown; the index loaded from
     ``bench/.cache/index`` or built there first (``bench/index.py``);
     the serving stack (``RAGServer`` behind ``ServeFrontend``, with the
     schema's tenants); one engine call per warm-up burst of the traffic
     file, so the window compiles nothing;
  3. the window: the traffic file's loop (``bench/loops/``) for
     ``--seconds``; with ``--trace 1`` a profiler trace of part of it;
  4. the check (``bench/check.py``) of a sample of the window's answers
     against the configuration's reference plan, after the program's
     state is freed;
  5. the result: info lines, then the numbers compared with their limits
     as the last lines on stderr, then one JSON line on stdout.

``--trace 0`` reports the cell's end-to-end metrics and ``--trace 1`` its
per-layer metrics; each is computed by its reader in
``bench/metrics/<name>.py``.  Client threads, the frontend's dispatcher
and the disk tier's readers are threads of this process.
"""
from __future__ import annotations

import time

START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, "bench", ".cache")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import check as checkm  # noqa: E402
from bench import data as datam  # noqa: E402
from bench import index as indexm  # noqa: E402
from bench import loop as loopm  # noqa: E402
from bench import roofline  # noqa: E402
from bench import spec as specm  # noqa: E402
from bench import trace as tracem  # noqa: E402
from bench.reference import index_file  # noqa: E402

GRACE_S = 60.0  # how long past the window an answer may come back
TRACE_DELAY_S = 1.0  # the traced part of a --trace 1 window starts here
TRACE_SECONDS = 3.0  # ... and lasts this long (shorter windows: all of it)
STATS = ("n_ios", "n_cache_hits", "n_tunnels", "n_exact", "n_hops")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def require_chips(n: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX sees {len(devs)}")
    return devs[:n]


@dataclasses.dataclass
class Call:
    """One engine call (``RAGServer.retrieve``) made by the dispatcher."""

    t0: float
    t1: float
    rows: int  # requests in the batch (padding rows not counted)
    stats: dict  # SearchStats field -> (rows,) array


@dataclasses.dataclass
class Run:
    """What a metric reader reads: one window of one cell."""

    config: dict
    traffic: dict
    device_kind: str
    setup_s: float
    start: float  # perf_counter seconds the window opened
    seconds: float
    requests: list  # loop.Request, every request sent in the window
    calls: list  # Call, every engine call that began in the window
    trace: dict | None = None  # --trace 1: see _traced_window


class Stack:
    """The serving path of a configuration: ``RAGServer`` behind
    ``ServeFrontend`` with the filter schema's tenants, and a thin wrapper
    around ``RAGServer.retrieve`` that logs each engine call and marks it
    in a profiler trace."""

    def __init__(self, engine, config: dict, traffic: dict, n: int, timeout: float,
                 schema, search):
        import jax

        from repro.serve import RAGServer, ServeFrontend, TenantSpec

        self.rag = RAGServer(
            engine=engine, cfg=None, params=None, layout=None,
            passage_tokens=np.zeros((n, 1), np.int32),
            search_config=search,
            bucket_sizes=tuple(traffic["bucket_sizes"]),
        )
        self.calls: list[Call] = []
        inner = self.rag.retrieve

        def retrieve(requests):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.retrieve"):
                ids, stats = inner(requests)
            self.calls.append(Call(t0, time.perf_counter(), len(requests),
                                   {f: np.asarray(getattr(stats, f)) for f in STATS}))
            return ids, stats

        self.rag.retrieve = retrieve
        self.config, self.schema = config, schema
        tenants = schema.tenants(config)
        self.filters = {name: (kind, params) for name, kind, params in tenants}
        self.frontend = ServeFrontend(
            self.rag,
            [TenantSpec(name, kind, params,
                        max_inflight=int(traffic.get("max_inflight", 4096)))
             for name, kind, params in tenants],
            max_batch=int(traffic["max_batch"]),
            batch_window_s=float(traffic["batch_window_s"]),
            admission_timeout_s=timeout,
        )

    def warm(self, vecs: np.ndarray, predicates, bursts, max_batch: int) -> None:
        """One engine call per burst size, straight into ``retrieve`` so
        each compiles exactly its bucket's shapes; then every batch size
        of the frontend once more through ``retrieve`` over a stand-in
        engine, which compiles the small ops that assemble a batch of
        that size without searching.  Each request is built as the
        frontend would serve it: the filter of the tenant that the
        schema's ``route`` names, with the route's keywords that name a
        ``RAGRequest`` field laid over it."""
        from repro.serve.rag import RAGRequest

        fields = {f.name for f in dataclasses.fields(RAGRequest)}
        reqs = []
        for i in range(max(max_batch, sum(bursts))):
            tenant, kw = self.schema.route(self.config, predicates[i % len(vecs)])
            kind, params = self.filters[tenant]
            req = dict(query_vec=vecs[i % len(vecs)],
                       prompt_tokens=np.zeros((0,), np.int32),
                       filter_kind=kind, filter_params=params)
            req.update((k, v) for k, v in kw.items() if k in fields)
            reqs.append(RAGRequest(**req))
        j = 0
        for b in bursts:
            self.rag.retrieve(reqs[j:j + int(b)])
            j += int(b)
        engine = self.rag.engine
        self.rag.engine = _NoSearch(self.rag.search_config.result_k)
        try:
            for g in range(1, max_batch + 1):
                self.rag.retrieve(reqs[:g])
        finally:
            self.rag.engine = engine

    def close(self) -> None:
        self.frontend.close()
        store = self.rag.engine.measured_store()
        if store is not None:
            store.close()


class _NoSearch:
    """An engine that answers every row with nothing, at once."""

    def __init__(self, k: int):
        self.k = k

    def search(self, queries, **_kw):
        from repro.core.search import SearchOutput, SearchStats

        b = len(queries)
        zero = np.zeros((b,), np.int32)
        return SearchOutput(ids=np.full((b, self.k), -1, np.int32),
                            dists=np.zeros((b, self.k), np.float32),
                            stats=SearchStats(*[zero] * len(SearchStats._fields)))

    def io_counters(self) -> dict:
        return {}

    def maybe_refresh(self) -> bool:
        return False


def _span_sums() -> dict:
    from repro import obs

    out = {}
    for c in obs.default_registry().children("trace.span_seconds"):
        name = c.labels.get("span")
        out[name] = out.get(name, 0.0) + c.sum
    return out


def _traced_window(start: float, seconds: float, log_dir: str, box: dict) -> threading.Thread:
    """Profile [start + delay, + TRACE_SECONDS] of the window on a helper
    thread; ``box`` gets the perf_counter bounds and span sums."""
    import jax

    from repro import obs

    delay = TRACE_DELAY_S if seconds >= TRACE_DELAY_S + TRACE_SECONDS else 0.0
    length = min(TRACE_SECONDS, seconds - delay)

    def work():
        time.sleep(max(0.0, start + delay - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        obs.enable()
        obs.trace.enable()
        # the part starts once the profiler collects: its start can take
        # a second or more, and the work and spans are counted from here
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        box["spans0"] = _span_sums()
        box["t0"] = time.perf_counter()
        time.sleep(max(0.0, box["t0"] + length - time.perf_counter()))
        box["t1"] = time.perf_counter()
        box["spans1"] = _span_sums()
        jax.profiler.stop_trace()
        box["stop_s"] = time.perf_counter() - box["t1"]
        obs.trace.disable()
        obs.disable()

    t = threading.Thread(target=work, name="tracer", daemon=True)
    t.start()
    return t


def _pro_rated_work(calls: list, t0: float, t1: float, config: dict) -> dict:
    """Summed SearchStats of the calls, each weighted by the share of its
    time that fell in [t0, t1]."""
    tot = {f: 0.0 for f in STATS}
    for c in calls:
        share = max(0.0, min(c.t1, t1) - max(c.t0, t0)) / max(c.t1 - c.t0, 1e-12)
        for f in STATS:
            tot[f] += share * float(np.sum(c.stats[f]))
    ix = config["index"]
    ops, nbytes = roofline.search_work(
        tot, dim=int(config["corpus"]["dim"]), pq_chunks=ix["pq_chunks"],
        degree=ix["degree"], r_max=ix["r_max"])
    return {"ops": ops, "bytes": nbytes}


def _window(stack: Stack, tr: dict, send, n: int, seconds: float,
            trace_dir: str | None, rng, root: str):
    """Drive the traffic for ``seconds`` (``send(i)`` submits request ``i``
    of the pool of ``n``): (requests, trace box).  Counts the programs
    compiled while it runs."""
    import jax

    # the compile event fires for a program loaded from the persistent
    # cache too; a retrace that reuses a program fires neither
    compiles = []

    def on_compile(event, _duration=None, **_kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(1)
        elif event.endswith("compilation_cache/cache_hits"):
            compiles.append(-1)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_compile)
    start = time.perf_counter() + 0.01
    box: dict = {"start": start}
    tracer = _traced_window(start, seconds, trace_dir, box) if trace_dir else None
    try:
        reqs, gen = loopm.run(tr["loop"], send, n, start=start, seconds=seconds,
                              grace_s=GRACE_S, rng=rng,
                              queue_depth=stack.frontend.queue_depth, root=root)
        if tracer is not None:
            tracer.join()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        jax.monitoring.unregister_event_listener(on_compile)
    say(f"generator {gen}; programs compiled in the window: {sum(compiles)}")
    return reqs, box


def _read_trace(trace_dir: str, box: dict, reqs: list, calls: list,
                config: dict, device_kind: str) -> dict:
    """The trace's reduction (``bench/trace.py``) with the host-side
    readings of the same part of the window."""
    t_read = time.perf_counter()
    red = tracem.reduce(tracem.load(tracem.find(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    say(f"trace written in {box['stop_s']:.1f} s, read and reduced in "
        f"{time.perf_counter() - t_read:.1f} s")
    t0, t1 = box["t0"], box["t1"]
    work = _pro_rated_work(calls, t0, t1, config)
    _, bound = roofline.least_time(work["ops"], work["bytes"], device_kind)
    say(f"trace: window {red['window_s']:.3f} s, busy {red['busy_s']:.3f} s, "
        f"idle share {red['idle_share']:.4f}; search work {work['ops']:.4g} "
        f"operations, {work['bytes']:.4g} bytes, bound by {bound}")
    return dict(red, t0=t0, t1=t1, work=work,
                spans={k: box["spans1"].get(k, 0.0) - box["spans0"].get(k, 0.0)
                       for k in box["spans1"]},
                answered=sum(1 for r in reqs if r.ok and t0 <= r.t_done <= t1))


def _faults(n: int) -> dict:
    """Faults planted in a sample's answers, for the limits' readings:
    each answer's first id altered; the second half of the sample given
    the first half's answers (half of a batch left out)."""
    def altered(ids):
        ids = np.array(ids)
        ids[0] = (ids[0] + 1) % n if ids[0] >= 0 else ids[0]
        return ids

    return {"answer_altered": lambda served: [altered(a) for a in served],
            "half_left_out": lambda served: (
                served[:len(served) - len(served) // 2] + served[:len(served) // 2])}


def check_answers(s: Setup, reqs: list, vecs: np.ndarray, predicates, rng,
                  readings: bool) -> dict:
    """``{"program": numbers}`` of a sample of the window's answers
    (``reqs``; request ``i`` asked for ``vecs[i]`` filtered on
    ``predicates[i]``); with ``readings`` also the control's numbers and
    those of each fault."""
    t0 = time.perf_counter()
    cfg, corpus = s.cell.config, s.corpus
    k = int(cfg["search"]["result_k"])
    sections = index_file.read(s.info["path"], s.plan.SECTIONS)
    answered = [r for r in reqs if r.ok]
    size = min(int(s.cell.traffic["check_sample"]), len(answered))
    sample = [answered[i] for i in
              np.sort(rng.choice(len(answered), size=size, replace=False))]
    served = [r.ids for r in sample]
    rows = np.asarray([r.index for r in sample], np.int64)
    s_vecs = vecs[rows]
    masks = [s.schema.passes(s.attrs, predicates[i]) for i in rows]
    reference = s.plan.answers(sections, corpus, s_vecs, masks, cfg, "f32")
    truth = checkm.brute_force(corpus, s_vecs, masks, k)

    def numbers(answers_):
        return checkm.numbers(answers_, reference, truth, corpus, s_vecs, masks, k)

    out = {"program": dict(numbers(served), unanswered=len(reqs) - len(answered),
                           sample=size)}
    say(f"check of {size} answers of {len(reqs)} took {time.perf_counter() - t0:.1f} s; "
        f"recall@{k} against exact filtered brute force "
        f"{1.0 - out['program']['recall_miss']:.4f}")
    if readings:
        out["control"] = numbers(s.plan.answers(sections, corpus, s_vecs, masks, cfg,
                                                "bf16"))
        out["faults"] = {name: numbers(f(list(served)))
                         for name, f in _faults(corpus.shape[0]).items()}
    return out


@dataclasses.dataclass
class Setup:
    """A cell's data and its serving stack, warm."""

    cell: specm.Cell
    bench: specm.Bench
    corpus: np.ndarray
    attrs: object  # the records' attributes, by the filter schema
    schema: object  # the configuration's filter schema (bench/filters/)
    plan: object  # the configuration's reference plan (bench/reference/plans/)
    stack: Stack
    info: dict  # bench/index.py: cold, path, build_s
    setup_s: float


def search_config(config: dict):
    """The engine's ``SearchConfig``, made of the configuration's whole
    ``search`` group; a key that names no field of it is refused."""
    from repro.core import SearchConfig

    s = config["search"]
    unknown = sorted(set(s) - {f.name for f in dataclasses.fields(SearchConfig)})
    if unknown:
        raise ValueError(f"unknown search key(s) {unknown} in configuration "
                         f"{config.get('name')!r}: no field of SearchConfig")
    return SearchConfig(**s)


def requests(cell: specm.Cell, corpus: np.ndarray, attrs, seed: int, schema):
    """The seed's request pool: (query vectors, each one's predicate).
    The schema's ``requests`` sees the vectors, the corpus row each was
    drawn near and the records' attributes."""
    cfg, tr = cell.config, cell.traffic
    rng = datam.streams(seed)
    pool = min(int(tr["pool"]), corpus.shape[0])
    vecs, sources = datam.queries(tr["query"], corpus, pool, rng["queries"])
    return vecs, schema.requests(cfg, tr["labels"], pool, rng["requests"],
                                 vecs, sources, attrs)


def set_up(name: str, seed: int, seconds: float, *, root: str, cache_dir: str,
           start_time: float) -> Setup:
    """Data, index and serving stack of cell ``name``, warmed on the
    shapes of the seed's traffic."""
    bench = specm.Bench(root)
    cell = bench.cell(name)
    cfg, tr = cell.config, cell.traffic
    schema, plan, search = bench.schema(cfg), bench.plan(cfg), search_config(cfg)
    data_rng = datam.streams(cfg["data_seed"])
    corpus = datam.corpus(cfg, data_rng["corpus"], root)
    attrs = schema.records(cfg, corpus.shape[0], data_rng["labels"], corpus)
    vecs, preds = requests(cell, corpus, attrs, seed, schema)
    engine, info = indexm.open_engine(cfg, corpus, attrs, schema,
                                      cache_dir=cache_dir, say=say)
    stack = Stack(engine, cfg, tr, corpus.shape[0], seconds + GRACE_S, schema, search)
    del engine
    stack.warm(vecs[::-1], preds[::-1], tr["warmup_bursts"], int(tr["max_batch"]))
    stack.calls.clear()
    setup_s = time.perf_counter() - start_time
    say(f"set-up {setup_s:.3f} s "
        f"({'cold: index built' if info['cold'] else 'warm: index loaded'})")
    return Setup(cell, bench, corpus, attrs, schema, plan, stack, info, setup_s)


def window(s: Setup, seed: int, seconds: float, trace_dir: str | None):
    """One window of the seed's traffic on a set-up stack: (requests,
    engine calls, trace box, request vectors, their predicates)."""
    vecs, preds = requests(s.cell, s.corpus, s.attrs, seed, s.schema)
    routes = [s.schema.route(s.cell.config, p) for p in preds]
    submit, timeout = s.stack.frontend.submit, seconds + GRACE_S

    def send(i):
        tenant, kw = routes[i]
        return submit(tenant, vecs[i], timeout=timeout, **kw)

    s.stack.calls.clear()
    reqs, box = _window(s.stack, s.cell.traffic, send, len(vecs), seconds, trace_dir,
                        datam.streams(seed)["arrivals"], s.bench.root)
    calls = [c for c in s.stack.calls if c.t0 >= box["start"]]
    if calls:
        dur = np.asarray([c.t1 - c.t0 for c in calls])
        say(f"engine calls {len(calls)}: seconds mean {dur.mean():.4f} "
            f"median {np.median(dur):.4f} max {dur.max():.4f}; rows mean "
            f"{np.mean([c.rows for c in calls]):.2f}; rounds mean "
            f"{np.mean([c.stats['n_hops'].max() for c in calls]):.2f}")
    return reqs, calls, box, vecs, preds


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, devices,
             root: str = ROOT, cache_dir: str = CACHE_DIR,
             start_time: float | None = None) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    kind = devices[0].device_kind
    say(f"cell {name} seed {seed} seconds {seconds} trace {int(trace)}")
    say(f"device platform={devices[0].platform} device_kind={kind!r} "
        f"count={len(devices)}")
    s = set_up(name, seed, seconds, root=root, cache_dir=cache_dir,
               start_time=START if start_time is None else start_time)
    cfg, tr = s.cell.config, s.cell.traffic
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    reqs, calls, box, vecs, preds = window(s, seed, seconds, trace_dir)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    say(f"memory_peak_bytes {peak}")
    s.stack.close()
    s.stack = None
    gc.collect()

    run = Run(config=cfg, traffic=tr, device_kind=kind, setup_s=s.setup_s,
              start=box["start"], seconds=seconds, requests=reqs, calls=calls)
    if trace:
        run.trace = _read_trace(trace_dir, box, reqs, calls, cfg, kind)
    metrics = {}
    for m in (s.cell.per_layer if trace else s.cell.end_to_end):
        v = s.bench.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    say("metrics " + json.dumps(metrics))

    # the check, on the host, once the program's state is freed
    numbers = check_answers(s, reqs, vecs, preds, datam.streams(seed)["sample"],
                            readings=False)["program"]
    say("numbers " + json.dumps(numbers))
    limits = cfg["limits"]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": checkm.verdict(numbers, limits) and numbers["sample"] > 0,
           "attempted": len(reqs), "failed": numbers["unanswered"],
           "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=run.trace["busy_s"], window_s=run.trace["window_s"])
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in checkm.NUMBERS if k in limits}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CACHE_DIR, "jax"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = require_chips(specm.Bench(ROOT).cell(args.workload).chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices=devices)
    print(f"correct {out['correct']}; the numbers compared:", file=sys.stderr)
    for k, v in out["checks"].items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
