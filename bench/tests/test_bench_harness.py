"""The harness on the CPU at tiny sizes: no chip means no result; every
name in BENCHMARK.json resolves to its file; cells and metrics, and a
configuration with its own reference plan, schema and search setting,
can be added as files alone; the warm-up sends requests as they are
routed; the index cache key; and whole runs, sound and with the timed
path broken, whose ``correct`` has to follow."""
import dataclasses
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import index as indexm  # noqa: E402
from bench import spec as specm  # noqa: E402
from bench.tests import tiny  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"), "--workload",
         "bigann-ssd.closed64", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr and "cpu" in p.stderr
    assert not p.stdout.strip()


def test_unknown_loop_kind_is_refused():
    from bench import loop as loopm

    with pytest.raises(ValueError, match="unknown loop kind 'bursty'"):
        loopm.run({"kind": "bursty", "rate_qps": 5.0}, None, 0, start=0.0,
                  seconds=0.0, grace_s=0.0, rng=None, queue_depth=None)


def test_every_name_resolves_to_its_file():
    bench = specm.Bench()
    spec = bench.spec
    for c in spec["configs"]:
        cfg = bench.config(c["name"])
        assert cfg["name"] == c["name"]
        assert c["file"].startswith("bench/configs/")
        assert callable(bench.schema(cfg).passes)
        assert callable(bench.plan(cfg).answers)
        assert callable(specm.plugin(bench.root, "corpora", cfg["corpus"]["generator"],
                                     "corpus generator").make)
    for w in spec["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.chips == 1
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        # a per-layer metric of a cell moves an end-to-end metric the cell reports
        assert all(m["moves"] in reported for m in cell.per_layer), w["name"]
        assert callable(specm.plugin(bench.root, "loops", cell.traffic["loop"]["kind"],
                                     "loop kind").run)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"]))
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        for w in m.get("workloads", ()):
            assert w in {x["name"] for x in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        assert m["workloads"], m["name"]  # none attaches itself to a cell unasked
        for w in m["workloads"]:
            assert w in {x["name"] for x in spec["workloads"]}


# a corpus generator, a filter schema and a loop kind, each a file alone
CUBE = """import numpy as np


def make(n, dim, rng, scale=1.0):
    return rng.uniform(0.0, float(scale), size=(n, dim)).astype(np.float32)
"""

PAIR = """\"\"\"pair: two attributes per record; a request asks for given values of
both, served by the program's tags filter (value a of the first is tag
a, value b of the second tag A + b).\"\"\"
import numpy as np


def _bits(config, a, b):
    return np.array([(1 << int(a)) | (1 << (config["pair"]["a"] + int(b)))], np.uint32)


def records(config, n, rng, corpus):
    p = config["pair"]
    return np.stack([rng.integers(0, p["a"], n), rng.integers(0, p["b"], n)], 1).astype(np.int32)


def build_args(config, attrs):
    return {"tag_bits": np.concatenate([_bits(config, a, b) for a, b in attrs])[:, None]}


def key_groups(config):
    return {"filter": "pair", "pair": config["pair"]}


def requests(config, spec, n, rng, queries, sources, attrs):
    return records(config, n, rng, None)


def tenants(config):
    p = config["pair"]
    return [(f"a{a}b{b}", "tags", _bits(config, a, b))
            for a in range(p["a"]) for b in range(p["b"])]


def route(config, predicate):
    return f"a{int(predicate[0])}b{int(predicate[1])}", {}


def passes(attrs, predicate):
    return (attrs[:, 0] == predicate[0]) & (attrs[:, 1] == predicate[1])
"""

SERIAL = """from bench.loop import Request, sleep_until, submit, wait
import time


def run(spec, send, n, *, start, seconds, grace_s, rng, queue_depth):
    out = []
    sleep_until(start)
    while (now := time.perf_counter()) < start + seconds:
        req = Request(index=len(out) % n, t_sched=now)
        out.append(req)
        h = submit(send, req)
        if h is not None:
            wait(h, req, seconds + grace_s)
        time.sleep(float(spec["pause_s"]))
    return out, {"loop": "serial"}
"""


def _write(root, rel, text):
    with open(os.path.join(root, rel), "w") as f:
        f.write(text if isinstance(text, str) else json.dumps(text))


def test_a_cell_and_a_metric_added_as_files_alone(tmp_path):
    import jax

    from bench import run

    root = tiny.make_root(str(tmp_path))
    _write(root, "bench/traffic/single.json",
           dict(tiny.TRAFFIC, loop={"kind": "closed", "clients": 1}))
    _write(root, "bench/metrics/frontend.calls.py",
           "def read(run):\n    return len(run.calls) or None\n")
    _write(root, "bench/corpora/uniform_cube.py", CUBE)
    _write(root, "bench/filters/pair.py", PAIR)
    _write(root, "bench/loops/serial.py", SERIAL)
    _write(root, "bench/traffic/serial.json",
           dict(tiny.TRAFFIC, loop={"kind": "serial", "pause_s": 0.001}))
    cfg = dict(tiny.config("memory"), name="tiny-pair", filter="pair", pair={"a": 2, "b": 3},
               corpus={"generator": "uniform_cube", "dim": 16, "scale": 10.0})
    del cfg["labels"]
    cfg["search"] = dict(cfg["search"], search_l=64)  # uniform points are harder to reach
    _write(root, "bench/configs/tiny-pair.json", cfg)
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-pair", "source": "tests",
                            "file": "bench/configs/tiny-pair.json", "reduced": [],
                            "why": "tests"})
    spec["workloads"] += [
        {"name": "tiny-memory.single", "config": "tiny-memory", "traffic": "single",
         "chips": 1, "why": "tests"},
        {"name": "tiny-pair.serial", "config": "tiny-pair", "traffic": "serial",
         "chips": 1, "why": "tests"}]
    spec["per_layer"].append({"name": "frontend.calls", "unit": "calls",
                              "better": "lower", "source": "program_counter",
                              "layer": "frontend", "moves": "qps",
                              "workloads": ["tiny-memory.single", "tiny-pair.serial"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    bench = specm.Bench(root)
    cell = bench.cell("tiny-memory.single")
    assert cell.traffic["loop"]["clients"] == 1
    assert "frontend.calls" in {m["name"] for m in cell.per_layer}
    assert "frontend.calls" not in {m["name"] for m in bench.cell("tiny-memory.t").per_layer}

    class Run:
        calls = [1, 2, 3]

    assert bench.reader("frontend.calls")(Run()) == 3

    out = run.run_cell("tiny-pair.serial", 2**34 + 1, 1.0, False, devices=jax.devices(),
                       root=root, cache_dir=os.path.join(root, "cache"))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 5 and out["failed"] == 0
    assert out["checks"]["filter_violations"]["value"] == 0
    # p95_ms lists its cells; the metrics that list none attach themselves
    assert set(out["metrics"]) == {"qps", "p50_ms", "setup_s"}
    # the records and requests are pairs, and the index was built with their tags
    s = run.set_up("tiny-pair.serial", 5, 1.0, root=root,
                   cache_dir=os.path.join(root, "cache"), start_time=0.0)
    try:
        assert s.attrs.shape == (1200, 2) and not s.info["cold"]
        assert s.corpus.max() <= 10.0 and s.corpus.min() >= 0.0
        assert "tags" in s.stack.rag.engine.filters
    finally:
        s.stack.close()


# the shape of a filter-track deployment: a reference plan that scans the
# passing records exactly where they are few, a schema whose records
# read the corpus and whose requests read each query's source row, and a
# search setting the program's SearchConfig gains with it
SCAN_OR_WALK = """\"\"\"scan_or_walk: the passing records scanned exactly where they number
at most ``search.scan_below``; the default walk otherwise.\"\"\"
import numpy as np

from bench.reference import oracle
from bench.reference.plans import walk

SECTIONS = walk.SECTIONS


def _scan(corpus, q, mask, k, precision):
    ids = np.flatnonzero(mask)
    if precision == "f32":
        diff = corpus[ids].astype(np.float64) - q.astype(np.float64)
        d = np.sum(diff * diff, axis=1)
    else:
        diff = oracle._bf16(corpus[ids]) - oracle._bf16(q)
        d = oracle._bf16(np.sum(diff * diff, axis=1, dtype=np.float32))
    top = ids[np.argsort(d, kind="stable")[:k]]
    return np.concatenate([top, np.full(k - top.size, -1, np.int64)])


def answers(sections, corpus, queries, masks, config, precision):
    s = config["search"]
    walked = [i for i, m in enumerate(masks) if m.sum() > int(s["scan_below"])]
    out = {i: ids for i, ids in zip(walked, walk.answers(
        sections, corpus, queries[walked], [masks[i] for i in walked], config, precision))}
    return [out[i] if i in out else _scan(corpus, q, m, int(s["result_k"]), precision)
            for i, (q, m) in enumerate(zip(queries, masks))]
"""

TAGGED = """\"\"\"tagged: a record carries the tag of its vector's largest leading
coordinate; a request asks for the tag of the record its query was
drawn near.\"\"\"
import numpy as np


def records(config, n, rng, corpus):
    return np.argmax(corpus[:, :config["tags"]], axis=1).astype(np.int32)


def build_args(config, attrs):
    return {"labels": attrs}


def key_groups(config):
    return {"filter": "tagged", "tags": config["tags"]}


def requests(config, spec, n, rng, queries, sources, attrs):
    return attrs[sources]


def tenants(config):
    return [(f"tag{t}", "label", np.int32(t)) for t in range(config["tags"])]


def route(config, predicate):
    return f"tag{int(predicate)}", {}


def passes(attrs, predicate):
    return attrs == int(predicate)
"""


def test_a_filter_track_configuration_added_as_files_alone(tmp_path, monkeypatch):
    import jax

    from bench import check, data, run
    from bench.reference import index_file
    from repro import core

    root = tiny.make_root(str(tmp_path))
    cache = os.path.join(root, "cache")
    _write(root, "bench/reference/plans/scan_or_walk.py", SCAN_OR_WALK)
    _write(root, "bench/filters/tagged.py", TAGGED)
    cfg = dict(tiny.config("memory"), name="tiny-tagged", filter="tagged", tags=4,
               reference="postings")
    del cfg["labels"]
    cfg["search"] = dict(cfg["search"], scan_below=2)
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-tagged", "source": "tests",
                            "file": "bench/configs/tiny-tagged.json", "reduced": [],
                            "why": "tests"})
    spec["workloads"].append({"name": "tiny-tagged.t", "config": "tiny-tagged",
                              "traffic": "t", "chips": 1, "why": "tests"})
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    def set_up(config):
        _write(root, "bench/configs/tiny-tagged.json", config)
        return run.set_up("tiny-tagged.t", 2**33 + 9, 1.0, root=root, cache_dir=cache,
                          start_time=0.0)

    # an unknown plan, then a search key the program's SearchConfig lacks:
    # each refused by name before any index is built
    with pytest.raises(ValueError, match="unknown reference plan 'postings'"):
        set_up(cfg)
    cfg["reference"] = "scan_or_walk"
    with pytest.raises(ValueError, match=r"unknown search key\(s\) \['scan_below'\]"):
        set_up(cfg)
    assert not os.path.exists(os.path.join(cache, "index"))

    # the program's SearchConfig as the change that serves the scan makes it
    scan = dataclasses.make_dataclass("SearchConfig", [("scan_below", int, 0)],
                                      bases=(core.SearchConfig,), frozen=True)
    monkeypatch.setattr(core, "SearchConfig", scan)
    out = run.run_cell("tiny-tagged.t", 2**34 + 5, 1.0, False, devices=jax.devices(),
                       root=root, cache_dir=cache)
    assert out["correct"], out["checks"]
    assert out["checks"]["mismatch"]["value"] == 0 and out["failed"] == 0
    s = set_up(cfg)
    try:
        assert s.stack.rag.search_config.scan_below == 2 and not s.info["cold"]
        assert s.plan.__name__.endswith("scan_or_walk")
        np.testing.assert_array_equal(s.attrs, np.argmax(s.corpus[:, :4], axis=1))
        tr = s.cell.traffic
        vecs, preds = run.requests(s.cell, s.corpus, s.attrs, 2**33 + 9, s.schema)
        _, sources = data.queries(tr["query"], s.corpus, len(vecs),
                                  data.streams(2**33 + 9)["queries"])
        np.testing.assert_array_equal(preds, s.attrs[sources])
    finally:
        s.stack.close()

    # the plan's own answers: an exact scan where it scans, the walk where not
    sections = index_file.read(s.info["path"], s.plan.SECTIONS)
    q, masks = vecs[:24], [s.schema.passes(s.attrs, p) for p in preds[:24]]
    truth = check.brute_force(s.corpus, q, masks, 10)

    def plan(scan_below):
        c = dict(cfg, search=dict(cfg["search"], scan_below=scan_below))
        return s.plan.answers(sections, s.corpus, q, masks, c, "f32")

    scanned, walked = plan(s.corpus.shape[0]), plan(0)
    np.testing.assert_array_equal(np.asarray(scanned), truth)
    np.testing.assert_array_equal(
        np.asarray(walked), np.asarray(s.bench.plan({}).answers(sections, s.corpus, q, masks,
                                                                cfg, "f32")))
    assert check.numbers(scanned, scanned, truth, s.corpus, q, masks, 10)["mismatch"] == 0
    altered = [np.where(np.arange(10) == 0, (a + 1) % s.corpus.shape[0], a) for a in scanned]
    assert check.numbers(altered, scanned, truth, s.corpus, q, masks, 10)["mismatch"] > 0


def test_warm_up_sends_each_request_as_routed():
    """A route's keywords that name a ``RAGRequest`` field take the place
    of the tenant's filter in the requests the warm-up builds."""
    from bench import run

    def route(config, p):
        if p % 2:
            return "all", {"filter_kind": "range", "filter_params": np.float32(p),
                           "deadline_s": 5.0}
        return "all", {}

    sent = []
    fake = types.SimpleNamespace(
        config={}, schema=types.SimpleNamespace(route=route),
        filters={"all": ("label", np.int32(7))},
        rag=types.SimpleNamespace(retrieve=lambda reqs: sent.append(list(reqs)),
                                  engine=None,
                                  search_config=types.SimpleNamespace(result_k=10)))
    vecs = np.arange(12, dtype=np.float32).reshape(6, 2)
    run.Stack.warm(fake, vecs, [0, 1, 2, 3, 4, 5], [2, 4], 3)
    assert [len(b) for b in sent] == [2, 4, 1, 2, 3]
    for i, r in enumerate(sent[0] + sent[1]):
        np.testing.assert_array_equal(r.query_vec, vecs[i])
        if i % 2:
            assert (r.filter_kind, r.filter_params) == ("range", np.float32(i))
        else:
            assert (r.filter_kind, int(r.filter_params)) == ("label", 7)
    assert fake.rag.engine is None


@pytest.mark.parametrize("twin,metric", [("frontend.p95_ms.open", "p95_ms"),
                                          ("frontend.queue_wait_ms.open",
                                           "frontend.queue_wait_ms")])
def test_open_loop_readers_read_as_their_twins(twin, metric):
    import types

    bench = specm.Bench()
    reqs = [types.SimpleNamespace(ok=True, latency=0.01 * k,
                                  trace=types.SimpleNamespace(queue_wait=0.002 * k))
            for k in range(1, 41)]
    run = types.SimpleNamespace(requests=reqs)
    assert bench.reader(twin)(run) == bench.reader(metric)(run) > 0
    assert bench.reader(twin)(types.SimpleNamespace(requests=[])) is None


def test_index_cache_key_moves_with_data_seed_code_and_precision():
    cfg = tiny.config("disk")
    base = dict(config=cfg, code="abc", precision="None",
                jax_version="0.9.0", platform="tpu")
    key = indexm.cache_key(**base)
    assert indexm.cache_key(**base) == key
    for change in ({"config": dict(cfg, data_seed=8)}, {"code": "abd"},
                   {"precision": "bfloat16"}, {"platform": "cpu"},
                   {"schema_groups": {"filter": "pair", "pair": {"a": 2}}}):
        assert indexm.cache_key(**dict(base, **change)) != key
    assert indexm.cache_key(**dict(base, schema_groups={})) == key
    assert len(indexm.code_hash()) == 16


# -- whole runs on the CPU ------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, cell, seed=2**35 + 3, seconds=1.5, **kw):
    import jax

    from bench import run

    return run.run_cell(cell, seed, seconds, False, devices=jax.devices(),
                        root=root, cache_dir=os.path.join(root, "cache"), **kw)


# the end-to-end metrics each tiny cell reports, as the cell it stands for
E2E = {"tiny-disk.t": {"qps", "p50_ms", "p95_ms", "setup_s"},
       "tiny-memory.t": {"qps", "p50_ms", "p95_ms", "setup_s"},
       "tiny-disk.o": {"qps", "p50_ms", "setup_s"}}


@pytest.mark.parametrize("cell", ["tiny-disk.t", "tiny-memory.t", "tiny-disk.o"])
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == E2E[cell]
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", ["tiny-disk.t", "tiny-memory.t", "tiny-disk.o"])
def test_limit_readings_separate_program_and_faults(root, cell):
    from bench import limits

    rows = limits.readings(cell, [2**33 + 1, 2**33 + 2], 1.0, root=root,
                           cache_dir=os.path.join(root, "cache"))
    assert len(rows) == 2 and all(r["program"]["sample"] > 0 for r in rows)
    got = limits.summary(rows)
    lim = tiny.config("disk" if "disk" in cell else "memory")["limits"]
    assert all(got["lower"][k] <= lim[k] for k in got["lower"])
    for fault in ("answer_altered", "half_left_out"):
        assert any(got["upper"][fault][k] > lim[k] for k in got["upper"][fault])


def _alter_first_answer(ids):
    ids = np.array(ids)
    ids[:, 0] = np.where(ids[:, 0] >= 0, (ids[:, 0] + 1) % 1200, ids[:, 0])
    return ids


def _half_batch_left_out(ids):
    ids = np.array(ids)
    h = (ids.shape[0] + 1) // 2
    ids[h:] = ids[:ids.shape[0] - h]  # the rest get answers for others
    return ids


class _StateUnchanged:
    """Each batch answered with the first batch's answers."""

    def __init__(self):
        self.first = None

    def __call__(self, ids):
        ids = np.array(ids)
        if self.first is None:
            self.first = ids.copy()
        return np.resize(self.first, ids.shape)


@pytest.mark.parametrize("fault", ["alter_first_answer", "half_batch_left_out",
                                   "state_unchanged"])
@pytest.mark.parametrize("cell", ["tiny-disk.t", "tiny-memory.t", "tiny-disk.o"])
def test_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch):
    from repro.core import GateANNEngine

    search = GateANNEngine.search
    plant = {"alter_first_answer": _alter_first_answer,
             "half_batch_left_out": _half_batch_left_out,
             "state_unchanged": _StateUnchanged()}[fault]

    def broken(self, *a, **kw):
        out = search(self, *a, **kw)
        return out._replace(ids=plant(out.ids))

    monkeypatch.setattr(GateANNEngine, "search", broken)
    out = _run(root, cell)
    assert not out["correct"], out["checks"]
