"""The harness on the CPU at tiny sizes: no chip means no result; every
name in BENCHMARK.json resolves to its file; cells and metrics can be
added as files alone; the index cache key; and whole runs, sound and
with the timed path broken, whose ``correct`` has to follow."""
import json
import os
import re
import subprocess
import sys

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

    with pytest.raises(ValueError, match="unknown loop kind"):
        loopm.run({"kind": "open", "rate_qps": 5.0}, None, [], [], start=0.0,
                  seconds=0.0, grace_s=0.0)


def test_every_name_resolves_to_its_file():
    bench = specm.Bench()
    spec = bench.spec
    for c in spec["configs"]:
        assert bench.config(c["name"])["name"] == c["name"]
        assert c["file"].startswith("bench/configs/")
    for w in spec["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.chips == 1
        assert {m["name"] for m in cell.end_to_end} >= {"qps", "p50_ms", "p95_ms", "setup_s"}
        assert cell.per_layer
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"]))
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        for w in m.get("workloads", ()):
            assert w in {x["name"] for x in spec["workloads"]}


def test_a_cell_and_a_metric_added_as_files_alone(tmp_path):
    root = tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "bench", "traffic", "single.json"), "w") as f:
        json.dump(dict(tiny.TRAFFIC, loop={"kind": "closed", "clients": 1}), f)
    with open(os.path.join(root, "bench", "metrics", "frontend.calls.py"), "w") as f:
        f.write("def read(run):\n    return len(run.calls) or None\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny-memory.single", "config": "tiny-memory",
                              "traffic": "single", "chips": 1, "why": "tests"})
    spec["per_layer"].append({"name": "frontend.calls", "unit": "calls",
                              "better": "lower", "source": "program_counter",
                              "layer": "frontend", "moves": "qps",
                              "workloads": ["tiny-memory.single"]})
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


def test_index_cache_key_moves_with_data_seed_code_and_precision():
    cfg = tiny.config("disk")
    base = dict(config=cfg, code="abc", precision="None",
                jax_version="0.9.0", platform="tpu")
    key = indexm.cache_key(**base)
    assert indexm.cache_key(**base) == key
    for change in ({"config": dict(cfg, data_seed=8)}, {"code": "abd"},
                   {"precision": "bfloat16"}, {"platform": "cpu"}):
        assert indexm.cache_key(**dict(base, **change)) != key
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


@pytest.mark.parametrize("cell", ["tiny-disk.t", "tiny-memory.t"])
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"qps", "p50_ms", "p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", ["tiny-disk.t", "tiny-memory.t"])
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
@pytest.mark.parametrize("cell", ["tiny-disk.t", "tiny-memory.t"])
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
