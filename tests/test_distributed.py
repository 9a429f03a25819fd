"""Distributed correctness on 8 simulated host devices (subprocess-isolated
so the main pytest process keeps its single-device view)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, n_dev: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_record_store_matches_inmemory():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.store.vector_store import ShardedRecordStore, InMemoryRecordStore

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    n, d, r = 64, 8, 4
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    nbrs = rng.integers(-1, n, size=(n, r)).astype(np.int32)
    v_p, g_p, rows = ShardedRecordStore.shard_arrays(vecs, nbrs, 4)
    store = ShardedRecordStore(
        local_vectors=None, local_neighbors=None, rows_per_shard=rows)

    ids = rng.integers(-1, n, size=(6, 3)).astype(np.int32)

    def run(lv, ln, ids):
        s = ShardedRecordStore(local_vectors=lv, local_neighbors=ln,
                               rows_per_shard=rows)
        return s.fetch_fn()(ids)

    mapped = jax.shard_map(run, mesh=mesh,
        in_specs=(P("model", None), P("model", None), P(None, None)),
        out_specs=(P(None, None, None), P(None, None, None)), check_vma=False)
    got_v, got_n = jax.jit(mapped)(jnp.asarray(v_p), jnp.asarray(g_p), jnp.asarray(ids))
    ref = InMemoryRecordStore(vectors=jnp.asarray(vecs), neighbors=jnp.asarray(nbrs))
    want_v, want_n = ref.fetch_fn()(jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
    print("sharded fetch OK")
    """)


def test_distributed_retrieve_step_runs_and_filters():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.distributed_search import DistSearchConfig, make_retrieve_step
    from repro.core import pq as pqm
    from repro.core.graph import build_vamana, find_medoid
    from repro.data import make_bigann_like, make_queries, uniform_labels

    # mesh (data=2, model=4) — mirrors the production layout shape
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    n, d = 800, 16
    corpus = make_bigann_like(n, d, seed=0)
    labels = uniform_labels(n, 5, seed=0)
    g = build_vamana(corpus, degree=12, build_l=24, batch_size=256)
    codec = pqm.train_pq(jnp.asarray(corpus), n_chunks=8, iters=4)
    codes = pqm.encode_pq(codec, jnp.asarray(corpus))
    queries = make_queries(corpus, 8, seed=1)
    lut = pqm.build_lut(codec, jnp.asarray(queries))

    rows = -(-n // 4)
    import numpy as _np
    v_p = _np.pad(corpus, ((0, rows*4-n), (0, 0)))
    g_p = _np.pad(_np.asarray(g.neighbors), ((0, rows*4-n), (0, 0)), constant_values=-1)

    cfg = DistSearchConfig(search_l=32, beam_width=4, n_hops=24, visited_cap=512)
    step = make_retrieve_step(mesh, cfg, rows_per_shard=rows)
    out = step(jnp.asarray(queries), lut, codes,
               jnp.asarray(_np.asarray(g.neighbors)[:, :8]),
               jnp.asarray(labels), jnp.asarray(v_p), jnp.asarray(g_p),
               g.medoid, jnp.zeros((8,), jnp.int32))
    ids = np.asarray(out["ids"])
    valid = ids[ids >= 0]
    assert len(valid) > 0
    assert (np.asarray(labels)[valid] == 0).all(), "filter violated"
    assert float(np.mean(np.asarray(out["n_tunnels"]))) > 0
    # I/O reduction vs post mode
    step_post = make_retrieve_step(mesh, DistSearchConfig(
        search_l=32, beam_width=4, n_hops=24, visited_cap=512, mode="post"),
        rows_per_shard=rows)
    out_post = step_post(jnp.asarray(queries), lut, codes,
               jnp.asarray(_np.asarray(g.neighbors)[:, :8]),
               jnp.asarray(labels), jnp.asarray(v_p), jnp.asarray(g_p),
               g.medoid, jnp.zeros((8,), jnp.int32))
    r = float(np.mean(np.asarray(out["n_ios"]))) / max(
        float(np.mean(np.asarray(out_post["n_ios"]))), 1e-9)
    assert r < 0.5, f"io ratio {r}"
    print("distributed retrieve OK, io ratio", r)
    """)


@pytest.mark.parametrize("mode", ["gate", "post"])
def test_distributed_matches_single_host_oracle(mode):
    """Oracle parity for core/distributed_search.py: on a tiny CPU mesh the
    sharded fixed-hop loop must return the same ids/distances and I/O
    counters as the single-host ``filtered_search`` (which is itself
    pinned to the NumPy oracle of Algorithm 1 in test_search_oracle)."""
    _run(f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.tree_util import Partial
    from repro.core.distributed_search import DistSearchConfig, make_retrieve_step
    from repro.core import pq as pqm
    from repro.core.search import SearchConfig, filtered_search
    from repro.core.filter_store import EqualityFilter
    from repro.core.neighbor_store import NeighborStore
    from repro.core.graph import build_vamana
    from repro.data import make_bigann_like, make_queries, uniform_labels
    from repro.store.vector_store import InMemoryRecordStore

    mode = {mode!r}
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    n, d, r_max, L, W, K = 600, 16, 8, 32, 4, 10
    corpus = make_bigann_like(n, d, seed=3)
    labels = uniform_labels(n, 5, seed=3)
    g = build_vamana(corpus, degree=12, build_l=24, batch_size=256, seed=3)
    codec = pqm.train_pq(jnp.asarray(corpus), n_chunks=8, iters=4)
    codes = pqm.encode_pq(codec, jnp.asarray(corpus))
    queries = make_queries(corpus, 8, seed=4)
    lut = pqm.build_lut(codec, jnp.asarray(queries))
    targets = jnp.zeros((8,), jnp.int32)

    # single-host reference: the oracle-pinned Algorithm 1 loop
    store = InMemoryRecordStore(vectors=jnp.asarray(corpus),
                                neighbors=jnp.asarray(g.neighbors))
    ref = filtered_search(
        fetch=store.fetch_fn(),
        neighbor_store=NeighborStore.from_graph(g.neighbors, r_max),
        filter_check=EqualityFilter(jnp.asarray(labels)).bind(targets),
        lut=lut, codes=codes, entry=g.medoid, queries=jnp.asarray(queries),
        config=SearchConfig(mode=mode, search_l=L, beam_width=W, result_k=K),
    )

    # distributed run: generous hop budget + visited capacity so the
    # frontier fully drains and the ring buffer never overwrites
    rows = -(-n // 4)
    v_p = np.pad(corpus, ((0, rows*4-n), (0, 0)))
    g_p = np.pad(np.asarray(g.neighbors), ((0, rows*4-n), (0, 0)),
                 constant_values=-1)
    cfg = DistSearchConfig(search_l=L, beam_width=W, result_k=K,
                           n_hops=96, visited_cap=4096, mode=mode)
    step = make_retrieve_step(mesh, cfg, rows_per_shard=rows)
    out = step(jnp.asarray(queries), lut, codes,
               jnp.asarray(np.asarray(g.neighbors)[:, :r_max]),
               jnp.asarray(labels), jnp.asarray(v_p), jnp.asarray(g_p),
               g.medoid, targets)

    ids_ref = np.asarray(ref.ids)
    ids_dist = np.asarray(out["ids"])
    np.testing.assert_array_equal(ids_dist, ids_ref)
    valid = ids_ref >= 0
    np.testing.assert_allclose(np.asarray(out["dists"])[valid],
                               np.asarray(ref.dists)[valid], rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(out["n_ios"]),
                                  np.asarray(ref.stats.n_ios))
    np.testing.assert_array_equal(np.asarray(out["n_tunnels"]),
                                  np.asarray(ref.stats.n_tunnels))
    print("distributed oracle parity OK:", mode)
    """)


def test_retrieve_step_from_disk_segments():
    """The record tier fed from per-shard on-disk segments: save(shards=4),
    load each shard's rows off its own segment file only, and the mesh
    retrieve step must match single-host ``filtered_search`` exactly —
    the persisted sharded layout serves the production mesh unchanged."""
    _run("""
    import os, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import EngineConfig, GateANNEngine
    from repro.core import pq as pqm
    from repro.core.distributed_search import (
        DistSearchConfig, load_shard_records, load_sharded_record_arrays,
        make_retrieve_step)
    from repro.core.search import SearchConfig, filtered_search
    from repro.data import make_bigann_like, make_queries, uniform_labels

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    n, d, L, W, K = 400, 16, 32, 4, 10
    corpus = make_bigann_like(n, d, seed=5)
    labels = uniform_labels(n, 5, seed=5)
    eng = GateANNEngine.build(
        corpus, config=EngineConfig(degree=12, build_l=24, pq_chunks=8, r_max=8),
        labels=labels)
    path = os.path.join(tempfile.mkdtemp(), "dist.gann")
    eng.save(path, shards=4)

    # per-host path: each shard opens ONLY its own segment file
    v0, n0, rows = load_shard_records(path, 0)
    assert v0.shape == (rows, d) and n0.shape[0] == rows
    v_p, g_p, rows2 = load_sharded_record_arrays(path)
    assert rows2 == rows and v_p.shape[0] == rows * 4

    queries = make_queries(corpus, 8, seed=6)
    lut = pqm.build_lut(eng.codec, jnp.asarray(queries))
    targets = jnp.zeros((8,), jnp.int32)
    ref = eng.search(queries, filter_kind="label", filter_params=targets,
                     search_config=SearchConfig(mode="gate", search_l=L,
                                                beam_width=W, result_k=K))
    cfg = DistSearchConfig(search_l=L, beam_width=W, result_k=K,
                           n_hops=96, visited_cap=4096, mode="gate")
    step = make_retrieve_step(mesh, cfg, rows_per_shard=rows)
    out = step(jnp.asarray(queries), lut, eng.codes,
               eng.neighbor_store.neighbors, jnp.asarray(labels),
               jnp.asarray(v_p), jnp.asarray(g_p),
               eng.medoid, targets)
    np.testing.assert_array_equal(np.asarray(out["ids"]), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(out["n_ios"]),
                                  np.asarray(ref.stats.n_ios))
    print("segment-fed retrieve parity OK")
    """)


@pytest.mark.slow  # jits a sharded model train step on 8 emulated devices
def test_train_step_sharded_2x4():
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.distributed.sharding import make_layout, tree_pspecs
    from repro.models import transformer as tfm, zoo
    from repro.optim import OptConfig, opt_init
    from repro.train.train_step import (TrainHParams, TrainState,
        make_train_state_specs, make_train_step)

    cfg = dataclasses.replace(get_smoke_config("deepseek-coder-33b"),
                              dtype="float32")
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    layout = make_layout("train", mesh)
    params, axes = tfm.init_model(jax.random.PRNGKey(0), cfg)
    hp = TrainHParams(opt=OptConfig(name="adamw"))
    state = TrainState(params=params, opt=opt_init(params, hp.opt),
                       step=jnp.zeros((), jnp.int32))
    specs = make_train_state_specs(params, axes, layout, "adamw")
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                      is_leaf=lambda s: isinstance(s, P))
    state = jax.device_put(state, sh)
    b, t = 4, 32
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32),
             "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)}
    bsh = {"tokens": NamedSharding(mesh, P("data", "model")),
           "targets": NamedSharding(mesh, P("data", "model"))}
    batch = jax.device_put(batch, bsh)
    step = jax.jit(make_train_step(cfg, layout, hp),
                   in_shardings=(sh, bsh), out_shardings=(sh, None))
    l0 = None
    for i in range(4):
        state, metrics = step(state, batch)
        l = float(metrics["loss"])
        assert np.isfinite(l)
        l0 = l if l0 is None else l0
    assert l < l0, (l0, l)  # same batch -> loss must drop
    print("sharded train OK", l0, "->", l)
    """)


@pytest.mark.slow  # two full model forwards (sharded + replicated) in subprocesses
def test_sharded_equals_single_device():
    """Numerical parity: the sharded loss equals the unsharded loss."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.distributed.sharding import NULL_LAYOUT, make_layout
    from repro.models import transformer as tfm

    cfg = dataclasses.replace(get_smoke_config("gemma3-4b"), dtype="float32")
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    b, t = 4, 32
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32),
             "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)}
    l_single = float(jax.jit(lambda p, bt: tfm.lm_loss(p, cfg, NULL_LAYOUT, bt))(params, batch))
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    layout = make_layout("train", mesh)
    l_shard = float(jax.jit(lambda p, bt: tfm.lm_loss(p, cfg, layout, bt))(params, batch))
    np.testing.assert_allclose(l_shard, l_single, rtol=2e-4)
    print("parity OK", l_single, l_shard)
    """)
