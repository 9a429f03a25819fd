"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode on the CPU cannot show what the TPU's compiler refuses
(block tiling, gathers, VMEM), so each kernel is AOT-compiled here for
one chip of a described ``v5e:2x2`` topology at the smoke test's shapes
(``chip_smoke.py``: B in {8, 32}, D=128, C=32, K=256, L=512, W=8,
M=W*(R+r_max) with R=32, r_max=16) and must contain a
``tpu_custom_call`` — a compiled Mosaic kernel, not an interpreted one.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around the compiles (a
compile for a described chip is written to it but cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import graph as graphm
from repro.core import pq as pqm
from repro.kernels import fused_traversal as ftk
from repro.kernels import l2_dist as l2k
from repro.kernels import pq_lookup as pqk
from repro.kernels import topk_merge as tkk

D, C, K, L, W, R, R_MAX = 128, 32, 256, 512, 8, 32, 16
M = W * (R + R_MAX)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fused(b, m):
    i32, f32, b_ = jnp.int32, jnp.float32, jnp.bool_
    return (
        lambda *a: ftk.fused_traversal_round(*a, mode="gate", width=W,
                                             interpret=False),
        [((b, L), i32), ((b, L), f32), ((b, L), b_), ((b, L), b_),
         ((b, m), i32), ((b, m, C), i32), ((b, m), b_), ((b, C, K), f32),
         ((b,), i32)],
    )


CASES = {
    "l2_dist": (
        lambda q, x: l2k.l2_dist(q, x, interpret=False),
        [((32, D), jnp.float32), ((32, W, D), jnp.float32)],
    ),
    "pq_lookup_gathered": (
        lambda lut, c: pqk.pq_lookup_gathered(lut, c, interpret=False),
        [((32, C, K), jnp.float32), ((32, M, C), jnp.int32)],
    ),
    "pq_scan": (
        lambda lut, c: pqk.pq_scan(lut, c, interpret=False),
        [((32, C, K), jnp.float32), ((4096, C), jnp.int32)],
    ),
    "topk_merge": (
        lambda d, i: tkk.topk_merge(d, i, L, interpret=False),
        [((32, L + M), jnp.float32), ((32, L + M), jnp.int32)],
    ),
    "fused_round_b8": _fused(8, M),
    "fused_round_b32": _fused(32, M),
    "fused_round0_b32": _fused(32, 0),  # the pre-loop call selects, merges nothing
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# The index build's nearest-point searches.  A plain jnp.argmin whose
# minimum is unused compiles for the TPU to a reduce over bf16 values,
# which picked wrong PQ centroids and prune candidates on the chip.
N_BUILD, B_BUILD = 4096, 64
BUILD_CASES = {
    "find_medoid": (
        jax.jit(graphm.find_medoid), [((N_BUILD, D), jnp.float32)], {},
    ),
    "robust_prune_batch": (
        graphm.robust_prune_batch,
        [((B_BUILD,), jnp.int32), ((B_BUILD, 224), jnp.int32),
         ((N_BUILD, D), jnp.float32)],
        dict(alpha=1.2, degree=R),
    ),
    "train_pq": (
        lambda v: pqm.train_pq(v, n_chunks=C, key=jax.random.PRNGKey(0)),
        [((N_BUILD, D), jnp.float32)], {},
    ),
    "encode_pq": (
        lambda books, v: pqm.encode_pq(
            pqm.PQCodec(books=books, n_chunks=C, n_centroids=K), v),
        [((C, K, D // C), jnp.float32), ((N_BUILD, D), jnp.float32)], {},
    ),
}


@pytest.mark.parametrize("name", sorted(BUILD_CASES))
def test_build_search_compares_f32_on_v5e(one_chip, name):
    fn, shapes, kw = BUILD_CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()
    assert "bf16" not in text, name
