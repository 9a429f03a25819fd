"""Record stores — the expensive storage tier holding full-precision records.

A *record* is the TPU analogue of DiskANN's 4 KB SSD sector: the node's
full-precision vector together with its full adjacency list.  Fetching a
record is the expensive operation GateANN's tunneling avoids; three tiers
are provided:

  * ``InMemoryRecordStore``   — plain device gathers (CPU tests, and the
                                Vamana in-memory baseline tier).
  * ``ShardedRecordStore``    — records sharded over the mesh ``model``
                                axis; a fetch is a masked local gather +
                                ``psum`` over ``model`` (remote HBM over
                                ICI — the production "SSD read").
  * ``HostOffloadRecordStore``— records pinned in host memory via
                                ``memory_kind='pinned_host'``; a fetch is
                                a host-DMA gather (closest analogue to an
                                NVMe read on a real TPU host).

All expose ``fetch_fn() -> (ids (B, W)) -> (vecs (B, W, D), nbrs (B, W, R))``
usable inside jit / shard_map.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

# A RecordFetchFn maps (B, W) ids -> (vecs (B, W, D), nbrs (B, W, R)).
# Concrete stores return jax.tree_util.Partial so fetches are pytrees
# (stable function identity, traced storage leaves — no retrace per call).
RecordFetchFn = Callable[[jax.Array], Tuple[jax.Array, jax.Array]]


def is_lazy_host(a) -> bool:
    """True for lazy host-resident corpus views (the disk tier's
    ``vectors``) that must never be shipped to the device wholesale —
    cache wiring gathers the hot rows host-side instead.  Covers
    memmap-backed arrays and any object flagging ``__lazy_host__``
    (e.g. the multi-segment ``LazySegmentVectors``)."""
    if getattr(a, "__lazy_host__", False):
        return True
    while isinstance(a, np.ndarray):
        if isinstance(a, np.memmap):
            return True
        if a.base is None:
            return False
        a = a.base
    return False


def _inmem_fetch(vectors, neighbors, ids):
    safe = jnp.maximum(ids, 0)
    vecs = jnp.where(ids[..., None] >= 0, vectors[safe], 0.0)
    nbrs = jnp.where(ids[..., None] >= 0, neighbors[safe], jnp.int32(-1))
    return vecs, nbrs


@dataclasses.dataclass(frozen=True)
class InMemoryRecordStore:
    vectors: jax.Array  # (N, D) float32
    neighbors: jax.Array  # (N, R) int32

    def fetch_fn(self) -> RecordFetchFn:
        return Partial(_inmem_fetch, self.vectors, self.neighbors)

    def record_bytes(self) -> int:
        n, d = self.vectors.shape
        r = self.neighbors.shape[1]
        # 4 KB-aligned like DiskANN sectors
        raw = d * 4 + (r + 1) * 4
        return n * ((raw + 4095) // 4096) * 4096


_SHARDED_FETCH_CACHE: dict = {}


def _sharded_fetch_factory(axis_name):
    """Per-axis-name fetch fn with stable identity (cached)."""
    if axis_name not in _SHARDED_FETCH_CACHE:

        def fetch(lv, ln, rows, ids, _axis=axis_name):
            shard = jax.lax.axis_index(_axis)
            local = ids - shard * rows
            mine = (ids >= 0) & (local >= 0) & (local < rows)
            safe = jnp.clip(local, 0, lv.shape[0] - 1)
            vecs = jnp.where(mine[..., None], lv[safe], 0.0)
            nbrs = jnp.where(mine[..., None], ln[safe] + 1, 0)  # shift: -1 pad sums right
            vecs = jax.lax.psum(vecs, _axis)
            nbrs = jax.lax.psum(nbrs, _axis) - 1  # unshift: unowned/-1 rows -> -1
            nbrs = jnp.where(ids[..., None] >= 0, nbrs, jnp.int32(-1))
            return vecs, nbrs

        _SHARDED_FETCH_CACHE[axis_name] = fetch
    return _SHARDED_FETCH_CACHE[axis_name]


@dataclasses.dataclass(frozen=True)
class ShardedRecordStore:
    """Records sharded row-wise over the ``model`` mesh axis.

    Inside a ``shard_map`` over ``model``, each device holds rows
    [shard_id * rows_per_shard, ...). A fetch broadcasts the id beam
    (replicated over ``model``), every device gathers the rows it owns
    (zeros elsewhere), and one ``psum`` over ``model`` materializes the
    records on all devices.  Collective bytes per fetch =
    B * W * record_size — this is the quantity graph tunneling removes.
    """

    local_vectors: jax.Array  # (N/shards, D) — per-device rows inside shard_map
    local_neighbors: jax.Array  # (N/shards, R)
    rows_per_shard: int
    axis_name: str = "model"

    def fetch_fn(self) -> RecordFetchFn:
        return Partial(
            _sharded_fetch_factory(self.axis_name),
            self.local_vectors,
            self.local_neighbors,
            jnp.int32(self.rows_per_shard),
        )

    @staticmethod
    def shard_arrays(vectors: np.ndarray, neighbors: np.ndarray, n_shards: int):
        """Pad + split host arrays into per-shard rows (for shard_map use)."""
        n = vectors.shape[0]
        rows = -(-n // n_shards)
        pad = rows * n_shards - n
        v = np.pad(vectors, ((0, pad), (0, 0)))
        g = np.pad(neighbors, ((0, pad), (0, 0)), constant_values=-1)
        return v, g, rows


@dataclasses.dataclass(frozen=True)
class HostOffloadRecordStore:
    """Records resident in host memory (``pinned_host``); fetch = host DMA.

    On an accelerator the records must land in ``pinned_host``: a backend
    that refuses it raises.  Only on the CPU backend, where device memory
    already is host memory and the build may offer no ``pinned_host``
    space, do the records stay in plain device memory.
    """

    vectors: jax.Array
    neighbors: jax.Array

    @classmethod
    def create(cls, vectors, neighbors) -> "HostOffloadRecordStore":
        dev = jax.devices()[0]
        if dev.platform == "cpu" and "pinned_host" not in {
            m.kind for m in dev.addressable_memories()
        }:
            return cls(vectors=jnp.asarray(vectors), neighbors=jnp.asarray(neighbors))
        host = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
        return cls(
            vectors=jax.device_put(jnp.asarray(vectors), host),
            neighbors=jax.device_put(jnp.asarray(neighbors), host),
        )

    def fetch_fn(self) -> RecordFetchFn:
        return Partial(_inmem_fetch, self.vectors, self.neighbors)
