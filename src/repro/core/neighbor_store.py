"""Neighbor store — in-memory adjacency for graph tunneling (§3.2).

Replicates the first ``R_max`` neighbors of each node from the on-disk
graph into a contiguous fixed-stride array.  Built at load time from the
unmodified index: ``build_vamana`` writes each row longest edge first,
so a prefix keeps the long routes that carry a tunneled walk toward the
query rather than the closest neighbors, which crowd together.
``R_max`` is a *runtime* knob — no index rebuild is ever required to
change it (§3.4).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@functools.partial(
    jax.tree_util.register_dataclass, data_fields=("neighbors",), meta_fields=()
)
@dataclasses.dataclass(frozen=True)
class NeighborStore:
    neighbors: jax.Array  # (N, R_max) int32, -1 padded

    @classmethod
    def from_graph(cls, full_neighbors: jax.Array, r_max: int) -> "NeighborStore":
        """Extract the first r_max columns (the longest edges)."""
        r = full_neighbors.shape[1]
        return cls(neighbors=full_neighbors[:, : min(r_max, r)])

    @property
    def r_max(self) -> int:
        return int(self.neighbors.shape[1])

    def lookup(self, ids: jax.Array) -> jax.Array:
        """(B, K) ids -> (B, K, R_max) neighbor ids; invalid ids -> -1 rows."""
        got = self.neighbors[jnp.maximum(ids, 0)]
        return jnp.where(ids[..., None] >= 0, got, jnp.int32(-1))

    def memory_bytes(self) -> int:
        """Paper Eq. (1): N * (1 + R_max) * 4 B (the +1 models the length
        word of the on-disk record header)."""
        n = int(self.neighbors.shape[0])
        return n * (1 + self.r_max) * 4
