"""Product Quantization (PQ) — the in-memory approximate-distance substrate.

DiskANN/PipeANN/GateANN all keep PQ-compressed vectors in memory and use
asymmetric distance computation (ADC) to order graph traversal.  GateANN
additionally uses PQ distances to score tunneled neighbors (§3.3).

This module provides:
  * ``train_pq``   — k-means codebooks per chunk (Lloyd iterations in JAX).
  * ``encode_pq``  — nearest-centroid code assignment.
  * ``build_lut``  — per-query lookup tables for ADC.
  * ``adc_lookup`` — LUT-based approximate distances (delegates to the
                     Pallas kernel wrapper in ``repro.kernels.ops`` when
                     enabled, else the pure-jnp reference).

Shapes / conventions
  vectors : (N, D) float32
  codes   : (N, C) uint8/int32   C = n_chunks, D % C == 0
  books   : (C, K, D/C) float32  K = 256 centroids per chunk
  lut     : (B, C, K) float32    per-query chunk-centroid distances
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32 matmul at full precision.  The TPU's default precision rounds
    f32 operands to bf16, and the ||x||^2 - 2 x.c + ||c||^2 expansion of
    every distance below turns that rounding into cancellation errors as
    large as the distances themselves (ADC ordering, hence recall,
    collapses).  On the CPU this is the default anyway."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def argmin_f32(x: jax.Array, axis: int) -> jax.Array:
    """``jnp.argmin`` that compares the f32 values themselves.  The TPU
    compiler lowers a plain argmin, whose minimum is discarded, to a
    reduce over bf16 values, so near-ties (nearest centroids, nearest
    prune candidates) resolve at 8 bits of mantissa.  The first index of
    the f32 minimum is the same answer argmin gives everywhere else."""
    return jnp.argmax(x == jnp.min(x, axis=axis, keepdims=True), axis=axis)


class PQCodec(NamedTuple):
    """Trained PQ codebooks."""

    books: jax.Array  # (C, K, Dc)
    n_chunks: int
    n_centroids: int

    @property
    def dim(self) -> int:
        return self.books.shape[0] * self.books.shape[2]


# rows per block of the nearest-centroid search: the (rows, K) distance
# block of every chunk stays small next to the corpus (32 chunks x 16k
# rows x 256 centroids x 4 B = 512 MiB)
_ASSIGN_ROWS = 16384


def _nearest(sub: jax.Array, book: jax.Array) -> jax.Array:
    """Nearest centroid of each row, by ||x||^2 - 2 x.c + ||c||^2.
    sub (N, Dc), book (K, Dc) -> (N,) int32, in blocks of _ASSIGN_ROWS
    rows (``argmin_f32`` reads its distances twice, so a whole (N, K)
    block per chunk would be materialized)."""
    book_sq = jnp.sum(book * book, axis=1)

    def one(row):
        d = jnp.sum(row * row) - 2.0 * _mm(book, row) + book_sq
        return argmin_f32(d, axis=0).astype(jnp.int32)

    return jax.lax.map(one, sub, batch_size=_ASSIGN_ROWS)


def _kmeans_one_chunk(sub: jax.Array, k: int, iters: int, key: jax.Array) -> jax.Array:
    """Lloyd's k-means for one PQ chunk. sub: (N, Dc) -> (k, Dc)."""
    n = sub.shape[0]
    init_idx = jax.random.choice(key, n, shape=(k,), replace=n < k)
    cents = sub[init_idx]

    def step(cents, _):
        assign = _nearest(sub, cents)
        one_hot = jax.nn.one_hot(assign, k, dtype=sub.dtype)  # (N, k)
        counts = one_hot.sum(axis=0)  # (k,)
        sums = _mm(one_hot.T, sub)  # (k, Dc)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return cents


@functools.partial(jax.jit, static_argnames=("n_chunks", "n_centroids", "iters"))
def train_pq(
    vectors: jax.Array,
    *,
    n_chunks: int = 32,
    n_centroids: int = 256,
    iters: int = 8,
    key: jax.Array | None = None,
) -> PQCodec:
    """Train per-chunk k-means codebooks on (a sample of) the corpus."""
    if key is None:
        key = jax.random.PRNGKey(0)
    n, d = vectors.shape
    assert d % n_chunks == 0, f"dim {d} not divisible by n_chunks {n_chunks}"
    dc = d // n_chunks
    subs = vectors.reshape(n, n_chunks, dc).transpose(1, 0, 2)  # (C, N, Dc)
    keys = jax.random.split(key, n_chunks)
    books = jax.vmap(lambda s, k: _kmeans_one_chunk(s, n_centroids, iters, k))(subs, keys)
    return PQCodec(books=books, n_chunks=n_chunks, n_centroids=n_centroids)


@jax.jit
def encode_pq(codec: PQCodec, vectors: jax.Array) -> jax.Array:
    """Assign each vector chunk to its nearest centroid. -> (N, C) int32."""
    n, d = vectors.shape
    c, k, dc = codec.books.shape
    subs = vectors.reshape(n, c, dc)

    codes = jax.vmap(_nearest, in_axes=(1, 0), out_axes=1)(subs, codec.books)
    return codes  # (N, C)


@jax.jit
def decode_pq(codec: PQCodec, codes: jax.Array) -> jax.Array:
    """Reconstruct approximate vectors from codes. -> (N, D)."""
    c, k, dc = codec.books.shape
    gathered = jax.vmap(lambda book, code: book[code], in_axes=(0, 1), out_axes=1)(
        codec.books, codes
    )  # (N, C, Dc)
    return gathered.reshape(codes.shape[0], c * dc)


@jax.jit
def build_lut(codec: PQCodec, queries: jax.Array) -> jax.Array:
    """Per-query ADC lookup table: lut[b, c, k] = ||q_bc - book_ck||^2.

    queries: (B, D) -> (B, C, K) float32
    """
    b, d = queries.shape
    c, k, dc = codec.books.shape
    q = queries.reshape(b, c, dc)

    def per_chunk(qc, book):  # (B, Dc), (K, Dc)
        return (
            jnp.sum(qc * qc, axis=1, keepdims=True)
            - 2.0 * _mm(qc, book.T)
            + jnp.sum(book * book, axis=1)[None, :]
        )

    return jax.vmap(per_chunk, in_axes=(1, 0), out_axes=1)(q, codec.books)  # (B, C, K)


def adc_lookup(lut: jax.Array, codes: jax.Array, *, use_kernel: bool = False) -> jax.Array:
    """Approximate distances dist[b, n] = sum_c lut[b, c, codes[n, c]].

    lut: (B, C, K), codes: (N, C) -> (B, N) float32.
    ``use_kernel=True`` routes through the Pallas ADC kernel.
    """
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.pq_scan(lut, codes)
    return adc_lookup_ref(lut, codes)


@jax.jit
def adc_lookup_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Pure-jnp ADC reference: one take_along_axis per chunk, summed."""
    # lut (B, C, K); codes (N, C). Gather along K for each (b, c, n).
    # -> per chunk: lut[:, c, :][:, codes[:, c]] summed over c.
    def per_chunk(acc, c):
        acc = acc + jnp.take(lut[:, c, :], codes[:, c], axis=1)  # (B, N)
        return acc, None

    b = lut.shape[0]
    n = codes.shape[0]
    acc = jnp.zeros((b, n), dtype=lut.dtype)
    acc, _ = jax.lax.scan(per_chunk, acc, jnp.arange(lut.shape[1]))
    return acc


def pq_memory_bytes(n: int, n_chunks: int = 32) -> int:
    """Paper Table 2: PQ vectors = N * 32 B at the default 32 chunks."""
    return n * n_chunks


def train_pq_numpy(vectors: np.ndarray, n_chunks: int = 32, n_centroids: int = 256,
                   iters: int = 8, seed: int = 0) -> PQCodec:
    """Convenience host-side wrapper (samples big corpora before training)."""
    rng = np.random.default_rng(seed)
    sample = vectors
    if vectors.shape[0] > 65536:
        idx = rng.choice(vectors.shape[0], 65536, replace=False)
        sample = vectors[idx]
    return train_pq(
        jnp.asarray(sample, dtype=jnp.float32),
        n_chunks=n_chunks,
        n_centroids=n_centroids,
        iters=iters,
        key=jax.random.PRNGKey(seed),
    )
