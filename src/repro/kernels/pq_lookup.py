"""Pallas TPU kernel: PQ asymmetric-distance computation (ADC).

This is GateANN's hottest in-memory loop — tunneling spends ~49% of
per-query time in "PQ + AdjIndex" (paper Table 5).  The CPU reference
implementation is a per-chunk table gather; on TPU the gather is
re-expressed as a **one-hot select-and-sum** over VMEM-resident tiles, so
the inner loop runs on the VPU with no scalar gathers:

    dist[m] = Σ_c lut[c, codes[m, c]]
            = Σ_c Σ_k [codes[m, c] == k] · lut[c, k]

The inner sum over K has exactly one non-zero term, so it is exact; the
outer sum over chunks is the fixed pairwise tree of ``ref.pairwise_sum``.
The kernel is therefore bitwise equal to the jnp reference (a
``take_along_axis`` gather followed by the same tree) on every backend.

Two entry points share the kernel body:

  * ``pq_lookup_gathered`` — per-query code rows (B, M, C), used by the
    search loop on gathered neighbor ids.
  * ``pq_scan``            — shared code matrix (N, C) scanned by every
    query (brute-force ADC / re-ranking sweeps).

Layout: the wrappers hand the kernel chunk-major codes ``(C, Mt)`` and a
transposed table ``(K, C)``, so candidates lie along lanes and the
result of a program is one ``(1, Mt)`` lane row.  Every block's last two
dimensions are either whole array dimensions or multiples of (8, 128),
as the TPU lowering requires; outputs are ``(B, 1, M)`` arrays for the
same reason.  Rows padded up to the block size are forced to **+INF
inside the kernel**; ``keep_padding`` returns the full padded array so
tests can pin the sentinel lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

# numpy scalar, not jnp: the kernel bodies reference it, and a traced jnp
# scalar would be captured as a pallas_call constant (a trace error)
_INF = np.float32(3.4e38)


def tree_sum(parts):
    """Sum a list of equal-shape arrays as the pairwise tree of
    ``ref.pairwise_sum``: (0+1), (2+3), ..., odd tail carried to the end."""
    while len(parts) > 1:
        nxt = [parts[i] + parts[i + 1] for i in range(0, len(parts) - 1, 2)]
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def adc_row(lut_t, codes_t):
    """(K, C) transposed lut × (C, Mt) chunk-major codes -> (1, Mt) ADC row.

    Per chunk, a (K, Mt) one-hot mask selects the table column and a
    sublane sum extracts it exactly (one non-zero per lane); the chunk
    partials are then summed by ``tree_sum``.
    """
    k = lut_t.shape[0]
    c, mt = codes_t.shape
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k, mt), 0)
    parts = []
    for ci in range(c):
        onehot = codes_t[ci : ci + 1, :] == iota_k  # (K, Mt)
        col = lut_t[:, ci : ci + 1]  # (K, 1)
        parts.append(
            jnp.sum(jnp.where(onehot, col, jnp.float32(0)), axis=0, keepdims=True)
        )
    return tree_sum(parts)


def _real_rows(block: int, rows: int, grid_axis: int):
    """(1, block) mask of genuine (non-padding) rows within this tile."""
    row0 = pl.program_id(grid_axis) * block
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    return row0 + lane < rows


def _adc_kernel(lut_ref, codes_ref, out_ref, *, block_m: int, m: int):
    """One (query b, row-tile m) program.

    lut_ref:   (1, K, C) f32 VMEM — transposed per-query table
    codes_ref: (1, C, Mt) int32 VMEM — chunk-major codes
    out_ref:   (1, 1, Mt) f32 VMEM — padded rows (>= m) emit +INF
    """
    d = adc_row(lut_ref[0], codes_ref[0])
    out_ref[0] = jnp.where(_real_rows(block_m, m, 1), d, _INF)


@functools.partial(
    jax.jit, static_argnames=("block_m", "interpret", "keep_padding")
)
def pq_lookup_gathered(
    lut: jax.Array,  # (B, C, K) float32
    codes: jax.Array,  # (B, M, C) int32
    *,
    block_m: int = 128,
    interpret: bool | None = None,
    keep_padding: bool = False,
) -> jax.Array:
    """Per-query gathered ADC: out[b, m] = sum_c lut[b, c, codes[b, m, c]]."""
    interpret = resolve_interpret(interpret)
    b, c, k = lut.shape
    bb, m, cc = codes.shape
    assert bb == b and cc == c, (lut.shape, codes.shape)
    block_m = min(block_m, m)
    pad_m = (-m) % block_m
    codes_t = codes.astype(jnp.int32).transpose(0, 2, 1)  # (B, C, M)
    if pad_m:
        codes_t = jnp.pad(codes_t, ((0, 0), (0, 0), (0, pad_m)))
    mp = m + pad_m
    out = pl.pallas_call(
        functools.partial(_adc_kernel, block_m=block_m, m=m),
        grid=(b, mp // block_m),
        in_specs=[
            pl.BlockSpec((1, k, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, c, block_m), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_m), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, mp), jnp.float32),
        interpret=interpret,
    )(lut.astype(jnp.float32).transpose(0, 2, 1), codes_t)[:, 0]
    return out if keep_padding else out[:, :m]


def _adc_scan_kernel(lut_ref, codes_ref, out_ref, *, block_n: int, n: int):
    """One (query b, node-tile n) program over a shared code matrix.

    lut_ref: (1, K, C) f32; codes_ref: (C, Nt) int32; out_ref: (1, 1, Nt) f32
    """
    d = adc_row(lut_ref[0], codes_ref[...])
    out_ref[0] = jnp.where(_real_rows(block_n, n, 1), d, _INF)


@functools.partial(
    jax.jit, static_argnames=("block_n", "interpret", "keep_padding")
)
def pq_scan(
    lut: jax.Array,  # (B, C, K) float32
    codes: jax.Array,  # (N, C) int32 — shared across queries
    *,
    block_n: int = 512,
    interpret: bool | None = None,
    keep_padding: bool = False,
) -> jax.Array:
    """Brute-force ADC sweep: out[b, n] = sum_c lut[b, c, codes[n, c]]."""
    interpret = resolve_interpret(interpret)
    b, c, k = lut.shape
    n, cc = codes.shape
    assert cc == c
    block_n = min(block_n, n)
    pad_n = (-n) % block_n
    codes_t = codes.astype(jnp.int32).T  # (C, N)
    if pad_n:
        codes_t = jnp.pad(codes_t, ((0, 0), (0, pad_n)))
    np_ = n + pad_n
    out = pl.pallas_call(
        functools.partial(_adc_scan_kernel, block_n=block_n, n=n),
        grid=(b, np_ // block_n),
        in_specs=[
            pl.BlockSpec((1, k, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((c, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, np_), jnp.float32),
        interpret=interpret,
    )(lut.astype(jnp.float32).transpose(0, 2, 1), codes_t)[:, 0]
    return out if keep_padding else out[:, :n]
