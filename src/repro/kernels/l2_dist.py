"""Pallas TPU kernel: exact squared-L2 re-ranking distances.

The fetch path computes exact distances between each query and its W
fetched full-precision records (paper: "Processing (exact dist.)" —
69.5% of PipeANN's per-query time, Table 5).

Tiles: one query per program.  The (W, D) record tile and the (1, D)
query row live in VMEM (W·D·4 B = 32·512·4 = 64 KB at the default
maxima); the difference is squared and summed along lanes into a
(W, 1) column.  The query enters as a ``(B, 1, D)`` array and the result
leaves as ``(B, W, 1)``, so every block's last two dimensions are whole
array dimensions, as the TPU lowering requires.  The lane sum is the
backend's own reduction, so results agree with ``ref.l2_dist_ref`` to
rounding, not bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _l2_kernel(q_ref, x_ref, out_ref):
    """q_ref: (1, 1, D) f32; x_ref: (1, W, D) f32; out_ref: (1, W, 1) f32."""
    diff = x_ref[0] - q_ref[0]  # (W, D) - (1, D)
    out_ref[0] = jnp.sum(diff * diff, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def l2_dist(
    queries: jax.Array,  # (B, D) float32
    rows: jax.Array,  # (B, W, D) float32
    *,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, d = queries.shape
    bb, w, dd = rows.shape
    assert bb == b and dd == d
    return pl.pallas_call(
        _l2_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, w, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, w, 1), jnp.float32),
        interpret=interpret,
    )(queries.astype(jnp.float32)[:, None, :], rows.astype(jnp.float32))[..., 0]
