"""Published chip peaks and the least work of a filtered search.

``PEAKS`` is copied from ``benchmarks/roofline.py``: one row per
``jax.Device.device_kind``, each with its source.  A kind missing here
is an error, never a default.

``search_work`` counts what Algorithm 1 must touch for the searches a
set of ``SearchStats`` describes, at the configuration's widths, from
the counts alone, so that no implementation of the loop (the XLA op
chain, the fused kernel, the Pallas ADC path) changes its own yardstick:

  * every candidate neighbour the expanded nodes hand to the frontier —
    the full row of ``degree`` for a fetched node, the ``r_max`` prefix
    for a tunneled one — has its PQ code read (one byte per chunk), one
    LUT entry per chunk read (4 B) and summed (one add per chunk);
  * every exact rerank reads one record vector (``dim`` float32) and
    takes its squared distance to the query (3 operations per element).
"""
from __future__ import annotations

#   "TPU v5 lite" = TPU v5e.  Source: Google Cloud documentation, "TPU v5e":
#   197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip
#   (4 links -> 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of this kind.  Raises ValueError for a kind with
    no published row."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"with its source to bench/roofline.py (known: {sorted(PEAKS)})"
        ) from None


def search_work(stats: dict, *, dim: int, pq_chunks: int, degree: int,
                r_max: int) -> tuple[float, float]:
    """(operations, bytes) of the searches whose summed ``SearchStats``
    fields are ``stats`` (n_ios, n_cache_hits, n_tunnels, n_exact)."""
    fetched = stats["n_ios"] + stats["n_cache_hits"]
    candidates = fetched * degree + stats["n_tunnels"] * r_max
    ops = candidates * pq_chunks + stats["n_exact"] * 3 * dim
    nbytes = candidates * pq_chunks * (1 + 4) + stats["n_exact"] * dim * 4
    return float(ops), float(nbytes)


def least_time(ops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """(seconds, bound): the larger of the compute and memory times at the
    chip's peaks, and which of the two it is."""
    p = peaks(device_kind)
    t_ops, t_bytes = ops / p["flops"], nbytes / p["hbm_bw"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "operations")
