"""Jitted public wrappers over the Pallas kernels.

``interpret`` mode is selected automatically (``kernels.backend``):
compiled Pallas on a TPU (Mosaic), Python interpretation — bit-accurate
kernel-body semantics — on every other backend (CPU).  Interpret mode is
an explicit opt-out via the ``interpret=`` kwarg on the underlying
modules, never a silent default on a TPU.
"""
from __future__ import annotations

from repro.kernels import fused_traversal as _ft
from repro.kernels import l2_dist as _l2
from repro.kernels import pq_lookup as _pq
from repro.kernels import topk_merge as _tk
from repro.kernels.backend import supports_compiled_pallas


def _interpret() -> bool:
    """Resolved interpret mode for this process's default backend."""
    return not supports_compiled_pallas()


def pq_lookup_gathered(lut, codes, *, block_m: int = 128):
    return _pq.pq_lookup_gathered(lut, codes, block_m=block_m, interpret=_interpret())


def pq_scan(lut, codes, *, block_n: int = 512):
    return _pq.pq_scan(lut, codes, block_n=block_n, interpret=_interpret())


def l2_dist(queries, rows):
    return _l2.l2_dist(queries, rows, interpret=_interpret())


def topk_merge(dists, ids, k: int):
    return _tk.topk_merge(dists, ids, k, interpret=_interpret())


def fused_traversal_round(*args, mode: str, width: int):
    """One fused stage-A round (see ``kernels.fused_traversal``)."""
    return _ft.fused_traversal_round(
        *args, mode=mode, width=width, interpret=_interpret()
    )
