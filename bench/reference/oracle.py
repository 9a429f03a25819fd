"""Algorithm 1 of GateANN, one query at a time, in NumPy.

A copy of the oracle in ``tests/test_search_oracle.py``, made to stand
alone: it takes its PQ and exact distances from nothing but the stored
index (PQ codebooks and codes, the adjacency rows, the entry point) and
the corpus the benchmark generated, and imports nothing of the program.

The loop keeps a frontier of at most ``L`` candidates ordered by PQ
distance (ties by insertion order), expands the ``W`` best unexpanded
ones per round, and by the mode's masks either fetches a node (its
record joins the results, ranked by exact distance, and its full
adjacency row feeds the frontier) or tunnels it (only the first
``r_max`` entries of its row feed the frontier, from memory).  A
candidate enters the frontier once: the first time a round offers it.
The search stops when no candidate is left unexpanded or after
``max_hops`` rounds.

``precision="f32"`` computes every distance in float64 and ranks by it,
so its answers are those of float32 arithmetic up to rounding.
``precision="bf16"`` is the control: the operands of every distance are
rounded to bfloat16 and every distance is rounded to bfloat16 (products
summed in float32), as a TPU computes at its default matmul precision.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

PRECISIONS = ("f32", "bf16")


def _bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class Index:
    vectors: np.ndarray  # (N, D) float32 corpus records
    books: np.ndarray  # (C, Kc, D/C) PQ codebooks
    codes: np.ndarray  # (N, C) PQ code per chunk
    neighbors: np.ndarray  # (N, R) adjacency rows, -1 padded
    entry: int  # the medoid
    r_max: int  # in-memory prefix of each row used for tunneling


def _lut(index: Index, q: np.ndarray, precision: str) -> np.ndarray:
    """(C, Kc) squared distances from each query chunk to each centroid."""
    c, _, dc = index.books.shape
    qc = q.reshape(c, 1, dc)
    if precision == "f32":
        diff = qc.astype(np.float64) - index.books.astype(np.float64)
        return np.sum(diff * diff, axis=-1)
    diff = _bf16(qc) - _bf16(index.books)
    return _bf16(np.sum(diff * diff, axis=-1, dtype=np.float32))


def _pq_dist(index: Index, lut: np.ndarray, ids: np.ndarray, precision: str):
    got = lut[np.arange(lut.shape[0])[None, :], index.codes[ids]]  # (M, C)
    if precision == "f32":
        return got.sum(axis=1)
    return _bf16(got.sum(axis=1, dtype=np.float32))


def _exact_dist(index: Index, q: np.ndarray, ids: np.ndarray, precision: str):
    if precision == "f32":
        diff = index.vectors[ids].astype(np.float64) - q.astype(np.float64)
        return np.sum(diff * diff, axis=1)
    diff = _bf16(index.vectors[ids]) - _bf16(q)
    return _bf16(np.sum(diff * diff, axis=1, dtype=np.float32))


def _masks(mode: str, sel: np.ndarray, passes: np.ndarray, entry: int):
    """(fetch, tunnel, result) of each selected node, as in the loop."""
    no = np.zeros_like(passes)
    yes = np.ones_like(passes)
    if mode == "gate":
        return passes, ~passes, passes
    if mode == "post" or mode == "early":
        return yes, no, passes
    if mode == "pre_naive":
        return passes | (sel == entry), no, passes
    if mode == "unfiltered":
        return yes, no, yes
    raise ValueError(f"unknown mode {mode!r}")


def search(index: Index, q: np.ndarray, passes_all: np.ndarray, *, mode: str,
           L: int, W: int, K: int, max_hops: int = 512,
           precision: str = "f32") -> tuple[np.ndarray, np.ndarray]:
    """Top-``K`` ids (-1 padded) and their distances for one query whose
    predicate the records of the (N,) bool mask ``passes_all`` pass (the
    filter schema's ``passes``; ``mode="unfiltered"`` ignores it)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    lut = _lut(index, q, precision)
    n = index.vectors.shape[0]
    entry = int(index.entry)
    visited = np.zeros(n, bool)
    visited[entry] = True
    # the frontier, kept sorted by (PQ distance, insertion order)
    f_ids = np.array([entry], np.int64)
    f_d = _pq_dist(index, lut, f_ids, precision)
    f_seq = np.zeros(1, np.int64)
    f_exp = np.zeros(1, bool)
    seq = 1
    res_ids, res_d = [], []
    rounds = 0
    while rounds < max_hops and not f_exp.all():
        rounds += 1
        slots = np.flatnonzero(~f_exp)[:W]
        f_exp[slots] = True
        sel = f_ids[slots]
        passes = passes_all[sel] if mode != "unfiltered" else np.ones(sel.size, bool)
        fetch, tunnel, result = _masks(mode, sel, passes, entry)
        if result.any():
            res_ids.append(sel[result])
            res_d.append(_exact_dist(index, q, sel[result], precision))
        cand = index.neighbors[sel[fetch]].ravel()
        if mode == "gate":
            cand = np.concatenate(
                [cand, index.neighbors[sel[tunnel], :index.r_max].ravel()])
        cand = cand[cand >= 0]
        _, first = np.unique(cand, return_index=True)
        cand = cand[np.sort(first)]  # first offer in this round's order
        cand = cand[~visited[cand]]
        visited[cand] = True
        if cand.size:
            ids = np.concatenate([f_ids, cand])
            d = np.concatenate([f_d, _pq_dist(index, lut, cand, precision)])
            sq = np.concatenate([f_seq, seq + np.arange(cand.size)])
            ex = np.concatenate([f_exp, np.zeros(cand.size, bool)])
            seq += cand.size
            keep = np.lexsort((sq, d))[:L]
            f_ids, f_d, f_seq, f_exp = ids[keep], d[keep], sq[keep], ex[keep]
    out_ids = np.full(K, -1, np.int64)
    out_d = np.full(K, np.inf)
    if res_ids:
        ids = np.concatenate(res_ids)
        d = np.concatenate(res_d)
        top = np.argsort(d, kind="stable")[:K]
        out_ids[:top.size], out_d[:top.size] = ids[top], d[top]
    return out_ids, out_d
