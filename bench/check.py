"""Whether the timed path's answers are correct.

Once the window has closed, a sample of its answered requests, drawn
from the run's seed, is searched again by the configuration's reference
plan (``bench/reference/plans/``; by default the plain Algorithm-1
oracle, ``bench/reference/oracle.py``) over the sections of the saved
index that the plan names, read by the benchmark's own reader
(``bench/reference/index_file.py``), and the generated corpus; and by
exact filtered brute force over the corpus alone.  The numbers
compared, each against the configuration's limit (``limits``):

  mismatch           share of the reference's top-K ids missing from the
                     served top-K, over the sample
  order_errors       adjacent pairs of a served top-K whose exact
                     distances to the query, worked out in float64 from
                     the generated corpus alone, fall by more than
                     float32 rounding (``ORDER_RTOL``): a check of the
                     exact rerank and of the records it read that takes
                     nothing the program made
  filter_violations  served ids that fail the request's predicate
  unanswered         requests of the window that got no answer (refused,
                     failed, or not back within the grace period)

``recall_miss``, 1 - recall@K of the served ids against exact filtered
brute force, is worked out and printed too.  A configuration compares it
only where the control separates it from the program (PERF.md).

Which records pass a request's predicate is the (N,) bool mask that the
filter schema's ``passes(attrs, predicate)`` gives (``bench/filters/``),
for the reference, brute force and ``filter_violations`` alike.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("mismatch", "order_errors", "recall_miss", "filter_violations",
           "unanswered")
ORDER_RTOL = 1e-5  # float32 rounding of a distance, with room


def brute_force(vectors: np.ndarray, queries: np.ndarray, masks, k: int,
                block: int = 64) -> np.ndarray:
    """Exact top-k among the records that pass each query's predicate
    (``masks[i]``), in float64."""
    v = vectors.astype(np.float64)
    v_sq = np.sum(v * v, axis=1)
    out = np.full((len(queries), k), -1, np.int64)
    for s in range(0, len(queries), block):
        q = queries[s:s + block].astype(np.float64)
        d = np.sum(q * q, axis=1)[:, None] - 2.0 * q @ v.T + v_sq[None, :]
        d = np.where(np.stack(masks[s:s + block]), d, np.inf)
        top = np.argsort(d, axis=1, kind="stable")[:, :k]
        ok = np.isfinite(np.take_along_axis(d, top, axis=1))
        out[s:s + block] = np.where(ok, top, -1)
    return out


def _misses(expected: np.ndarray, got: np.ndarray) -> tuple[int, int]:
    """(ids of ``expected`` absent from ``got``, ids of ``expected``)."""
    want = set(int(i) for i in expected if i >= 0)
    have = set(int(i) for i in got if i >= 0)
    return len(want - have), len(want)


def numbers(served: list, reference: list, truth: np.ndarray,
            vectors: np.ndarray, queries: np.ndarray, masks, k: int) -> dict:
    """The compared numbers of ``served[i]`` against the reference's
    ``reference[i]`` and brute force's ``truth[i]`` (request i: query
    ``queries[i]`` over the records that ``masks[i]`` passes;
    ``vectors``: the corpus)."""
    miss = total = gm = g_total = violations = disorder = 0
    for got, ref, want, q, mask in zip(served, reference, truth, queries, masks):
        got = np.asarray(got)[:k]
        m, t = _misses(ref, got)
        miss, total = miss + m, total + t
        m, t = _misses(want, got)
        gm, g_total = gm + m, g_total + t
        ids = got[got >= 0]
        violations += int(np.sum(~mask[ids]))
        diff = vectors[ids].astype(np.float64) - q.astype(np.float64)
        d = np.sum(diff * diff, axis=1)
        disorder += int(np.sum(d[:-1] - d[1:] > ORDER_RTOL * d[:-1]))
    return {"mismatch": miss / max(total, 1),
            "order_errors": disorder,
            "recall_miss": gm / max(g_total, 1),
            "filter_violations": violations}


def verdict(numbers_: dict, limits: dict) -> bool:
    """Every number the configuration limits is within its limit."""
    return all(numbers_[k] <= limits[k] for k in limits)
