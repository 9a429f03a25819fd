"""Shared ``SearchStats`` aggregation + registry recording.

One home for the summing/ratio arithmetic that ``RAGServer.io_report``
and ``ServeFrontend.io_report`` used to carry as private copies, plus
``record_search_stats`` — the single point where a materialized batch of
per-query stats becomes registry families (the fetched-vs-tunneled
split per mode is the paper's headline ratio, so it gets first-class
counters here rather than being re-derived per report).

Everything here duck-types the stats object (any NamedTuple of ``(B,)``
arrays with ``_fields``) so ``obs`` never imports ``core.search`` — the
dependency points the other way.
"""
from __future__ import annotations

import numpy as np

from repro.obs import registry as regm


def stats_totals(stats) -> dict:
    """Host-materialized integer sums of a per-query stats batch.

    Materializing forces the whole search computation (ordered
    io_callbacks included), so counters read afterwards are complete —
    same discipline as ``DiskRecordStore``'s counter notes.  Returns
    one ``"queries"`` key (the batch size) plus one key per stats field.
    """
    out = {}
    n = 0
    for f in stats._fields:
        arr = np.asarray(getattr(stats, f))
        n = int(arr.shape[0])
        out[f] = int(arr.sum())
    out["queries"] = n
    return out


def hit_rate(ios: int, cache_hits: int) -> float:
    """Cache-tier share of record fetches (0.0 when there were none)."""
    return cache_hits / max(ios + cache_hits, 1)


def tier_mix(*, queries: int, ios: int, cache_hits: int, tunnels: int) -> dict:
    """The lifetime tier-mix report head shared by both serving layers."""
    return {
        "queries": queries,
        "slow_tier_reads": ios,
        "cache_hits": cache_hits,
        "tunnels": tunnels,
        "cache_hit_rate": hit_rate(ios, cache_hits),
    }


def record_search_stats(reg: regm.MetricsRegistry, stats, *,
                        mode: str, tier: str) -> dict:
    """Fold one materialized stats batch into the registry families.

    Counters (labeled ``mode``/``tier``) carry the reconciliation
    contracts — ``search.ios{tier=disk}`` totals must equal the disk
    store's ``disk.records_read`` exactly, and
    ``search.ios + search.cache_hits`` vs ``search.tunnels`` is the
    fetched-vs-tunneled split.  Returns ``stats_totals``.
    """
    t = stats_totals(stats)
    labels = {"mode": mode, "tier": tier}
    reg.counter("search.queries", **labels).inc(t["queries"])
    reg.counter("search.ios", **labels).inc(t["n_ios"])
    reg.counter("search.cache_hits", **labels).inc(t["n_cache_hits"])
    reg.counter("search.tunnels", **labels).inc(t["n_tunnels"])
    reg.counter("search.exact", **labels).inc(t["n_exact"])
    reg.counter("search.hops", **labels).inc(t["n_hops"])
    if "n_degraded" in t:  # duck-typed stats may predate the field
        reg.counter("search.degraded", **labels).inc(t["n_degraded"])
        reg.counter("search.degraded_queries", **labels).inc(
            int((np.asarray(stats.n_degraded) > 0).sum())
        )
    return t
