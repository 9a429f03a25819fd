"""p95_ms: 95th percentile latency of every request sent in the window
(nearest rank); a request without an answer counts at +inf."""
import math


def read(run):
    lat = sorted(r.latency for r in run.requests)
    if not lat:
        return None
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]
