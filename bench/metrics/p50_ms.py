"""p50_ms: median latency of every request sent in the window (nearest
rank); a request without an answer counts at +inf."""
import math


def read(run):
    lat = sorted(r.latency for r in run.requests)
    if not lat:
        return None
    return 1e3 * lat[math.ceil(0.50 * len(lat)) - 1]
