"""frontend.p95_ms.open: ``p95_ms`` in the open-loop cells, where it is
reported per layer and held to no bound: 95th percentile latency of
every request sent in the window (nearest rank), from the time it was
due; a request without an answer counts at +inf.  A host stall of a few
seconds holds up every arrival during it and the backlog it leaves, over
5% of a 50-s window, so this tail swings with the host's stalls."""
import math


def read(run):
    lat = sorted(r.latency for r in run.requests)
    if not lat:
        return None
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]
