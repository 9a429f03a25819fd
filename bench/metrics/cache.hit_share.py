"""cache.hit_share: share of the window's record fetches that the record
cache served (``n_cache_hits / (n_cache_hits + n_ios)``).  Nothing to
read where the configuration has no record cache."""


def read(run):
    if not run.config["record_tier"].get("cache_records", 0):
        return None
    hits = sum(float(c.stats["n_cache_hits"].sum()) for c in run.calls)
    ios = sum(float(c.stats["n_ios"].sum()) for c in run.calls)
    return hits / (hits + ios) if hits + ios else None
