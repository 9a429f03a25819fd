"""search.reads_per_query: records fetched from the record tier per query
of the window (``SearchStats.n_ios``; cache hits not counted)."""


def read(run):
    rows = sum(c.rows for c in run.calls)
    if not rows:
        return None
    return sum(float(c.stats["n_ios"].sum()) for c in run.calls) / rows
