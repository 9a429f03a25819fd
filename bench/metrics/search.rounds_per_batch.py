"""search.rounds_per_batch: mean rounds of the search loop per engine
call of the window.  A batch runs until its slowest query ends, and
every row counts every round (``SearchStats.n_hops``), so a call's
rounds are its rows' largest ``n_hops``."""


def read(run):
    if not run.calls:
        return None
    return sum(float(c.stats["n_hops"].max()) for c in run.calls) / len(run.calls)
