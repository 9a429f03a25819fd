"""frontend.batch_rows: mean requests per engine call of the window
(padding rows of a bucket not counted)."""


def read(run):
    return sum(c.rows for c in run.calls) / len(run.calls) if run.calls else None
