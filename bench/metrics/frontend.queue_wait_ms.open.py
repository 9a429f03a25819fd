"""frontend.queue_wait_ms.open: ``frontend.queue_wait_ms`` in the open-loop
cells, whose end-to-end latency is the median (``p50_ms``): mean time a
request of the window waited in the frontend's queue before a batch
took it (``RequestTrace.queue_wait``)."""


def read(run):
    waits = [r.trace.queue_wait for r in run.requests if r.ok]
    return 1e3 * sum(waits) / len(waits) if waits else None
