"""device.idle_share: share of the traced window in which no operation
ran on the chip (waits on a host callback count as idle); from the
profiler trace (``bench/trace.py``)."""


def read(run):
    return None if run.trace is None else run.trace["idle_share"]
