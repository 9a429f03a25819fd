"""device.search_roofline: the least time of the search work done in the
traced window at the chip's peaks (``bench/roofline.py``: counted from
``SearchStats``, so the same whatever implementation ran), as a
percentage of the chip's busy time in that window."""
from bench import roofline


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    w = run.trace["work"]
    if w["ops"] <= 0:
        return None
    t, _bound = roofline.least_time(w["ops"], w["bytes"], run.device_kind)
    return 100.0 * t / run.trace["busy_s"]
