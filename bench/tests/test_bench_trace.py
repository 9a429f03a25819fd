"""The trace reduction on a hand-made trace and on traces recorded on a
TPU v5e (trimmed to a few tens of milliseconds, ``data/``)."""
import glob
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import trace  # noqa: E402

MS = 1e6  # ns
WAIT = ("%io_callback.7 = (f32[4,2,8]{2,1,0}, token[]) recv-done(%x), "
        "channel_id=1, is_host_transfer=true")


def hand_made(planes=1):
    ops = [["%while.9 = (s32[], f32[8]{0}) while(%t), body=%b", 0 * MS, 100 * MS],
           ["%fusion.1 = f32[8]{0} fusion(%a)", 0 * MS, 10 * MS],
           ["%fusion.2 = s32[4,3]{1,0} fusion(%b)", 10 * MS, 10 * MS],
           [WAIT, 20 * MS, 30 * MS],
           ["%sort.3 = (f32[4,9]{1,0}, s32[4,9]{1,0}) sort(%c)", 50 * MS, 10 * MS],
           ["%fusion.1 = f32[8]{0} fusion(%a)", 90 * MS, 10 * MS]]
    return {"devices": {f"/device:TPU:{i}": ops for i in range(planes)},
            "host": {"dispatcher": [["bench.retrieve", 0.0, 80 * MS]],
                     "other": [["PjitFunction(x)", 60 * MS, 5 * MS]]}}


@pytest.mark.parametrize("planes", [1, 2])
def test_reduce_by_hand(planes):
    red = trace.reduce(hand_made(planes), window_ns=(0.0, 100 * MS))
    # busy: [0, 20) u [50, 60) u [90, 100) = 40 ms; the host wait is
    # idle, and the while around every op is no op of its own
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["idle_share"] == pytest.approx(0.6)
    assert dict(red["device_ops"]) == pytest.approx({
        "io_callback.7 f32[4,2,8]": 0.030, "fusion.1 f32[8]": 0.020,
        "fusion.2 s32[4,3]": 0.010, "sort.3 f32[4,9]": 0.010})
    # [20, 50) the chip waits in the callback; [60, 90) the dispatcher is
    # inside bench.retrieve (its midpoint 75 ms lies before 80 ms)
    assert dict(red["idle_gaps"]) == pytest.approx({
        "waits on host: io_callback.7 f32[4,2,8]": 0.030,
        "host in bench.retrieve": 0.030})


def test_default_window_spans_every_event():
    red = trace.reduce(hand_made())
    assert red["window_s"] == pytest.approx(0.100)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce({"devices": {}, "host": {}})


def test_op_label():
    assert trace.op_label(WAIT) == "io_callback.7 f32[4,2,8]"
    assert trace.op_label("%copy.1 = s32[32,8]{0,1:T(8,128)} copy(%x)") == "copy.1 s32[32,8]"
    assert trace.op_label("while.5") == "while.5"


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data", "trace_*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace_against_a_time_grid(path):
    """busy and idle of a chip trace against a plain count over a 1 us grid."""
    with open(path) as f:
        rec = json.load(f)
    lo, hi = rec["window_ns"]
    red = trace.reduce(rec, window_ns=(lo, hi))
    grid = np.arange(lo, hi, 1000.0) + 500.0  # 1 us cells, by their centres
    for evs in rec["devices"].values():
        starts = np.asarray([e[1] for e in evs])
        busy = np.zeros(grid.size, bool)
        for name, start, dur in evs:
            # an op during which another op starts holds others (a while)
            holds = np.sum((starts >= start) & (starts < start + dur)) > 1
            if trace.HOST_WAIT not in name and not holds:
                busy |= (grid >= start) & (grid < start + dur)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert red["busy_s"] == pytest.approx(busy.mean() * (hi - lo) * 1e-9, abs=2e-5)
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert 0.0 <= red["idle_share"] <= 1.0
