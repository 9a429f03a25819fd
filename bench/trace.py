"""From a profiler trace to device busy time, idle gaps and top ops.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
``load`` turns it into plain lists: for each device plane (``/device:TPU:<i>``)
the events of its ``XLA Ops`` line, and for the host plane the events of
every thread line (``jax.profiler.TraceAnnotation`` spans among them),
each as ``[name, start_ns, duration_ns]`` on the trace's one clock.
``reduce`` works on those lists only, so it can be checked on a small
recorded trace (``bench/tests/data``) without a chip.

The ops line nests: a ``while`` op spans every op of its body.  Only
the innermost ops count (an op during which no other op starts), so a
loop that waits on the host inside its body is not counted busy for its
whole length.  An innermost op counts as busy time unless it is a host
transfer (``is_host_transfer=true``: the ``recv-done`` / ``send-done``
of an ``io_callback``), during which the chip only waits for the host.
Idle time is the traced window minus the union of busy intervals.  Each idle
stretch is attributed to what covers it: a host-transfer op the chip is
blocked in (named by the callback's operand shape), else the
benchmark's host span, else nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

HOST_WAIT = "is_host_transfer=true"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def find(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": {line: [[name, start_ns, dur_ns], ...]}}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["devices"][plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"][line.name] = [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]
    return out


def innermost(evs: list) -> list:
    """The events during which no other event of the line starts (the
    ops inside a ``while``, not the ``while``)."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def _union(intervals: list) -> list:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals if b > lo and a < hi]


def op_label(name: str) -> str:
    """``%fusion.232 = f32[393216]{0:T(1024)} fusion(...)`` ->
    ``fusion.232 f32[393216]``: the op and its first result's shape."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?([a-z]+\d*\[[\d,]*\])", rest)
    return head.lstrip("%") + (f" {shape.group(1)}" if shape else "")


def _covering(spans: list, starts: list, t: float):
    """Name of the span of ``spans`` (sorted, not overlapping) that holds
    ``t``; ``starts`` are their start times."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < spans[i][2]:
        return spans[i][0]
    return None


def reduce(trace: dict, *, window_ns: tuple | None = None,
           host_spans: tuple = ("bench.",), top: int = 10) -> dict:
    """Busy and idle time of the traced window, averaged over the device
    planes, with the innermost ops that took most time and idle time by
    what covered it.

    ``window_ns`` (lo, hi) clips every interval; by default the window
    runs from the first to the last event of the trace.
    ``host_spans`` are the name prefixes of host events that may claim
    idle time."""
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    if window_ns is None:
        evs = [e for part in ("devices", "host")
               for evs in trace[part].values() for e in evs]
        window_ns = (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))
    lo, hi = window_ns
    spans = sorted(
        ([e[0], e[1], e[1] + e[2]]
         for evs in trace["host"].values() for e in evs
         if e[0].startswith(host_spans)), key=lambda s: s[1])
    span_starts = [s[1] for s in spans]
    busy_total, op_time, idle_by = 0.0, {}, {}
    for evs in devices.values():
        evs = innermost(evs)
        work = [[e[1], e[1] + e[2]] for e in evs if HOST_WAIT not in e[0]]
        waits = sorted(([op_label(e[0]), e[1], e[1] + e[2]]
                        for e in evs if HOST_WAIT in e[0]), key=lambda s: s[1])
        wait_starts = [w[1] for w in waits]
        busy = _clip(_union(work), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for e in evs:
            a, b = max(e[1], lo), min(e[1] + e[2], hi)
            if b > a:
                key = op_label(e[0])
                op_time[key] = op_time.get(key, 0.0) + (b - a)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            wait = _covering(waits, wait_starts, mid)
            span = _covering(spans, span_starts, mid)
            label = (f"waits on host: {wait}" if wait
                     else f"host in {span}" if span else "no host span")
            idle_by[label] = idle_by.get(label, 0.0) + (b - a)
    n = len(devices)
    window_s = (hi - lo) * 1e-9
    busy_s = busy_total * 1e-9 / n
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "device_ops": sorted(([k, v * 1e-9 / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v * 1e-9 / n] for k, v in idle_by.items()),
                            key=lambda kv: -kv[1])[:top],
    }
