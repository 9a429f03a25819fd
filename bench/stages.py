"""Device time by the search loop's stages, and the chip's waits on the
host by what the host was doing meanwhile.

``bench/trace.py`` names a device op by its HLO instruction, whose
number changes whenever the program does.  The search loop runs its
pieces under stable ``jax.named_scope`` names (``core/search.py``:
``select``, ``adc``, ``visited``, ``merge``, ``fetch``, ``rerank``, and
``fused_round``), which the compiler keeps as each op's ``op_name``.  On
a TPU the profiler writes that name stack as the ``tf_op`` stat of the
op's event metadata (``jit(filtered_search)/while/body/adc/gather:``),
which ``jax.profiler.ProfileData`` does not expose; ``load`` therefore
reads the ``.xplane.pb`` (an ``XSpace`` protobuf) itself, with message
types declared here for the few fields it needs.

``load`` gives what ``bench/trace.py``'s ``load`` gives, with each
device op as ``[name, start_ns, dur_ns, name_stack]``.  ``reduce`` works
on those lists only, so it can be checked on recorded traces
(``bench/tests/data``) without a chip:

  * ``device_scopes``: busy time of the innermost ops (as in
    ``bench/trace.py``) by the first stage name in each op's name
    stack; ops under none go to ``unscoped``;
  * ``host_waits``: the time the chip sat in a host-transfer op, split
    exactly (by interval intersection) into time covered by a program
    span (``bench.``, ``disk.``, ``engine.``), under the innermost open
    one; else by a runtime event of the host, under the innermost one;
    else ``nothing on the host``.  Innermost is the latest to open, on
    any thread.  A span or event open over the whole of a wait op is
    the caller blocked on the device (``bench.retrieve``,
    ``engine.search``, ``np.asarray``), not what the chip waits for,
    and claims none of that op.

``callback_transit_s`` is the chip's host-transfer wait during which no
callback span of the disk tier was open on any thread: the time the
crossing itself took.

**The two clocks.**  The profiler puts host events and device ops on one
timeline, but on a TPU v5e the two are not aligned: a ``disk.drain``
span can end after the device op that waited for its result.
``host_offset_ns`` measures the shift from the callbacks' own operand
transfers, which both sides record, and both attributions move the
host's events back by it; with no such transfer the shift is 0.
"""
from __future__ import annotations

import bisect
import functools

from bench import trace as tracem

STAGES = ("select", "adc", "visited", "merge", "fetch", "rerank", "fused_round")
HOST_SPANS = ("bench.", "disk.", "engine.")
CALLBACK_SPANS = ("disk.submit", "disk.drain", "disk.fetch")
D2H = "tpu::System::TransferFromDevice"  # the host's receipt of a device send
NOTHING = "nothing on the host"
UNSCOPED = "unscoped"
SCOPE_STAT = "tf_op"


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The ``XSpace`` message (``tsl/profiler/protobuf/xplane.proto``),
    cut to the fields ``load`` reads; the rest are skipped unparsed."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, s, m = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, F.TYPE_MESSAGE
    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    messages = {
        "XStat": [("metadata_id", 1, i64, one), ("str_value", 5, s, one),
                  ("ref_value", 7, u64, one)],
        "XEvent": [("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
                   ("duration_ps", 3, i64, one)],
        "XLine": [("name", 2, s, one), ("timestamp_ns", 3, i64, one),
                  ("events", 4, "XEvent", rep), ("display_name", 11, s, one)],
        "XEventMetadata": [("id", 1, i64, one), ("name", 2, s, one),
                           ("stats", 5, "XStat", rep)],
        "XStatMetadata": [("id", 1, i64, one), ("name", 2, s, one)],
        "EventMetadataEntry": [("key", 1, i64, one),
                               ("value", 2, "XEventMetadata", one)],
        "StatMetadataEntry": [("key", 1, i64, one),
                              ("value", 2, "XStatMetadata", one)],
        "XPlane": [("name", 2, s, one), ("lines", 3, "XLine", rep),
                   ("event_metadata", 4, "EventMetadataEntry", rep),
                   ("stat_metadata", 5, "StatMetadataEntry", rep)],
        "XSpace": [("planes", 1, "XPlane", rep)],
    }
    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                           package="bench_xplane", syntax="proto3")
    for name, fields in messages.items():
        msg = f.message_type.add(name=name)
        for fname, number, kind, label in fields:
            fd = msg.field.add(name=fname, number=number, label=label)
            if isinstance(kind, str):
                fd.type, fd.type_name = m, f".bench_xplane.{kind}"
            else:
                fd.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _events(line, meta: dict) -> list:
    t0 = float(line.timestamp_ns)
    # whole nanoseconds, as jax.profiler.ProfileData gives them
    return [[meta[e.metadata_id][0], t0 + e.offset_ps // 1000, float(e.duration_ps // 1000),
             meta[e.metadata_id][1]] for e in line.events]


def load(path: str) -> dict:
    """``bench/trace.py``'s ``load`` with each device op's name stack (its
    ``tf_op`` stat, "" where it has none) as a fourth element."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {"devices": {}, "host": {}}
    for plane in space.planes:
        device = tracem.DEVICE_PLANE.match(plane.name)
        if not device and plane.name != "/host:CPU":
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            scope = ""
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    scope = st.str_value or stat_names.get(st.ref_value, "")
            meta[e.key] = (e.value.name, scope)
        for line in plane.lines:
            name = line.display_name or line.name
            if device and name == tracem.OPS_LINE:
                out["devices"][plane.name] = _events(line, meta)
            elif not device:
                out["host"][name] = [e[:3] for e in _events(line, meta)]
    return out


def stage(name_stack: str, stages: tuple = STAGES) -> str:
    """The first stage name among the components of a name stack."""
    for part in name_stack.split("/"):
        if part in stages:
            return part
    return UNSCOPED


def _window(trace: dict) -> tuple:
    evs = [e for part in ("devices", "host") for evs in trace[part].values() for e in evs]
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def _waits(evs: list, lo: float, hi: float) -> list:
    """[start, end] of the innermost host-transfer ops, clipped."""
    return [[max(e[1], lo), min(e[1] + e[2], hi)] for e in tracem.innermost(evs)
            if tracem.HOST_WAIT in e[0] and e[1] + e[2] > lo and e[1] < hi]


def _split(wait: list, spans: list, events: list) -> dict:
    """Seconds of one wait [a, b] by the innermost span open, else the
    innermost runtime event, else nothing; spans and events are
    ``[name, start, end]`` that overlap the wait without covering it."""
    a, b = wait
    cuts = sorted({a, b} | {t for s in spans + events for t in s[1:] if a < t < b})
    out: dict = {}
    for lo, hi in zip(cuts, cuts[1:]):
        mid = 0.5 * (lo + hi)
        label = NOTHING
        for group in (spans, events):
            open_ = [s for s in group if s[1] <= mid < s[2]]
            if open_:
                label = max(open_, key=lambda s: s[1])[0]
                break
        out[label] = out.get(label, 0.0) + (hi - lo)
    return out


def _overlapping(items: list, starts: list, a: float, b: float, longest: float) -> list:
    """Items ``[name, start, end]`` (sorted by start) that overlap [a, b]
    and do not cover it."""
    i = bisect.bisect_left(starts, a - longest)
    j = bisect.bisect_left(starts, b)
    return [s for s in items[i:j] if s[2] > a and not (s[1] <= a and s[2] >= b)]


def host_offset_ns(trace: dict, *, reach_ns: float = 1e8, bin_ns: float = 1e5) -> float:
    """How far the host's events sit after the device's on the trace's
    timeline, in ns (negative: before).

    Each operand a host callback takes leaves the chip in a ``send-done``
    op and reaches the host as one runtime event ``D2H``, in program order
    and a near-constant latency apart.  Every pair of the two within
    ``reach_ns`` votes for its difference, in bins of ``bin_ns``; the true
    pairs agree on one bin, while a pairing shifted by some rounds agrees
    only as far as those rounds happen to last alike.  The offset is the
    median difference of the pairs in the winning bin and its neighbours;
    0 where the trace holds no such pair."""
    sends = sorted(e[1] + e[2] for evs in trace["devices"].values()
                   for e in tracem.innermost(evs)
                   if tracem.HOST_WAIT in e[0] and "send-done" in e[0])
    recvs = [e[1] for evs in trace["host"].values() for e in evs if e[0] == D2H]
    diffs = []
    for h in recvs:
        i, j = bisect.bisect_left(sends, h - reach_ns), bisect.bisect_right(sends, h + reach_ns)
        diffs.extend(h - d for d in sends[i:j])
    if not diffs:
        return 0.0
    votes: dict = {}
    for d in diffs:
        k = int(d // bin_ns)
        votes[k] = votes.get(k, 0) + 1
    best = max(votes, key=lambda k: (votes.get(k - 1, 0) + votes[k] + votes.get(k + 1, 0), -abs(k)))
    near = sorted(d for d in diffs if best - 1 <= d // bin_ns <= best + 1)
    return float(near[len(near) // 2])


def _host(trace: dict, lo: float, hi: float, shift: float) -> list:
    """Host events as ``[name, start, end]`` moved back by ``shift``,
    those in [lo, hi], by start."""
    return sorted(([e[0], e[1] - shift, e[1] + e[2] - shift]
                   for evs in trace["host"].values() for e in evs
                   if e[1] - shift < hi and e[1] + e[2] - shift > lo),
                  key=lambda s: s[1])


def reduce(trace: dict, *, window_ns: tuple | None = None,
           host_spans: tuple = HOST_SPANS, stages: tuple = STAGES,
           offset_ns: float | None = None) -> dict:
    """``device_scopes`` and ``host_waits`` of the traced window (seconds,
    averaged over the device planes, largest first), and the
    ``host_offset_ns`` used (``offset_ns``, else estimated)."""
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    lo, hi = window_ns if window_ns is not None else _window(trace)
    shift = host_offset_ns(trace) if offset_ns is None else offset_ns
    host = _host(trace, lo, hi, shift)
    spans = [s for s in host if s[0].startswith(host_spans)]
    events = [s for s in host if not s[0].startswith(host_spans)]
    index = [(g, [s[1] for s in g], max((s[2] - s[1] for s in g), default=0.0))
             for g in (spans, events)]
    scopes: dict = {}
    waits: dict = {}
    for evs in devices.values():
        for e in tracem.innermost(evs):
            a, b = max(e[1], lo), min(e[1] + e[2], hi)
            if b > a and tracem.HOST_WAIT not in e[0]:
                key = stage(e[3] if len(e) > 3 else "", stages)
                scopes[key] = scopes.get(key, 0.0) + (b - a)
        for w in _waits(evs, lo, hi):
            found = [_overlapping(g, st, w[0], w[1], longest) for g, st, longest in index]
            for k, v in _split(w, *found).items():
                waits[k] = waits.get(k, 0.0) + v
    n = len(devices)
    return {
        "device_scopes": sorted(([k, v * 1e-9 / n] for k, v in scopes.items()),
                                key=lambda kv: -kv[1]),
        "host_waits": sorted(([k, v * 1e-9 / n] for k, v in waits.items()),
                             key=lambda kv: -kv[1]),
        "host_offset_ns": shift,
    }


def callback_transit_s(trace: dict, *, window_ns: tuple | None = None,
                       spans: tuple = CALLBACK_SPANS,
                       offset_ns: float | None = None) -> float:
    """Seconds the chip sat in a host-transfer op while no span named in
    ``spans`` was open on any thread (averaged over the device planes),
    with the host's events moved back by ``offset_ns`` (else estimated)."""
    lo, hi = window_ns if window_ns is not None else _window(trace)
    shift = host_offset_ns(trace) if offset_ns is None else offset_ns
    open_ = tracem._union([[s[1], s[2]] for s in _host(trace, lo, hi, shift)
                           if s[0] in spans])
    total = 0.0
    for evs in trace["devices"].values():
        for a, b in _waits(evs, lo, hi):
            total += (b - a) - sum(max(0.0, min(b, y) - max(a, x)) for x, y in open_
                                   if x < b and y > a)
    return total * 1e-9 / len(trace["devices"])


def traced_rounds(calls: list, t0: float, t1: float) -> float:
    """Rounds of the search loop in [t0, t1]: over engine calls, the share
    of each call's time in the interval times its largest ``n_hops``
    (a batch runs until its slowest row ends)."""
    total = 0.0
    for c in calls:
        share = max(0.0, min(c.t1, t1) - max(c.t0, t0)) / max(c.t1 - c.t0, 1e-12)
        total += share * float(c.stats["n_hops"].max())
    return total
