"""Span tracer: monotonic-clock stage timing with per-thread ring buffers.

``with trace.span("disk.preadv", store=...):`` times one stage of the
I/O path on ``time.perf_counter()`` (monotonic, high-resolution — wall
clock steps can never corrupt a duration) and publishes it two ways:

  * a per-thread **ring buffer** of the most recent spans — the raw
    material for "what did the last few requests actually do", exported
    by ``obs.export`` and rendered by ``scripts/obs_report.py``.  Rings
    are per-thread so the disk store's reader-pool threads, the serving
    dispatcher, and the client threads never contend on a shared list.
  * a ``trace.span_seconds{span=...}`` **histogram family** in the bound
    registry, so span percentiles ride the same export path as every
    other metric (span labels beyond the name stay in the ring only —
    histogram families need fixed, bounded label sets; ``name`` itself
    is reserved for the registry API).

An enabled span also enters a ``jax.profiler.TraceAnnotation`` of the
same name, so under a running ``jax.profiler`` trace it lands on the
host plane, on the thread that ran it and on the timeline of the device
ops: a device op that waits on a host callback can be laid beside what
the host was doing meanwhile (``bench/stages.py`` does, after finding
how far the host's events sit from the device's on that timeline).

Overhead budget (documented, and pinned by the tier-1 overhead guard):

  * **disabled** (the default): ``span()`` is one attribute read, one
    branch, and a shared no-op context manager — near-zero, safe to
    leave in the hottest host callback.
  * **enabled**: two ``perf_counter`` calls, a ring append, one
    histogram observe and a profiler annotation per recorded span, a
    few us on commodity CPUs (the annotation adds under 1us while no
    profiler trace runs) —
    <2% of even a page-cache-served 4 KB ``preadv`` round, which is the
    cheapest stage we time.  The ``sample_rate`` knob (1-in-N per
    thread, deterministic) cuts it further for high-frequency spans.

Pre-measured durations (e.g. the serving dispatcher computes queue-wait
arithmetic itself) enter through ``trace.record(name, dur_s, ...)`` —
same ring, same histogram family, no double clocking; such a span is
not annotated in a profiler trace, which cannot be backdated.
"""
from __future__ import annotations

import threading
import time

from repro.obs import registry as regm

RING_SIZE = 512  # spans kept per thread


class _NopSpan:
    """Shared do-nothing context manager — the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOP = _NopSpan()
_Annotation = None  # jax.profiler.TraceAnnotation, imported by the first span


def _annotation_cls():
    global _Annotation
    if _Annotation is None:
        from jax.profiler import TraceAnnotation

        _Annotation = TraceAnnotation
    return _Annotation


class _Ring:
    """Fixed-capacity overwrite-oldest span buffer (single-writer)."""

    __slots__ = ("buf", "cap", "i", "seen")

    def __init__(self, cap: int):
        self.buf: list = []
        self.cap = cap
        self.i = 0
        self.seen = 0  # spans offered, sampled out or not

    def push(self, item) -> None:
        if len(self.buf) < self.cap:
            self.buf.append(item)
        else:
            self.buf[self.i % self.cap] = item
        self.i += 1

    def items(self) -> list:
        if len(self.buf) < self.cap:
            return list(self.buf)
        k = self.i % self.cap
        return self.buf[k:] + self.buf[:k]


class _Span:
    __slots__ = ("_tracer", "name", "labels", "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, labels: dict):
        self._tracer = tracer
        self.name = name
        self.labels = labels
        self._ann = _annotation_cls()(name)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        self._tracer._commit(self.name, self.labels, self.t0, dur)
        return False


class Tracer:
    """One span sink: per-thread rings + a span-seconds histogram family.

    The process-default tracer (module-level ``span``/``record``/...)
    binds to whatever the process-default registry currently is; a
    serving front end creates its own ``Tracer(registry=...)`` so its
    request spans land in its own registry regardless of global state.
    """

    def __init__(self, registry: regm.MetricsRegistry | None = None,
                 ring_size: int = RING_SIZE):
        self.enabled = False
        self.sample_every = 1
        self._registry = registry
        self._ring_size = ring_size
        self._rings: dict[str, _Ring] = {}
        self._rings_lock = threading.Lock()
        self._tls = threading.local()

    def enable(self, sample_rate: float = 1.0) -> None:
        """Start recording; ``sample_rate`` keeps 1-in-round(1/rate)
        spans per thread (deterministic, counter-based — no RNG in the
        hot path).  Histogram percentiles are over the sampled spans."""
        if not 0.0 < sample_rate <= 1.0:
            raise ValueError(f"sample_rate must be in (0, 1], got {sample_rate}")
        self.sample_every = max(1, int(round(1.0 / sample_rate)))
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def _reg(self) -> regm.MetricsRegistry:
        return self._registry if self._registry is not None \
            else regm.default_registry()

    def span(self, name: str, **labels):
        if not self.enabled:
            return _NOP
        return _Span(self, name, labels)

    def record(self, name: str, duration_s: float, **labels) -> None:
        """Publish an externally measured duration as a span: ring and
        histogram only, since a profiler annotation cannot be backdated."""
        if not self.enabled:
            return
        self._commit(name, labels, time.perf_counter() - duration_s,
                     duration_s)

    def _commit(self, name: str, labels: dict, t0: float, dur: float) -> None:
        tls = self._tls
        ring = getattr(tls, "ring", None)
        if ring is None:
            # a host callback's thread gets fresh thread-local state on
            # every call, so the ring is found again by thread, not kept
            t = threading.current_thread()
            with self._rings_lock:
                ring = self._rings.setdefault(f"{t.name}-{t.ident}",
                                              _Ring(self._ring_size))
            tls.ring = ring
        n = ring.seen
        ring.seen = n + 1
        if n % self.sample_every:
            return
        ring.push((name, labels, t0, dur))
        self._reg().histogram("trace.span_seconds", span=name).observe(dur)

    def snapshot(self) -> dict:
        """``{thread: [span dicts, oldest first]}`` across all threads."""
        with self._rings_lock:
            rings = list(self._rings.items())
        return {
            tname: [
                {"name": n, "labels": dict(l), "start": t0, "dur_s": d}
                for (n, l, t0, d) in ring.items()
            ]
            for tname, ring in rings
        }

    def reset(self) -> None:
        with self._rings_lock:
            self._rings.clear()
        self._tls = threading.local()


_tracer = Tracer()


def default_tracer() -> Tracer:
    return _tracer


def span(name: str, **labels):
    return _tracer.span(name, **labels)


def record(name: str, duration_s: float, **labels) -> None:
    _tracer.record(name, duration_s, **labels)


def enable(sample_rate: float = 1.0) -> None:
    _tracer.enable(sample_rate)


def disable() -> None:
    _tracer.disable()


def snapshot() -> dict:
    return _tracer.snapshot()
