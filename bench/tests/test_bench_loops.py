"""The arrival loops against a stub server that answers one request at a
time, slowly: the open loop sends on the seed's schedule whatever is in
flight, and its latencies show the stub's queue; the closed loop keeps
one request per client in flight."""
import os
import queue
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO]

from bench import data, spec  # noqa: E402
from bench import loop as loopm  # noqa: E402

SEED = 2**36 + 77


class _Handle:
    def __init__(self, i: int):
        self.i, self.trace, self.ids = i, None, None
        self.done = threading.Event()

    def result(self, timeout=None):
        if not self.done.wait(timeout):
            raise TimeoutError("not served")
        return self.ids


class Stub:
    """Answers request ``i`` with ``[i]``, one at a time, ``service_s``
    after it starts on it, first come first served."""

    def __init__(self, service_s: float):
        self.service_s = service_s
        self.q: queue.Queue = queue.Queue()
        self.inflight = self.most_inflight = 0
        self.lock = threading.Lock()
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def send(self, i: int) -> _Handle:
        h = _Handle(i)
        with self.lock:
            self.inflight += 1
            self.most_inflight = max(self.most_inflight, self.inflight)
        self.q.put(h)
        return h

    def queue_depth(self) -> int:
        return self.q.qsize()

    def _serve(self):
        while (h := self.q.get()) is not None:
            time.sleep(self.service_s)
            h.ids = np.array([h.i])
            with self.lock:
                self.inflight -= 1
            h.done.set()

    def close(self):
        self.q.put(None)
        self.thread.join(timeout=10)
        assert not self.thread.is_alive()


def _run(kind_spec, stub, seconds, seed=SEED, n=1000):
    start = time.perf_counter() + 0.02
    reqs, report = loopm.run(kind_spec, stub.send, n, start=start, seconds=seconds,
                             grace_s=5.0, rng=data.streams(seed)["arrivals"],
                             queue_depth=stub.queue_depth)
    return start, reqs, report


def test_open_arrivals_follow_the_seeds_schedule():
    open_loop = spec.plugin(spec.ROOT, "loops", "open", "loop kind")
    offsets = []
    for seed in (SEED, SEED, SEED + 1):
        stub = Stub(0.001)
        try:
            start, reqs, report = _run({"kind": "open", "rate_qps": 50.0}, stub, 0.6, seed)
        finally:
            stub.close()
        want = open_loop.schedule(50.0, 0.6, data.streams(seed)["arrivals"])
        got = np.array([r.t_sched for r in reqs]) - start
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-9)
        assert len(reqs) == report["sent"] == 30
        assert all(r.ok and r.ids.tolist() == [r.index] for r in reqs)
        assert [r.index for r in reqs] == list(range(30))
        assert report["threads_left"] == 0
        offsets.append(got)
    np.testing.assert_array_equal(offsets[0], offsets[1])
    assert not np.allclose(offsets[0], offsets[2])
    assert np.all(np.diff(offsets[0]) >= 0) and 0 < offsets[0][0] and offsets[0][-1] < 0.6


@pytest.mark.parametrize("seed", [SEED, 2**33 + 9])
def test_open_schedule_is_exponential_gaps_filling_the_window(seed):
    open_loop = spec.plugin(spec.ROOT, "loops", "open", "loop kind")
    due = open_loop.schedule(17.5, 50.0, data.streams(seed)["arrivals"])
    assert due.size == 875 and 0 < due[0] and due[-1] < 50.0
    gaps = np.diff(due)
    # exponential gaps: mean 1/rate, standard deviation as large as the mean
    assert np.mean(gaps) == pytest.approx(1 / 17.5, rel=0.1)
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.15)


def test_open_sender_never_waits_and_latency_shows_the_queue():
    open_loop = spec.plugin(spec.ROOT, "loops", "open", "loop kind")
    open_loop.DEPTH_AT_S = 0.3
    stub = Stub(0.05)  # 20 answers a second, offered 40
    try:
        start = time.perf_counter() + 0.02
        reqs, report = open_loop.run({"kind": "open", "rate_qps": 40.0}, stub.send, 1000,
                                     start=start, seconds=1.0, grace_s=5.0,
                                     rng=data.streams(SEED)["arrivals"],
                                     queue_depth=stub.queue_depth)
    finally:
        stub.close()
    assert len(reqs) == 40 and all(r.ok for r in reqs)
    # every request went out on time though most were still unanswered
    assert report["lag_max_s"] < 0.3 and stub.most_inflight >= 10
    lat = np.array([r.latency for r in reqs])
    assert lat.min() >= 0.05
    # 40 answers take 2 s of service, offered over 1 s: the last ones wait
    assert lat.max() > 0.6 and lat[-5:].mean() > 4 * lat[:3].mean()
    assert report["queue_depth_at_close"] > report["queue_depth_at_10s"] >= 0


def test_open_window_shorter_than_the_depth_probe_reports_none():
    stub = Stub(0.001)
    try:
        _, reqs, report = _run({"kind": "open", "rate_qps": 20.0}, stub, 0.5)
    finally:
        stub.close()
    assert report["queue_depth_at_10s"] is None
    assert report["queue_depth_at_close"] == 0 and len(reqs) == 10


def test_closed_loop_keeps_one_request_per_client_in_flight():
    stub = Stub(0.01)
    try:
        _, reqs, report = _run({"kind": "closed", "clients": 3}, stub, 0.5)
    finally:
        stub.close()
    assert report["clients"] == 3 and report["threads_left"] == 0
    assert stub.most_inflight == 3
    assert all(r.ok for r in reqs) and len(reqs) >= 10
    assert sorted(r.index for r in reqs) == list(range(len(reqs)))
