"""open: requests arrive on a schedule at ``rate_qps``, whether or not
earlier ones have been answered (independent users).

The schedule is a Poisson process conditioned on its count: the window
holds ``round(rate_qps * seconds)`` arrivals, and the gaps between them
are exponential draws of the run's ``arrivals`` stream, scaled together
so that they fill the window.  Every seed so sends the same number of
requests, in other bursts.  The pool's requests go out in order
(cyclically).

One sender thread sends each request at its due time and never waits
for an answer; one collector thread waits for the answers in the order
they were sent (the frontend batches first come, first served) and
stamps each as it comes.  Latency runs from the due time, so a sender
that falls behind counts against the system.  The report gives the
sender's lag (submit - due, mean and max) and the frontend's queue
depth 10 s into the window and at its close (None for a shorter
window): a depth that grows between the two is a load above capacity.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from bench.loop import Request, sleep_until, submit, wait

DEPTH_AT_S = 10.0


def schedule(rate_qps: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times of the window's arrivals, in seconds from its opening."""
    count = max(1, round(float(rate_qps) * seconds))
    gaps = rng.exponential(1.0, size=count + 1)
    return np.cumsum(gaps)[:count] * (seconds / gaps.sum())


def run(spec: dict, send, n: int, *, start: float, seconds: float, grace_s: float,
        rng: np.random.Generator, queue_depth) -> tuple[list[Request], dict]:
    due = start + schedule(spec["rate_qps"], seconds, rng)
    out = [Request(index=k % n, t_sched=float(t)) for k, t in enumerate(due)]
    sent: queue.Queue = queue.Queue()
    depth = {}

    def sender():
        for req in out:
            sleep_until(req.t_sched)
            if "at_10s" not in depth and req.t_sched >= start + DEPTH_AT_S:
                depth["at_10s"] = queue_depth()
            sent.put((req, submit(send, req)))
        sent.put(None)
        sleep_until(start + seconds)
        depth["at_close"] = queue_depth()

    def collector():
        while (item := sent.get()) is not None:
            req, h = item
            if h is not None:
                wait(h, req, max(0.0, start + seconds + grace_s - time.perf_counter()))

    threads = [threading.Thread(target=sender, name="open-sender", daemon=True),
               threading.Thread(target=collector, name="open-collector", daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 2 * grace_s)
    lag = np.asarray([r.t_submit - r.t_sched for r in out])
    lag = lag[np.isfinite(lag)] if np.isfinite(lag).any() else np.zeros(1)
    return out, {"loop": "open", "rate_qps": float(spec["rate_qps"]), "sent": len(out),
                 "lag_mean_s": float(lag.mean()), "lag_max_s": float(lag.max()),
                 "queue_depth_at_10s": depth.get("at_10s"),
                 "queue_depth_at_close": depth.get("at_close"),
                 "threads_left": sum(t.is_alive() for t in threads)}
