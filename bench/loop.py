"""The load generator: drives the frontend for a timed window.

The traffic file's ``loop.kind`` names the loop, a file of its own,
``bench/loops/<kind>.py``, whose ``run(spec, send, n, *, start, seconds,
grace_s, rng, queue_depth)`` returns ``(requests, report)``:

  * ``spec``: the traffic file's ``loop`` group;
  * ``send(i)``: submits request ``i`` of the pool (``0 <= i < n``) to
    the frontend and returns its handle, whose ``result(timeout)`` gives
    the answer and whose ``trace`` is the frontend's ``RequestTrace``;
  * ``rng``: the run's ``arrivals`` stream; ``queue_depth()``: the
    requests waiting in the frontend's queue;
  * ``requests``: a ``Request`` for every request sent in the window;
    ``report``: what the loop tells about itself (how late it sent).

Requests are sent from ``start`` until ``start + seconds``.  Every
request sent in the window is then waited for (up to ``grace_s``), and
counts: the window's throughput is all its requests over the time from
``start`` to the last answer, and a request that fails or never comes
back counts at an infinite latency.  Latency runs from the time a
request was due (``Request.t_sched``).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import spec as specm


@dataclasses.dataclass
class Request:
    index: int  # into the traffic's request pool
    t_sched: float  # perf_counter seconds it was due
    t_submit: float = float("nan")
    t_done: float = float("nan")
    ids: np.ndarray | None = None
    trace: object = None  # the frontend's RequestTrace
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.ids is not None

    @property
    def latency(self) -> float:
        return self.t_done - self.t_sched if self.ok else float("inf")


def submit(send, req: Request):
    """Send ``req``; its handle, or None where the frontend refused it."""
    req.t_submit = time.perf_counter()
    try:
        h = send(req.index)
    except Exception as e:  # noqa: BLE001 — a refused request is a result
        req.error = f"refused: {e!r}"
        return None
    req.trace = h.trace
    return h


def wait(h, req: Request, timeout: float) -> None:
    """Wait for ``req``'s answer; stamp when it came."""
    try:
        req.ids = h.result(timeout=timeout)
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        req.error = f"failed: {e!r}"
    req.t_done = time.perf_counter()


def sleep_until(t: float) -> None:
    while (left := t - time.perf_counter()) > 0:
        time.sleep(min(left, 1e-3) if left < 2e-3 else left - 1e-3)


def run(spec: dict, send, n: int, *, start: float, seconds: float, grace_s: float,
        rng: np.random.Generator, queue_depth, root: str = specm.ROOT
        ) -> tuple[list[Request], dict]:
    """The traffic file's loop (``spec``: its ``loop`` group)."""
    loop = specm.plugin(root, "loops", spec["kind"], "loop kind")
    return loop.run(spec, send, n, start=start, seconds=seconds, grace_s=grace_s,
                    rng=rng, queue_depth=queue_depth)
