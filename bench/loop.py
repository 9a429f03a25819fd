"""The load generator: drives ``ServeFrontend.submit`` for a timed window.

The traffic file's ``loop.kind`` names the loop; ``closed`` is the one
loop so far: ``clients`` threads, each with one request in flight, so a
client sends its next request the moment the last one is answered
(callers that each wait for a reply).  Latency runs from submit to
answer.

Requests are sent from ``start`` until ``start + seconds``.  Every
request sent in the window is then waited for (up to ``grace_s``), and
counts: the window's throughput is all its requests over the time from
``start`` to the last answer, and a request that fails or never comes
back counts at an infinite latency.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np


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


def _serve_one(srv, req: Request, tenant: str, vec, timeout: float) -> None:
    try:
        h = srv.submit(tenant, vec, timeout=timeout)
    except Exception as e:  # noqa: BLE001 — a refused request is a result
        req.error = f"refused: {e!r}"
        return
    req.trace = h.trace
    try:
        req.ids = h.result(timeout=timeout)
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        req.error = f"failed: {e!r}"
    req.t_done = time.perf_counter()


def closed(srv, tenants, vecs, *, clients: int, start: float, seconds: float,
           grace_s: float) -> tuple[list[Request], dict]:
    """``tenants[i]``, ``vecs[i]``: request i of the pool, sent in order
    (cyclically) by whichever client is free."""
    end = start + seconds
    lock = threading.Lock()
    out: list[Request] = []
    gaps: list[float] = []  # answer -> next submit, per client (generator lag)
    cursor = [0]

    def client():
        last = None
        while True:
            now = time.perf_counter()
            if now >= end:
                return
            with lock:
                i = cursor[0] % len(vecs)
                cursor[0] += 1
                req = Request(index=i, t_sched=now)
                out.append(req)
            if last is not None:
                gaps.append(now - last)
            req.t_submit = now
            _serve_one(srv, req, tenants[i], vecs[i], seconds + grace_s)
            last = req.t_done
            if not req.ok:
                last = None

    while time.perf_counter() < start:
        time.sleep(min(1e-3, max(0.0, start - time.perf_counter())))
    threads = [threading.Thread(target=client, name=f"client-{c}", daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 2 * grace_s)
    g = np.asarray(gaps) if gaps else np.zeros(1)
    return out, {"loop": "closed", "clients": clients,
                 "answer_to_submit_mean_s": float(g.mean()),
                 "answer_to_submit_max_s": float(g.max()),
                 "threads_left": sum(t.is_alive() for t in threads)}


def run(spec: dict, srv, tenants, vecs, *, start: float, seconds: float,
        grace_s: float) -> tuple[list[Request], dict]:
    """The traffic file's loop (``spec``: its ``loop`` group)."""
    if spec["kind"] != "closed":
        raise ValueError(f"unknown loop kind {spec['kind']!r}")
    return closed(srv, tenants, vecs, clients=int(spec["clients"]), start=start,
                  seconds=seconds, grace_s=grace_s)
