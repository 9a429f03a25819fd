"""closed: ``clients`` threads, each with one request in flight, so a
client sends its next request the moment the last one is answered
(callers that each wait for a reply).  The pool's requests go out in
order (cyclically), each to whichever client is free; a request is due
when its client sends it."""
from __future__ import annotations

import threading
import time

import numpy as np

from bench.loop import Request, sleep_until, submit, wait


def run(spec: dict, send, n: int, *, start: float, seconds: float, grace_s: float,
        rng, queue_depth) -> tuple[list[Request], dict]:
    clients = int(spec["clients"])
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
                i = cursor[0] % n
                cursor[0] += 1
                req = Request(index=i, t_sched=now)
                out.append(req)
            if last is not None:
                gaps.append(now - last)
            h = submit(send, req)
            if h is None:
                last = None
                continue
            wait(h, req, seconds + grace_s)
            last = req.t_done if req.ok else None

    sleep_until(start)
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
