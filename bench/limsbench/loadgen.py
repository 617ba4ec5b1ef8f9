"""Closed and open loops against one submit function.

The arithmetic is the program's ``benchmarks/bench_load.py``'s, copied
so that it cannot move with the program: in an open loop a request is
timed from the instant it was due, not from when a worker got round to
sending it, so a stalled server is charged for the requests queued
behind the stall; a request the frontend sheds is a failure, not a fast
answer.  Percentiles are taken over every request of the window, not
over a reservoir.

A closed loop holds ``clients`` threads, each sending its next request
when its last one returns.  The loop starts with the frontend paused
until every client has queued its first request, so the first batch is
as full as every later one.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import numpy as np


class Overload(Exception):
    """Raised by a submit function for a request the server shed."""


@dataclass
class Record:
    req: object
    due: float                   # perf_counter instant it was due
    released: float = 0.0        # when a worker handed it to the server
    done: float = 0.0            # when the answer (or refusal) came back
    result: object = None
    shed: bool = False
    error: BaseException | None = None


def _send(submit, rec: Record) -> None:
    rec.released = time.perf_counter()
    try:
        rec.result = submit(rec.req)
    except Overload:
        rec.shed = True
    except Exception as e:          # the server's fault: never answered
        rec.error = e
    rec.done = time.perf_counter()


def closed_loop(submit, reqs: list, clients: int, seconds: float,
                pause=None, resume=None, queued=None) -> tuple[float, list]:
    """Run ``clients`` closed-loop clients for ``seconds``.  Client ``c``
    sends ``reqs[c], reqs[c + clients], ...`` (cycling).  Returns the
    window's start instant and every request sent in it.

    ``pause``/``resume``/``queued`` hold the server until all clients
    have queued their first request (``queued()`` counts requests the
    server has accepted)."""
    records: list[list] = [[] for _ in range(clients)]
    start = threading.Event()
    t0 = [0.0]

    def client(c: int) -> None:
        j = c
        start.wait()
        while True:
            if records[c] and time.perf_counter() >= t0[0] + seconds:
                return
            rec = Record(reqs[j % len(reqs)], due=time.perf_counter())
            records[c].append(rec)
            _send(submit, rec)
            j += clients

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    if pause is not None:
        pause()
        before = queued()
    t0[0] = time.perf_counter() + 3600.0     # no client stops early
    start.set()
    if pause is not None:
        while queued() < before + clients:
            time.sleep(0.0005)
    t0[0] = time.perf_counter()
    if resume is not None:
        resume()
    for t in threads:
        t.join()
    return t0[0], [r for rs in records for r in rs]


def open_loop(submit, reqs: list, due: np.ndarray, workers: int,
              grace: float = 60.0) -> tuple[float, list]:
    """Send ``reqs[i]`` at ``t0 + due[i]`` from a pool of ``workers``
    threads; wait up to ``grace`` seconds past the last due instant for
    the answers.  Returns ``t0`` and one record per request; a request
    no worker could send on time is sent late and charged for it."""
    t0 = time.perf_counter() + 0.05
    records = [Record(r, due=t0 + float(d)) for r, d in zip(reqs, due)]
    nxt = [0]
    lock = threading.Lock()
    deadline = t0 + float(due[-1]) + grace

    def worker() -> None:
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= len(records) or time.perf_counter() > deadline:
                return
            rec = records[i]
            wait = rec.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            _send(submit, rec)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()) + 1.0)
    return t0, records


def latencies_ms(records: list) -> np.ndarray:
    """Due-to-answer milliseconds of every answered request."""
    return np.array([(r.done - r.due) * 1e3 for r in records
                     if r.result is not None])


def late_ms(records: list) -> np.ndarray:
    """How late each request left the generator, in milliseconds."""
    return np.array([(r.released - r.due) * 1e3 for r in records
                     if r.released])
