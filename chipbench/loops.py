"""The one traffic generator: drives ``Session.submit(...).result()
.to_numpy()`` — the path users call — as a traffic file says.

A traffic file (``chipbench/traffic/<mix>.json``) holds:

* ``query``: the name of a module under ``chipbench/queries``;
* ``loop``: ``"closed"`` (one client; each query is submitted when the
  previous answer is on the host) or ``"open"`` (Poisson arrivals at
  ``rate_per_s``, each request timed from when it was due);
* ``warmup``: queries run before the window, counted as set-up.

Open-loop arrivals are the same for every seed up to a local reordering:
``round(rate * seconds)`` exponential quantiles, scaled to fill the window,
in one fixed order (a Poisson sample path), whose gaps the seed shuffles
within blocks of ``BLOCK``.  So every run offers the same work with the
same bursts, and the seed moves each arrival by a few gaps at most.
"""
from __future__ import annotations

import contextlib
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

NOW = time.perf_counter
STRAGGLER_S = 60.0      # how long past the window's close an answer may come
BLOCK = 4               # the seed reorders arrival gaps within such blocks
# Open loop: reader threads, four times the queries a Session admits at
# once (8 by default), so each request in flight has a reader of its own.
READERS = 32


@dataclass
class Request:
    i: int
    due: float              # seconds after the window opened
    sent: float = 0.0
    done: float | None = None
    answer: dict | None = None
    record: Any = None      # the session's QueryRecord
    error: str | None = None

    @property
    def latency(self) -> float:
        return self.done - self.due


def no_span(name: str):
    return contextlib.nullcontext()


def run_one(sess, make: Callable, r: Request, t0: float, span=no_span) -> None:
    """Submit one query, wait for its answer and read it to the host."""
    try:
        with span("submit"):
            fut = sess.submit(make())
        finish(fut, r, t0, None, span)
    except Exception as e:          # a failed query counts, it does not stop
        r.error = f"{type(e).__name__}: {e}"


def finish(fut, r: Request, t0: float, timeout, span=no_span) -> None:
    try:
        with span("result"):
            t = fut.result(timeout=timeout)
        with span("to_numpy"):
            r.answer = t.to_numpy()
        r.done = NOW() - t0
        r.record = t.query_record
    except Exception as e:
        r.error = f"{type(e).__name__}: {e}"


def warm(sess, make: Callable, n: int) -> list[Request]:
    out = []
    for i in range(n):
        r = Request(i, 0.0)
        run_one(sess, make, r, NOW())
        out.append(r)
    return out


def closed(sess, make: Callable, seconds: float,
           span=no_span) -> list[Request]:
    """One client for ``seconds``.  The query in flight at the close runs
    to its end and is returned too."""
    reqs: list[Request] = []
    t0 = NOW()
    while (s := NOW() - t0) < seconds:
        r = Request(len(reqs), s, s)
        run_one(sess, make, r, t0, span)
        reqs.append(r)
    return reqs


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s after the window opens) of an open loop."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng(0).permutation(gaps * seconds / gaps.sum())
    rng = np.random.default_rng(int(seed) % 2**63)
    gaps = gaps[np.argsort(np.arange(n) // BLOCK + 0.5 * rng.random(n))]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def open_loop(sess, make: Callable, dues: np.ndarray, seconds: float,
              span=no_span) -> list[Request]:
    """Submit at ``dues`` from this thread.  Each request has a reader
    thread of its own that waits for its answer and reads it to the host,
    so no request waits for the readback of another, as independent
    clients would not.  Returns once every answer has come or
    ``STRAGGLER_S`` past the close has run out."""
    reqs: list[Request] = []
    readers = ThreadPoolExecutor(READERS, thread_name_prefix="bench-reader")

    def read(r: Request, fut) -> None:
        left = max(t0 + seconds + STRAGGLER_S - NOW(), 0.0)
        finish(fut, r, t0, left, span)

    t0 = NOW()
    try:
        for i, d in enumerate(dues):
            wait = t0 + d - NOW()
            if wait > 0:
                time.sleep(wait)
            r = Request(i, float(d), NOW() - t0)
            reqs.append(r)
            try:
                with span("submit"):
                    fut = sess.submit(make())
            except Exception as e:
                r.error = f"{type(e).__name__}: {e}"
                continue
            readers.submit(read, r, fut)
    finally:
        readers.shutdown(wait=True)
    return reqs
