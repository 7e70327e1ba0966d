"""Open-loop load generator: requests are submitted at fixed due times,
whatever the server has finished, and each is timed from its due time to
its answer, so a stall counts against every request behind it.

Taken from the program's `serve.batching.run_open_loop`, with Poisson
arrivals drawn from the seed, failures counted instead of raised, and the
generator's own lateness (submit time minus due time) reported, so that a
starved generator is not read as a fast server.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, NamedTuple

import numpy as np


class OpenLoopResult(NamedTuple):
    due_s: np.ndarray        # (n,) due time after the window start
    sent_s: np.ndarray       # (n,) submit time after the window start
    done_s: np.ndarray       # (n,) answer time after the window start, nan
    answers: np.ndarray      # (n,) int answer, or a value < -1 if failed
    failed: np.ndarray       # (n,) bool: refused, errored or never answered
    backlog_at_close: int    # requests queued when the last one was due
    t_close_s: float         # when the last request was submitted


def poisson_arrivals(rate_hz: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Due times of a Poisson process of `rate_hz` over [0, seconds)."""
    n = int(rate_hz * seconds * 1.2 + 10 * np.sqrt(rate_hz * seconds) + 10)
    t = np.cumsum(rng.exponential(1.0 / rate_hz, size=n))
    while t[-1] < seconds:
        more = t[-1] + np.cumsum(rng.exponential(1.0 / rate_hz, size=n))
        t = np.concatenate([t, more])
    return t[t < seconds]


def run(submit: Callable, queries: np.ndarray, due_s: np.ndarray,
        queue_depth: Callable[[], int], drain_s: float,
        span: Callable[[str], contextlib.AbstractContextManager]
        ) -> OpenLoopResult:
    """Submit `queries[i]` at `due_s[i]` after the start, then wait for
    every answer until `drain_s` after the last due time. `submit` returns
    a `concurrent.futures.Future` or raises; `span(name)` wraps the
    generator's sleeps and submits (a trace annotation, or a no-op). The
    generator keeps no future: each records its answer when it resolves,
    as an independent client would."""
    n = len(due_s)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    answers = np.full(n, -2, np.int64)
    failed = np.zeros(n, bool)
    resolved = threading.Condition()
    count = [0]
    t0 = time.perf_counter()

    def mark(i):
        def cb(fut):
            done[i] = time.perf_counter() - t0
            try:
                answers[i] = int(fut.result())
            except Exception:  # noqa: BLE001 - an errored request counts
                failed[i] = True
            with resolved:
                count[0] += 1
                resolved.notify()
        return cb

    submitted = 0
    for i in range(n):
        wait = t0 + due_s[i] - time.perf_counter()
        if wait > 0:
            with span("bench.sleep"):
                time.sleep(wait)
        with span("bench.submit"):
            sent[i] = time.perf_counter() - t0
            try:
                fut = submit(queries[i])
            except Exception:  # noqa: BLE001 - a refused request is counted
                failed[i] = True
                continue
            submitted += 1
            fut.add_done_callback(mark(i))
    t_close = time.perf_counter() - t0
    backlog = int(queue_depth())
    deadline = t0 + (due_s[-1] if n else 0.0) + drain_s
    with span("bench.drain"), resolved:
        while count[0] < submitted:
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            resolved.wait(left)
    failed |= np.isnan(done)
    return OpenLoopResult(due_s, sent, done, answers, failed, backlog,
                          t_close)


def lateness_ms(res: OpenLoopResult) -> dict:
    """How late the generator submitted, against the due times."""
    late = (res.sent_s - res.due_s) * 1e3
    late = late[np.isfinite(late)]
    if late.size == 0:
        return {}
    return {"p50": float(np.percentile(late, 50)),
            "p99": float(np.percentile(late, 99)),
            "max": float(late.max())}


def stalls(res: OpenLoopResult, top: int = 5, over_ms: float = 8.0
           ) -> list[list[float]]:
    """The generator's longest holds: for request i, the time it was sent
    after it was due and after request i - 1 was sent, i.e. how long the
    generator lost right before it. [[hold ms, due s], ...], longest
    first, holds over `over_ms` only."""
    sent = res.sent_s
    ok = np.isfinite(sent)
    prev = np.concatenate([[-np.inf], sent[:-1]])
    hold = np.where(ok, sent - np.maximum(res.due_s, prev), 0.0) * 1e3
    idx = np.argsort(-hold)[:top]
    return [[float(hold[i]), float(res.due_s[i])] for i in idx
            if hold[i] > over_ms]
