"""Open-loop load: a seeded arrival schedule and the loop that sends it.

Requests are due at Poisson arrival times ordered by the seed, and each
request's images are picked from the pool beforehand, so the sending
loop does no work but wait and submit.  Latency is measured from the
time a request was *due*, not from when the loop got round to sending
it, so a stall delays every request behind it in the numbers too; how
late the loop ran is reported beside them.  A request the system
refuses or never answers has infinite latency.

The loop keeps only the handles of the requests it is told to keep
(the checked sample) and records the rest as a time and an outcome:
a user drops a verdict once it has it, and a benchmark that held every
answer would grow the process's heap until Python's full garbage
collections, which scan all of it, stall every thread for 100-200 ms.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, Collection, Dict

import numpy as np


@dataclasses.dataclass
class Schedule:
    due_s: np.ndarray        # (n,) seconds after the window opens
    pool_index: np.ndarray   # (n, images_per_request) pool rows


def poisson_schedule(rng: np.random.Generator, *, rate: float,
                     seconds: float, pool: int,
                     images_per_request: int = 1) -> Schedule:
    """``round(rate * seconds)`` arrivals with exponential gaps that
    fill ``seconds`` exactly.  The gaps themselves are the same for every
    seed (drawn from a fixed generator and scaled to the window); ``rng``
    orders them and picks each request's pool rows, so runs on different
    seeds send the same amount of work in the same time, in another
    order."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(0).exponential(1.0 / rate, n)
    due = np.cumsum(rng.permutation(gaps * (seconds / gaps.sum())))
    idx = rng.integers(0, pool, (n, images_per_request))
    return Schedule(due_s=due, pool_index=idx)


@dataclasses.dataclass
class Sent:
    """What happened to each request of a schedule."""
    due_s: np.ndarray                 # (n,)
    late_s: np.ndarray                # (n,) send time minus due time
    refused: np.ndarray               # (n,) the system declined it
    done_s: np.ndarray                # (n,) resolve time, nan if never
    ok: np.ndarray                    # (n,) settled with a result
    kept: Dict[int, object]           # handles of the kept requests
    t0: float                         # perf_counter at window open


def _has_result(handle) -> bool:
    try:
        handle.result(timeout=0)
        return True
    except Exception:      # settled with an error
        return False


class _Clock:
    """Resolve times and outcomes, stamped by the handles'
    done-callbacks."""

    def __init__(self, n: int, t0: float):
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.t0 = t0
        self.lock = threading.Lock()

    def stamp(self, k: int):
        def cb(handle):
            t = time.perf_counter() - self.t0
            ok = _has_result(handle)
            with self.lock:     # ok first: a resolve time means settled
                self.ok[k] = ok
                self.done[k] = t
        return cb


def requests(schedule: Schedule, pool: np.ndarray) -> list:
    """Each request's images, (images_per_request, H, W, 3); a
    one-image request is a view of the pool, not a copy."""
    return [pool[i[0]: i[0] + 1] if len(i) == 1 else pool[i]
            for i in schedule.pool_index]


def send(schedule: Schedule, reqs: list,
         submit: Callable[[int, np.ndarray], object],
         refused: type, *, span: Callable[[str], object],
         keep: Collection[int] = ()) -> Sent:
    """Send request ``k`` (images ``reqs[k]``) at its due time through
    ``submit(k, images)``, which returns a handle with
    ``add_done_callback`` and ``result(timeout)``; ``refused`` is the
    exception the system raises when it declines a request.  The handles
    of the requests in ``keep`` are kept.  ``span(name)`` opens a host
    trace span around each sleep and each submit."""
    n = len(schedule.due_s)
    kept: Dict[int, object] = {}
    declined = np.zeros(n, bool)
    late = np.zeros(n)
    t0 = time.perf_counter()
    clock = _Clock(n, t0)
    for k in range(n):
        wait = schedule.due_s[k] - (time.perf_counter() - t0)
        if wait > 0:
            with span("bench.sleep"):
                time.sleep(wait)
        now = time.perf_counter() - t0
        late[k] = now - schedule.due_s[k]
        with span("bench.submit"):
            try:
                h = submit(k, reqs[k])
            except refused:
                declined[k] = True
                continue
        if k in keep:
            kept[k] = h
        h.add_done_callback(clock.stamp(k))
    return Sent(due_s=schedule.due_s, late_s=late, refused=declined,
                done_s=clock.done, ok=clock.ok, kept=kept, t0=t0)


def answered(sent: Sent) -> np.ndarray:
    """(n,) bool: the request settled with a result."""
    return ~np.isnan(sent.done_s) & sent.ok


def latencies_ms(sent: Sent) -> np.ndarray:
    """Per-request latency from due time, inf where the request was
    refused, never resolved, or resolved with an error."""
    return np.where(answered(sent), (sent.done_s - sent.due_s) * 1e3,
                    np.inf)


def quantile(values: np.ndarray, q: float) -> float:
    """Nearest-rank quantile; inf where it falls on a missing answer."""
    v = np.sort(np.asarray(values, float))
    if not len(v):
        return float("nan")
    return float(v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))])
