"""Open-loop request traffic: a Poisson tape and the driver that replays it.

The tape fixes the work of a run: ``n`` inter-arrival gaps at the
quantiles of an exponential distribution and ``n`` request sizes at the
quantiles of a log-uniform one, each set in an order shuffled once by
``ORDER_SEED``.  So every seed offers the same sizes and arrivals in the
same order, and the seed picks which users are asked for.  (With the
order drawn from the seed as well, the p95 of 20-second windows at 100
requests/s on one v5e ranged from 86 to 129 ms over six seeds: the order
of bursts, not the system, set the tail.)

The driver submits each request when it is due, whatever the system is
doing, and times it from its due time to the moment its future resolves:
a stall makes every request due behind it late, and that wait counts.
How late the generator itself ran (submit time minus due time) is
returned apart, so a starved generator is not read as a fast server.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Tape:
    due: np.ndarray        # seconds after the start, non-decreasing
    users: list            # one int32 array of user ids per request


ORDER_SEED = 20171121


def make_tape(n: int, rate: float, size_lo: int, size_hi: int,
              num_users: int, seed: int) -> Tape:
    order = np.random.default_rng(ORDER_SEED)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    sizes = np.exp(math.log(size_lo)
                   + (math.log(size_hi) - math.log(size_lo)) * q)
    sizes = np.clip(np.round(sizes), size_lo, size_hi).astype(int)
    order.shuffle(gaps)
    order.shuffle(sizes)
    rng = np.random.default_rng(seed)
    users = [rng.integers(0, num_users, s).astype(np.int32) for s in sizes]
    return Tape(np.cumsum(gaps), users)


@dataclasses.dataclass
class Replay:
    latency: np.ndarray    # seconds from due to resolution; inf if failed
    lag: np.ndarray        # seconds the submit ran behind its due time
    answers: list          # the future's result, or None if it failed
    failed: int
    start: float           # clock reading of due time 0
    end: float             # clock reading when the last request resolved


def replay(submit, tape: Tape, *, clock=time.perf_counter,
           sleep=time.sleep, span=None, lead: float = 0.05,
           grace: float = 60.0) -> Replay:
    """Submit ``tape`` through ``submit(user_ids) -> Future`` on time.

    ``span(name)`` (optional) returns a context manager around each wait
    for a due time (``bench.wait``), each submit (``bench.submit``) and
    the wait for the results (``bench.result``).  Requests unresolved
    ``grace`` seconds after the last due time count as failed."""

    n = len(tape.users)
    done = np.full(n, np.nan)
    lag = np.zeros(n)
    futures = []
    ctx = span or _NullSpan
    start = clock() + lead
    due = start + tape.due

    def stamp(i):
        return lambda f: done.__setitem__(i, clock())

    for i in range(n):
        wait = due[i] - clock()
        if wait > 0:
            with ctx("bench.wait"):
                sleep(wait)
        with ctx("bench.submit"):
            lag[i] = clock() - due[i]
            f = submit(tape.users[i])
            f.add_done_callback(stamp(i))
        futures.append(f)
    answers, failed = [], 0
    deadline = due[-1] + grace if n else clock()
    with ctx("bench.result"):
        for i, f in enumerate(futures):
            try:
                answers.append(f.result(timeout=max(0.0, deadline - clock())))
            except Exception:      # a request that never came or raised
                answers.append(None)
                done[i] = np.nan
                failed += 1
    latency = np.where(np.isnan(done), np.inf, done - due)
    end = float(np.nanmax(done)) if n and failed < n else clock()
    return Replay(latency, lag, answers, failed, start, end)


class _NullSpan:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def percentile_ms(latency: np.ndarray, q: float) -> float:
    """The q-th percentile of every request's latency, in ms; a failed
    request counts as infinitely late."""

    x = np.sort(np.asarray(latency, float))
    h = (len(x) - 1) * q / 100.0
    lo, frac = int(math.floor(h)), h - math.floor(h)
    if frac == 0 or np.isinf(x[lo]):
        v = x[lo]
    elif np.isinf(x[lo + 1]):
        v = np.inf
    else:
        v = x[lo] + (x[lo + 1] - x[lo]) * frac
    return float(v) * 1e3
