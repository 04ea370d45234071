"""Open-loop timing from due times, and the percentiles over all requests."""

from concurrent.futures import Future

import numpy as np
import pytest

import openloop


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def server(clock, service, stall=None):
    """One worker that serves requests in turn; ``stall`` = (at, length)
    holds it once."""

    state = {"free_at": 0.0}

    def submit(users):
        start = max(clock(), state["free_at"])
        if stall and start >= stall[0] and not stall[2]:
            start += stall[1]
            stall[2] = True
        state["free_at"] = start + service
        f = Future()
        f._done_at = state["free_at"]
        pending.append(f)
        return f

    pending = []
    return submit, pending


def drive(tape, service, stall=None):
    clock = FakeClock()
    submit, pending = server(clock, service, stall)

    def resolve_all():
        for f in pending:
            if not f.done():
                clock.t = max(clock.t, f._done_at)
                f.set_result((np.zeros((1, 1)), np.zeros((1, 1))))

    real_sleep = clock.sleep

    def sleep(s):
        # resolve what would have finished while waiting
        target = clock.t + s
        for f in pending:
            if not f.done() and f._done_at <= target:
                clock.t = f._done_at
                f.set_result((np.zeros((1, 1)), np.zeros((1, 1))))
        clock.t = target

    clock.sleep = sleep

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            if self.name == "bench.result":
                resolve_all()

        def __exit__(self, *e):
            return False

    return openloop.replay(submit, tape, clock=clock, sleep=clock.sleep,
                           span=Span, lead=0.0)


def test_tape_same_work_for_every_seed():
    a = openloop.make_tape(500, 100.0, 1, 1500, 6040, seed=1)
    b = openloop.make_tape(500, 100.0, 1, 1500, 6040, seed=2 ** 31 + 5)
    assert list(map(len, a.users)) == list(map(len, b.users))
    np.testing.assert_array_equal(a.due, b.due)
    assert not np.array_equal(a.users[0], b.users[0])
    assert a.due[-1] == pytest.approx(5.0, rel=0.05)
    sizes = np.array(sorted(map(len, a.users)))
    assert sizes[0] == 1 and sizes[-1] <= 1500 and sizes[-1] > 1400


def test_latency_is_timed_from_due_time():
    tape = openloop.Tape(np.array([0.0, 1.0, 2.0]),
                         [np.zeros(1, np.int32)] * 3)
    rp = drive(tape, service=0.25)
    assert rp.failed == 0
    np.testing.assert_allclose(rp.latency, [0.25, 0.25, 0.25])
    np.testing.assert_allclose(rp.lag, 0.0)


def test_stall_moves_p95_through_the_requests_behind_it():
    due = np.arange(100) * 0.01
    tape = openloop.Tape(due, [np.zeros(1, np.int32)] * 100)
    calm = drive(tape, service=0.005)
    stalled = drive(tape, service=0.005, stall=[0.5, 0.2, False])
    p95_calm = openloop.percentile_ms(calm.latency, 95)
    p95_stall = openloop.percentile_ms(stalled.latency, 95)
    assert p95_calm == pytest.approx(5.0)
    # the stall delays the request due at 0.5 s and the ~20 behind it
    assert p95_stall > 100.0
    assert openloop.percentile_ms(stalled.latency, 50) == pytest.approx(5.0)


def test_percentiles_over_all_requests_count_failures():
    lat = np.array([0.001] * 95 + [np.inf] * 5)
    assert openloop.percentile_ms(lat, 50) == pytest.approx(1.0)
    assert openloop.percentile_ms(lat, 94) == pytest.approx(1.0)
    assert openloop.percentile_ms(lat, 96) == np.inf
    lat = np.arange(1, 101) / 1000.0
    assert openloop.percentile_ms(lat, 95) == pytest.approx(
        np.percentile(lat, 95) * 1e3)
