"""``rounds_per_s`` over a window that ends on a chunk boundary."""

import time

import pytest

import fit_driver


def test_window_ends_on_a_chunk_boundary():
    run = fit_driver.FitRun.__new__(fit_driver.FitRun)
    run.C = 4
    calls = []

    def step():
        time.sleep(0.03)
        calls.append(time.perf_counter())

    run.step = step

    class Span:
        def __init__(self, name):
            assert name == "bench.fit_chunk"

        def __enter__(self):
            return self

        def __exit__(self, *e):
            return False

    t0 = time.perf_counter()
    w = run.window(0.1, Span)
    assert w["rounds"] == 4 * len(calls) and w["attempted"] == len(calls)
    # the last chunk started before the deadline and ran to its end
    assert w["window_s"] >= 0.1
    assert calls[-1] - t0 == pytest.approx(w["window_s"], abs=0.01)
    assert w["rounds_per_s"] == pytest.approx(w["rounds"] / w["window_s"])
