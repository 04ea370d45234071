"""The five readers of the program's own spans, on hand-built traces and
registry snapshots."""

import importlib.util
import pathlib

import pytest

import trace_reduce as tr

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def op(start, end):
    return tr.Op("fusion.1", "jit_wave_step", start, end, False)


def fit_run(ops, host, rounds, lo=0, hi=100):
    trace = tr.Trace(ops=ops, modules={d: [] for d in ops}, host=host)
    return {"trace": trace, "lo": lo, "hi": hi, "window": {"rounds": rounds},
            "obs": {}}


def serve_run(**sums):
    """A snapshot holding ``span_seconds{name=serve.<k>}`` of (count, sum)."""

    hists = {f"span_seconds{{name=serve.{k}}}": {"count": c, "sum": s}
             for k, (c, s) in sums.items()}
    return {"trace": None, "obs": {"histograms": hists},
            "window": {"completed": 4}}


def test_loop_idle_counts_a_straddling_gap_inside_rounds_only():
    # rounds at [0, 40) and [40, 80), the cost at [80, 95); the device is
    # idle over [35, 45), across the round boundary, and over [82, 92)
    ops = {0: [op(0, 35), op(45, 82), op(92, 100)]}
    host = [tr.Span("fit.round", 0, 40), tr.Span("fit.round", 40, 80),
            tr.Span("fit.order", 36, 39), tr.Span("fit.cost", 80, 95)]
    run = fit_run(ops, host, rounds=2)
    assert reader("loop_idle_ms_per_round")(run) == pytest.approx(
        10e-6 / 2)
    assert reader("cost_idle_ms_per_round")(run) == pytest.approx(
        10e-6 / 2)


def test_fit_idle_is_the_mean_over_chips_and_clipped_to_the_window():
    ops = {0: [op(10, 100)],                  # idle 10 ns of the round
           1: [op(0, 100)]}                   # never idle
    host = [tr.Span("fit.round", -50, 50), tr.Span("fit.cost", 50, 60)]
    run = fit_run(ops, host, rounds=1)
    assert reader("loop_idle_ms_per_round")(run) == pytest.approx(5e-6)
    assert reader("cost_idle_ms_per_round")(run) == 0.0


def test_fit_readers_without_their_spans_return_none():
    ops = {0: [op(0, 50)]}
    run = fit_run(ops, [tr.Span("fit.wave", 0, 100)], rounds=16)
    assert reader("loop_idle_ms_per_round")(run) is None
    assert reader("cost_idle_ms_per_round")(run) is None
    run["trace"] = None
    assert reader("loop_idle_ms_per_round")(run) is None


def test_serve_readers_divide_by_requests():
    run = serve_run(request=(4, 0.040), dispatch=(5, 0.002),
                    fetch=(5, 0.030))
    assert reader("serve_worker_ms_per_request")(run) == pytest.approx(10.0)
    assert reader("serve_dispatch_ms_per_request")(run) == pytest.approx(0.5)
    assert reader("serve_fetch_ms_per_request")(run) == pytest.approx(7.5)


def test_serve_readers_without_the_spans_return_none():
    # the snapshot of a program without worker spans: queue wait only
    run = {"trace": None, "window": {"completed": 4}, "obs": {
        "histograms": {"queue_wait_seconds": {"count": 4, "sum": 0.01}}}}
    for name in ("serve_worker_ms_per_request",
                 "serve_dispatch_ms_per_request",
                 "serve_fetch_ms_per_request"):
        assert reader(name)(run) is None
    assert reader("serve_fetch_ms_per_request")(
        serve_run(fetch=(5, 0.03))) is None
