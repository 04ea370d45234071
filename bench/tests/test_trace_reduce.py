"""The trace reduction on hand-built event lists and on a trace recorded
on a TPU v5e by ``bench/run.py --trace 1`` (a one-second serving window
at 100 requests/s, ``data/serve_v5e.xplane.pb.gz``)."""

import gzip
import pathlib
import shutil

import pytest

import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"


def op(start, end, name="fusion.1", module="jit_step", text=None):
    text = text or f"%{name} = f32[8] fusion(f32[8] %p)"
    body = text.split(" = ", 1)[-1]
    return tr.Op(name, module, start, end, bool(tr.COLLECTIVE.search(body)))


def all_reduce(start, end):
    return op(start, end, "all-reduce.1", "jit_step",
              "%all-reduce.1 = f32[8] all-reduce(f32[8] %s)")


def test_union_and_subtract():
    assert tr.union([(0, 2), (1, 3), (5, 6), (7, 20)], 0, 10) == [
        [0, 3], [5, 6], [7, 10]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == [
        [0, 2], [3, 5], [7, 10]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]


def test_idle_share_is_mean_over_devices():
    t = tr.Trace(ops={0: [op(0, 25), op(20, 50)],      # busy 50 of 100
                      1: [op(10, 20), op(90, 120)]},   # busy 20 of 100
                 modules={0: [], 1: []}, host=[])
    assert tr.idle_share(t, 0, 100) == pytest.approx((0.5 + 0.8) / 2)
    assert tr.busy_seconds(t, 0, 100) == pytest.approx(35e-9)


def test_dispatches_count_module_starts_inside_the_window():
    mods = [tr.Span("jit_a", s, s + 5) for s in (-3, 0, 10, 20, 99, 100)]
    t = tr.Trace(ops={0: []}, modules={0: mods}, host=[])
    assert tr.dispatches(t, 0, 100) == 4


def test_compute_seconds_leave_out_collectives():
    # compute 0-20 and 65-80 on device 0, a collective alone on device 1
    t = tr.Trace(ops={0: [op(0, 20), all_reduce(10, 30), all_reduce(60, 70),
                          op(65, 80, module="jit_other")],
                      1: [all_reduce(0, 10)]},
                 modules={0: [], 1: []}, host=[])
    assert tr.compute_seconds(t, 0, 100) == pytest.approx(
        (35e-9 + 0.0) / 2)
    assert tr.compute_seconds(t, 0, 100, ("jit_step",)) == pytest.approx(
        (20e-9 + 0.0) / 2)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    t = tr.Trace(ops={0: [op(0, 10), op(40, 50), op(52, 100)]},
                 modules={0: []},
                 host=[tr.Span("bench.window", 0, 100),
                       tr.Span("bench.fit_chunk", 0, 100),
                       tr.Span("fit.wave", 5, 45)])
    gaps = tr.idle_gaps(t, 0, 100)
    assert gaps[0] == ["fit.wave", pytest.approx(30e-9)]
    assert gaps[1] == ["bench.fit_chunk", pytest.approx(2e-9)]


def test_recorded_v5e_trace(tmp_path):
    path = tmp_path / "serve_v5e.xplane.pb"
    with gzip.open(DATA / "serve_v5e.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    t = tr.load(str(path))
    lo, hi = tr.window(t)
    assert hi > lo
    assert list(t.ops) == [0]
    mods = {m.name for m in t.modules[0]}
    assert "jit_recommend_topk" in mods
    share = tr.idle_share(t, lo, hi)
    assert 0.0 < share < 1.0
    assert tr.dispatches(t, lo, hi) > 0
    serve = tr.compute_seconds(t, lo, hi, ("jit_recommend_topk",))
    assert 0 < serve <= tr.busy_seconds(t, lo, hi) + 1e-12
    assert not any(o.collective for o in t.ops[0])
    assert {s.name for s in t.host} >= {"bench.window", "bench.submit"}
    top = tr.top_ops(t, lo, hi)
    assert top and top[0][0].startswith("jit_recommend_topk/")
    assert all(g[1] > 0 for g in tr.idle_gaps(t, lo, hi))
