"""repro.obs: registry semantics, histogram percentiles vs the numpy
oracle, device-true spans, and the three instrumented planes (training
via the Telemetry callback, ingest counters, serving latency)."""

import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.registry import DEFAULT_EDGES, NOOP, Histogram, Registry


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test sees an empty, enabled default registry — and leaves
    one behind (the registry is process-global across the suite)."""

    obs.set_enabled(True)
    obs.reset()
    yield
    obs.set_enabled(True)
    obs.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_get_or_create_and_identity():
    c1 = obs.counter("events_total")
    c1.inc()
    c1.inc(2.5)
    assert obs.counter("events_total") is c1
    assert obs.counter("events_total").value == 3.5
    # labels are part of the identity, order-independent
    a = obs.counter("routed_total", shard="0,1", kind="x")
    b = obs.counter("routed_total", kind="x", shard="0,1")
    assert a is b
    assert obs.counter("routed_total", shard="1,0", kind="x") is not a


def test_counter_rejects_negative():
    with pytest.raises(ValueError):
        obs.counter("events_total").inc(-1)


def test_gauge_set_add():
    g = obs.gauge("free_slots")
    g.set(10)
    g.add(-3)
    assert obs.snapshot()["gauges"]["free_slots"] == 7.0


def test_snapshot_keys_and_reset():
    obs.counter("c_total").inc()
    obs.gauge("g").set(1)
    obs.histogram("h").observe(0.5)
    snap = obs.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["c_total"] == 1.0
    assert snap["histograms"]["h"]["count"] == 1
    obs.reset()
    empty = obs.snapshot()
    assert not empty["counters"] and not empty["gauges"] \
        and not empty["histograms"]


def test_default_edges_cover_latency_and_bytes():
    # 10 buckets per decade from 1 µs to 10 ks, strictly increasing
    assert DEFAULT_EDGES[0] == pytest.approx(1e-6)
    assert DEFAULT_EDGES[-1] == pytest.approx(1e4)
    assert all(a < b for a, b in zip(DEFAULT_EDGES, DEFAULT_EDGES[1:]))
    ratio = DEFAULT_EDGES[1] / DEFAULT_EDGES[0]
    assert ratio == pytest.approx(10 ** 0.1)


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram(edges=[1.0, 1.0, 2.0])
    with pytest.raises(ValueError):
        Histogram(edges=[3.0])


# ---------------------------------------------------------------------------
# percentiles vs the numpy oracle
# ---------------------------------------------------------------------------


def test_quantiles_match_numpy_within_bucket_resolution():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-5.0, sigma=1.5, size=20_000)
    h = Histogram()
    for s in samples:
        h.observe(float(s))
    for q in (0.50, 0.90, 0.99):
        oracle = float(np.quantile(samples, q))
        got = h.quantile(q)
        # log-spaced buckets (10/decade) bound the relative error by the
        # bucket ratio 10^0.1 ≈ 1.26; in practice interpolation lands much
        # closer — 15% is a loose, stable bound
        assert abs(got - oracle) / oracle < 0.15, (q, got, oracle)
    summ = h.summary()
    assert summ["count"] == len(samples)
    assert summ["mean"] == pytest.approx(samples.mean(), rel=1e-6)
    assert summ["min"] == pytest.approx(samples.min())
    assert summ["max"] == pytest.approx(samples.max())


def test_single_observation_reports_itself():
    h = Histogram()
    h.observe(0.0042)
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) == pytest.approx(0.0042)


def test_empty_histogram_quantile_nan():
    h = Histogram()
    assert math.isnan(h.quantile(0.5))
    assert h.summary() == {"count": 0, "sum": 0.0}


# ---------------------------------------------------------------------------
# disabled registry: shared no-op instruments
# ---------------------------------------------------------------------------


def test_disabled_registry_hands_out_noop():
    prev = obs.set_enabled(False)
    try:
        c = obs.counter("off_total")
        assert c is NOOP
        c.inc(5)
        obs.histogram("off_h").observe(1.0)
        obs.gauge("off_g").set(3)
        snap = obs.snapshot()
        assert not snap["counters"] and not snap["gauges"] \
            and not snap["histograms"]
    finally:
        obs.set_enabled(prev)
    # re-enabled: fresh live instruments again
    obs.counter("off_total").inc()
    assert obs.snapshot()["counters"]["off_total"] == 1.0


def test_set_enabled_returns_previous():
    assert obs.set_enabled(False) is True
    assert obs.set_enabled(True) is False
    assert obs.enabled()


def test_isolated_registry_instances():
    r = Registry()
    r.counter("x_total").inc()
    assert r.snapshot()["counters"]["x_total"] == 1.0
    assert "x_total" not in obs.snapshot()["counters"]


# ---------------------------------------------------------------------------
# spans: device-true timing
# ---------------------------------------------------------------------------


def test_span_waits_for_device_work():
    """An async-dispatched jit workload: the span must charge the device
    time (block_until_ready on declared outputs), so its reading is at
    least the independently-synced wall time of the same computation."""

    @jax.jit
    def work(x):
        for _ in range(8):
            x = x @ x / jnp.linalg.norm(x)
        return x

    x = jnp.asarray(np.random.default_rng(0).normal(size=(400, 400)),
                    jnp.float32)
    work(x).block_until_ready()                 # compile outside the span

    t0 = time.perf_counter()
    work(x).block_until_ready()
    synced = time.perf_counter() - t0

    with obs.span("work") as sp:
        sp.outputs(work(x))
    # device-true: the span covers the actual compute (loosely — the
    # comparison run gives the scale), and never reads less than the
    # host-side dispatch slice it contains
    assert sp.seconds >= sp.host_seconds
    assert sp.seconds > 0.2 * synced
    snap = obs.snapshot()
    assert snap["histograms"]["span_seconds{name=work}"]["count"] == 1


def test_span_disabled_records_nothing():
    prev = obs.set_enabled(False)
    try:
        with obs.span("quiet") as sp:
            sp.outputs(jnp.ones(4))
        assert sp.seconds >= 0.0
    finally:
        obs.set_enabled(prev)
    assert "span_seconds{name=quiet}" not in obs.snapshot()["histograms"]


def test_device_sync_handles_non_arrays():
    obs.device_sync({"a": jnp.ones(3), "b": [1, 2.5, None]})


def test_span_records_one_histogram_per_name():
    """``seconds`` is recorded; the dispatch-only ``host_seconds`` stays
    an attribute and no longer lands in a second histogram."""

    with obs.span("one", annotate=True, rid=3) as sp:
        sp.outputs(jnp.ones(4) * 2)
    assert sp.host_seconds is not None and sp.seconds >= sp.host_seconds
    hists = obs.snapshot()["histograms"]
    assert hists["span_seconds{name=one}"]["count"] == 1
    assert not any(k.startswith("span_host_seconds") for k in hists)


def _host_events(log_dir, prefix):
    """(name, {stat: value}) of every host trace event named ``prefix*``
    in the one capture under ``log_dir``."""

    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, dict(e.stats)))
    return out


def test_span_meta_rides_the_trace_event(tmp_path):
    """Keyword metadata becomes stats of the trace event; the event keeps
    the plain name, and the registry keys on the name alone."""

    with obs.trace(str(tmp_path)):
        for rid in (4, 5):
            with obs.span("meta.demo", annotate=True, rid=rid, bucket=64):
                pass
    events = _host_events(tmp_path, "meta.")
    assert sorted(events, key=lambda e: e[1]["rid"]) == [
        ("meta.demo", {"rid": 4, "bucket": 64}),
        ("meta.demo", {"rid": 5, "bucket": 64})]
    assert obs.snapshot()["histograms"][
        "span_seconds{name=meta.demo}"]["count"] == 2


# ---------------------------------------------------------------------------
# training plane: Telemetry callback + gossip round metrics
# ---------------------------------------------------------------------------


def _small_problem(m=48, n=40, p=2, q=2, rank=4, seed=0):
    from repro.data import lowrank_problem
    from repro.mc import CompletionProblem

    ds = lowrank_problem(m, n, r=rank, density=0.3, seed=seed)
    return CompletionProblem.from_dataset(ds, p, q, rank=rank,
                                          layout="sparse")


def test_telemetry_round_parity_wave():
    from repro.mc import Telemetry, Trainer, Wave

    problem = _small_problem()
    rounds, every = 24, 6
    obs.reset()
    Trainer(callbacks=[Telemetry()]).fit(
        problem, Wave(num_rounds=rounds, eval_every=every), seed=0)
    snap = obs.snapshot()
    assert snap["counters"]["train_units_total"] == rounds
    assert snap["counters"]["train_evals_total"] == rounds // every
    assert snap["counters"]["train_fits_total"] == 1.0
    assert snap["gauges"]["train_cost"] == snap["gauges"]["train_final_cost"]
    assert snap["gauges"]["train_consensus_error"] >= 0.0
    hist = snap["histograms"]["train_eval_interval_seconds"]
    assert hist["count"] == rounds // every


def test_gossip_rounds_and_exact_halo_bytes():
    from repro.core.gossip import halo_bytes_per_round
    from repro.mc import Gossip, Trainer

    problem = _small_problem()
    rounds = 12
    sched = Gossip(num_rounds=rounds, eval_every=4)
    obs.reset()
    Trainer().fit(problem, sched, seed=0)
    snap = obs.snapshot()
    assert snap["counters"]["train_gossip_rounds_total"] == rounds
    # the counter must agree with the plan's own edge accounting (0 on the
    # single-device CI plan — no wires, no bytes; the multidevice-smoke CI
    # job exercises the non-zero arm via benchmarks/gossip_comm.py)
    spec = problem.spec
    plan = sched._plan(problem)
    expected = halo_bytes_per_round(plan, spec.mb, spec.nb,
                                    spec.r)["total_bytes"]
    assert snap["counters"]["train_gossip_halo_bytes_total"] == \
        rounds * expected
    assert snap["histograms"]["train_gossip_round_seconds"]["count"] == 3


def test_halo_bytes_formula_matches_edge_geometry():
    from repro.core.gossip import halo_bytes_per_round
    from repro.mesh.plan import MeshPlan

    plan = MeshPlan.build(4, 4)           # geometry-only 4x4 block grid
    h = halo_bytes_per_round(plan, mb=8, nb=6, r=2, grid=(2, 2))
    # 2x2 shard grid over 4x4 blocks: 2 blocks per shard per axis, so a U
    # edge message is (2 blocks)·(mb=8)·(r=2) float32s
    assert h["u_edge_message_bytes"] == 2 * 8 * 2 * 4
    assert h["w_edge_message_bytes"] == 2 * 6 * 2 * 4
    # only interior pairs exchange: 2 directions x R rows x (C-1) column
    # neighbour pairs for U (and transposed for W)
    assert h["u_bytes"] == 2 * 2 * 1 * h["u_edge_message_bytes"]
    assert h["w_bytes"] == 2 * 2 * 1 * h["w_edge_message_bytes"]
    assert h["total_bytes"] == h["u_bytes"] + h["w_bytes"]
    assert h["per_interior_agent_bytes"] == \
        2 * (h["u_edge_message_bytes"] + h["w_edge_message_bytes"])
    # a 1x1 deployment has no neighbours: exactly zero wire bytes
    assert halo_bytes_per_round(plan, 8, 6, 2,
                                grid=(1, 1))["total_bytes"] == 0


def test_telemetry_disabled_is_silent():
    from repro.mc import Telemetry, Trainer, Wave

    problem = _small_problem()
    prev = obs.set_enabled(False)
    obs.reset()          # drop the problem-build ingest counters too
    try:
        res = Trainer(callbacks=[Telemetry()]).fit(
            problem, Wave(num_rounds=8, eval_every=4), seed=0)
    finally:
        obs.set_enabled(prev)
    assert res.history                           # the fit itself ran
    snap = obs.snapshot()
    assert not snap["counters"] and not snap["histograms"]


def _span_counts(prefix="fit."):
    return {k[len("span_seconds{name="):-1]: h["count"]
            for k, h in obs.snapshot()["histograms"].items()
            if k.startswith("span_seconds{name=" + prefix)}


@pytest.mark.parametrize("rounds,every", [(10, 4), (8, 4), (5, 0)])
def test_wave_round_spans(rounds, every):
    """One ``fit.round`` and one ``fit.order`` per round, one ``fit.cost``
    per eval boundary (every ``every`` rounds and the last)."""

    from repro.mc import Trainer, Wave

    problem = _small_problem()
    obs.reset()
    Trainer().fit(problem, Wave(num_rounds=rounds, eval_every=every), seed=0)
    evals = math.ceil(rounds / every) if every else 1
    assert _span_counts() == {"fit.wave": 1, "fit.round": rounds,
                              "fit.order": rounds, "fit.cost": evals}


def test_wave_round_spans_leave_the_fit_bit_identical():
    from repro.mc import Trainer, Wave

    problem = _small_problem()
    sched = Wave(num_rounds=6, eval_every=2)
    on = Trainer().fit(problem, sched, seed=3)
    prev = obs.set_enabled(False)
    try:
        off = Trainer().fit(problem, sched, seed=3)
    finally:
        obs.set_enabled(prev)
    for a, b in zip(on.state, off.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert on.history == off.history


def test_full_gd_rounds_draw_no_order():
    from repro.mc import FullGD, Trainer

    problem = _small_problem()
    obs.reset()
    Trainer().fit(problem, FullGD(num_rounds=5, eval_every=5), seed=0)
    assert _span_counts() == {"fit.full": 1, "fit.round": 5, "fit.cost": 1}


# ---------------------------------------------------------------------------
# ingest plane
# ---------------------------------------------------------------------------


def test_ingest_counters_track_store_and_appends():
    from repro import sparse

    m, n, p, q = 40, 32, 2, 2
    rng = np.random.default_rng(0)
    mask = rng.random((m, n)) < 0.3
    rr, cc = np.nonzero(mask)
    vv = rng.normal(size=len(rr)).astype(np.float32)
    cut = len(rr) - 10

    obs.reset()
    sp, _ = sparse.from_entries(rr[:cut], cc[:cut], vv[:cut], m, n, p, q,
                                headroom=64)
    snap = obs.snapshot()
    assert snap["counters"]["ingest_entries_total"] == cut
    free0 = snap["gauges"]["ingest_free_slots"]
    assert free0 > 0

    sp2 = sparse.append_entries(sp, rr[cut:], cc[cut:], vv[cut:])
    snap = obs.snapshot()
    assert snap["counters"]["ingest_appends_total"] == 1.0
    assert snap["counters"]["ingest_appended_entries_total"] == 10.0
    assert snap["histograms"]["ingest_append_seconds"]["count"] == 1
    assert snap["gauges"]["ingest_free_slots"] <= free0
    assert int(jnp.sum(sp2.nnz)) == len(rr)


@pytest.mark.parametrize("tile", [True, False])
def test_ingest_spans_and_padding_share(tile, monkeypatch):
    """``from_entries`` records the host sort and the upload as
    ``ingest.*`` spans and sets ``ingest_padding_share`` to 1 - sum of
    nnz / (blocks x capacity), with the dense tile built or not."""

    from repro.mc import CompletionProblem
    from repro.sparse import store

    monkeypatch.setitem(store.TILE_CROSSOVER, "cpu", 0.0 if tile else 2.0)
    rng = np.random.default_rng(3)
    lin = rng.choice(40 * 30, 250, replace=False)
    obs.reset()
    problem = CompletionProblem.from_entries(
        lin // 30, lin % 30, rng.normal(size=250), (40, 30), 2, 3, 4)
    sp = problem.data
    assert sp.has_tile == tile
    snap = obs.snapshot()
    hists = snap["histograms"]
    assert hists["span_seconds{name=ingest.sort}"]["count"] >= 1
    assert hists["span_seconds{name=ingest.upload}"]["count"] == 1
    share = snap["gauges"]["ingest_padding_share"]
    assert share == pytest.approx(1 - 250 / (6 * sp.capacity))
    assert share == pytest.approx(
        1 - float(jnp.sum(sp.valid)) / sp.valid.size)


# ---------------------------------------------------------------------------
# serving plane
# ---------------------------------------------------------------------------


def test_service_latency_histogram_and_qps():
    from repro.serve.recommend import RecommendIndex, RecommendService

    rng = np.random.default_rng(0)
    idx = RecommendIndex(
        jnp.asarray(rng.normal(size=(30, 4)), jnp.float32),
        jnp.asarray(rng.normal(size=(20, 4)), jnp.float32),
        jnp.full((30, 16), 20, jnp.int32),
    )
    svc = RecommendService(idx, batch=8, k=3)
    obs.reset()
    items, scores = svc.recommend(np.arange(20))    # 3 batches (tail padded)
    assert items.shape == (20, 3)

    snap = obs.snapshot()
    assert snap["counters"]["serve_requests_total"] == 1.0
    assert snap["counters"]["serve_users_total"] == 20.0
    assert snap["counters"]["serve_batches_total"] == 3.0
    # the first batch pays the jit compile and is routed to the warmup
    # histogram — steady-state latency holds only the other two batches
    assert snap["counters"]["serve_warmup_batches_total"] == 1.0
    assert snap["histograms"]["serve_warmup_seconds"]["count"] == 1
    assert snap["histograms"]["serve_batch_seconds"]["count"] == 2
    assert snap["histograms"]["queue_wait_seconds"]["count"] == 3

    m = svc.metrics()
    assert m["latency"]["count"] == 2
    assert m["latency"]["p99"] >= m["latency"]["p50"] > 0.0
    assert m["warmup"]["batches"] == 1.0
    assert m["warmup"]["seconds"]["count"] == 1
    assert m["queue_wait"]["count"] == 3
    assert m["requests"] == 1 and m["users"] == 20
    assert m["qps"] > 0.0 and m["users_per_s"] > 0.0

    svc.reset_metrics()
    m = svc.metrics()
    assert m["requests"] == 0 and m["qps"] == 0.0


def test_service_metrics_before_any_request():
    from repro.serve.recommend import RecommendIndex, RecommendService

    idx = RecommendIndex(jnp.ones((4, 2)), jnp.ones((6, 2)),
                         jnp.full((4, 16), 6, jnp.int32))
    m = RecommendService(idx, batch=4, k=2).metrics()
    assert m["latency"]["count"] == 0
    assert m["qps"] == 0.0 and m["window_seconds"] == 0.0
