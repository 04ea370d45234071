"""Unified session API (repro.mc): facade-vs-direct parity for every
schedule × layout combo, checkpoint resume exactness, input validation,
and the legacy entry points' deprecation shims."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import GossipMCConfig
from repro.core import grid as G
from repro.core import gossip, sequential, waves
from repro.core.state import make_problem
from repro.data import lowrank_problem
from repro.mc import (BenchLogger, Callback, Checkpoint, CompletionProblem,
                      EngineOptions, EvalRMSE, FullGD, Gossip, Sequential,
                      Trainer, Wave, make_schedule)

M, N, P, Q, R = 96, 80, 3, 2, 4


@pytest.fixture(scope="module")
def setup():
    ds = lowrank_problem(M, N, R, density=0.25, seed=0)
    cfg = GossipMCConfig(m=M, n=N, p=P, q=Q, rank=R)
    problems = {
        layout: CompletionProblem.from_dataset(ds, P, Q, R, layout=layout)
        for layout in ("dense", "sparse")
    }
    return ds, cfg, problems


# ---------------------------------------------------------------------------
# Facade-vs-direct parity: same seed -> identical State
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_sequential_schedule_matches_direct(setup, layout):
    ds, cfg, problems = setup
    prob = problems[layout]
    res = Trainer(cfg).fit(prob, Sequential(num_iters=200), seed=3)
    st, hist = sequential._fit(prob.data, prob.spec, cfg,
                               jax.random.PRNGKey(3), num_iters=200)
    np.testing.assert_allclose(np.asarray(res.state.U), np.asarray(st.U),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.state.W), np.asarray(st.W),
                               rtol=1e-5, atol=1e-5)
    assert res.history == hist and res.t == int(st.t)


@pytest.mark.parametrize("layout", ["dense", "sparse"])
@pytest.mark.parametrize("sched_name", ["wave", "full"])
def test_wave_full_schedules_match_direct(setup, layout, sched_name):
    ds, cfg, problems = setup
    prob = problems[layout]
    res = Trainer(cfg).fit(prob, sched_name, num_rounds=4, seed=1)
    st, hist = waves._fit(prob.data, prob.spec, cfg, jax.random.PRNGKey(1),
                          num_rounds=4, mode=sched_name)
    np.testing.assert_allclose(np.asarray(res.state.U), np.asarray(st.U),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.state.W), np.asarray(st.W),
                               rtol=1e-5, atol=1e-5)
    assert res.history == hist


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_gossip_schedule_matches_direct_step_loop(setup, layout):
    """Gossip schedule (1×1 degenerate mesh on CPU) == hand-rolled
    make_gossip_step loop == FullGD, within 1e-5."""

    from repro.mesh import build_mesh as make_mesh
    from repro.core.state import init_state

    ds, cfg, problems = setup
    prob = problems[layout]
    rounds = 5
    res = Trainer(cfg).fit(prob, Gossip(num_rounds=rounds), seed=2)

    # direct: the fragmented pre-facade call shape
    mesh = make_mesh((1, 1), ("data", "model"))
    key, ik = jax.random.split(jax.random.PRNGKey(2))
    st0 = init_state(ik, prob.spec)
    step, _ = gossip.make_gossip_step(mesh, (P, Q), cfg,
                                      steps_per_call=rounds,
                                      layout=prob.layout)
    carry = step(prob.data, gossip.init_carry(st0))
    np.testing.assert_allclose(np.asarray(res.state.U),
                               np.asarray(carry.state.U),
                               rtol=1e-5, atol=1e-5)

    # and the single-device deterministic limit
    full = Trainer(cfg).fit(prob, FullGD(num_rounds=rounds), seed=2)
    scale = float(jnp.max(jnp.abs(full.state.U))) + 1e-12
    np.testing.assert_allclose(np.asarray(res.state.U),
                               np.asarray(full.state.U),
                               rtol=1e-5, atol=1e-5 * scale)


def test_dense_and_sparse_layouts_agree_through_facade(setup):
    ds, cfg, problems = setup
    res_d = Trainer(cfg).fit(problems["dense"], Wave(num_rounds=3), seed=0)
    res_s = Trainer(cfg).fit(problems["sparse"], Wave(num_rounds=3), seed=0)
    np.testing.assert_allclose(np.asarray(res_s.state.U),
                               np.asarray(res_d.state.U),
                               rtol=1e-5, atol=1e-5)
    assert res_s.history[-1][0] == res_d.history[-1][0]


# ---------------------------------------------------------------------------
# Problem construction
# ---------------------------------------------------------------------------


def test_from_entries_matches_from_dense(setup):
    ds, cfg, problems = setup
    rr, cc = np.nonzero(ds.train_mask)
    pe = CompletionProblem.from_entries(rr, cc, ds.x[rr, cc], (M, N), P, Q, R,
                                        layout="sparse")
    pd = problems["sparse"]
    for a, b in zip(jax.tree.leaves(pe.data), jax.tree.leaves(pd.data)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (pe.num_users, pe.num_items) == (M, N)
    res = Trainer(cfg).fit(pe, Wave(num_rounds=2), seed=0)
    ref = Trainer(cfg).fit(pd, Wave(num_rounds=2), seed=0)
    np.testing.assert_allclose(np.asarray(res.state.U),
                               np.asarray(ref.state.U), rtol=1e-5, atol=1e-5)


def test_from_entries_validates_bounds():
    with pytest.raises(ValueError, match="out of range"):
        CompletionProblem.from_entries(
            np.array([0, 50]), np.array([0, 1]), np.array([1.0, 2.0]),
            (40, 30), 2, 2, 3,
        )


def test_with_engine_and_layout_views(setup):
    ds, cfg, problems = setup
    prob = problems["sparse"]
    tuned = prob.with_engine(chunk=16, method="scatter")
    assert tuned.engine.chunk == 16 and tuned.data is prob.data
    assert prob.engine.chunk is None                  # original untouched
    dense = prob.with_layout("dense")
    assert dense.layout == "dense"
    np.testing.assert_allclose(dense.density, prob.density, rtol=1e-6)
    st = Trainer(cfg).fit(prob, Wave(num_rounds=1), seed=0).state
    g_seg = prob.full_gradients(st, rho=cfg.rho, lam=cfg.lam)
    g_chk = tuned.with_engine(method="segment").full_gradients(
        st, rho=cfg.rho, lam=cfg.lam)
    scale = float(jnp.max(jnp.abs(g_seg[0]))) + 1e-12
    np.testing.assert_allclose(np.asarray(g_chk[0]), np.asarray(g_seg[0]),
                               rtol=1e-5, atol=1e-5 * scale)


def test_engine_options_validation():
    with pytest.raises(ValueError, match="method"):
        EngineOptions(method="csr")
    with pytest.raises(ValueError, match="chunk"):
        EngineOptions(chunk=0)
    with pytest.raises(ValueError, match="bucket"):
        EngineOptions(bucket=-1)


def test_trainer_rejects_raw_problems(setup):
    ds, cfg, problems = setup
    spec = problems["dense"].spec
    raw = make_problem(ds.x[:M], np.asarray(ds.train_mask)[:M], spec)
    with pytest.raises(TypeError, match="CompletionProblem"):
        Trainer(cfg).fit(raw)


def test_make_schedule_resolution():
    s = make_schedule("sequential", num_iters=7)
    assert isinstance(s, Sequential) and s.num_iters == 7
    assert make_schedule(s) is s
    assert isinstance(make_schedule("full"), FullGD)
    with pytest.raises(ValueError, match="unknown schedule"):
        make_schedule("nomad")


# ---------------------------------------------------------------------------
# Callbacks + checkpoint resume
# ---------------------------------------------------------------------------


def test_eval_rmse_and_bench_logger_callbacks(setup):
    ds, cfg, problems = setup
    rmse_cb = EvalRMSE()
    bench = BenchLogger(log=None)
    res = Trainer(cfg, callbacks=[rmse_cb, bench]).fit(
        problems["dense"], Wave(num_rounds=4, eval_every=2), seed=0)
    assert len(rmse_cb.history) == 2 and len(bench.history) == 2
    assert rmse_cb.history[-1][0] == res.t
    assert all(dt >= 0 for _, _, _, dt in bench.history)
    # the callback's final RMSE equals the result's own bridge
    np.testing.assert_allclose(rmse_cb.history[-1][1], res.rmse(), rtol=1e-6)


def test_checkpoint_resume_is_bit_exact(setup, tmp_path):
    ds, cfg, problems = setup
    prob = problems["sparse"]
    sched = Wave(num_rounds=8, eval_every=2)
    ref = Trainer(cfg).fit(prob, sched, seed=0)

    class Crash(RuntimeError):
        pass

    class CrashAt(Callback):
        def on_eval(self, unit, cost, state, key):
            if unit >= 6:
                raise Crash()

    ck = Checkpoint(str(tmp_path / "ck"))
    with pytest.raises(Crash):
        Trainer(cfg, callbacks=[CrashAt(), ck]).fit(prob, sched, seed=0)
    rec = Trainer(cfg).fit(prob, sched, seed=0, resume_from=ck)
    np.testing.assert_array_equal(np.asarray(rec.state.U),
                                  np.asarray(ref.state.U))
    np.testing.assert_array_equal(np.asarray(rec.state.W),
                                  np.asarray(ref.state.W))
    assert rec.t == ref.t


# ---------------------------------------------------------------------------
# Input validation (GridSpec / GossipMCConfig)
# ---------------------------------------------------------------------------


def test_gridspec_validation_messages():
    with pytest.raises(ValueError, match="rank must be positive"):
        G.GridSpec(8, 8, 2, 2, 0)
    with pytest.raises(ValueError, match="more blocks than matrix"):
        G.GridSpec(4, 8, 5, 2, 2)
    with pytest.raises(ValueError, match="pad to 9x6"):
        G.GridSpec(7, 5, 3, 2, 2)
    with pytest.raises(ValueError, match="positive dimensions"):
        G.GridSpec(8, 8, 0, 2, 2)


def test_gossip_mc_config_validation_messages():
    with pytest.raises(ValueError, match="rank must be positive"):
        GossipMCConfig(rank=0)
    with pytest.raises(ValueError, match="more blocks"):
        GossipMCConfig(m=3, n=500, p=4, q=4)
    with pytest.raises(ValueError, match="density"):
        GossipMCConfig(density=0.0)
    with pytest.raises(ValueError, match="a > 0"):
        GossipMCConfig(a=0.0)
    with pytest.raises(ValueError, match="unknown mode"):
        GossipMCConfig(mode="jacobi")
    GossipMCConfig()                                  # defaults stay valid


# ---------------------------------------------------------------------------
# Deprecation shims
# ---------------------------------------------------------------------------


def test_legacy_fit_entry_points_warn_and_match(setup):
    ds, cfg, problems = setup
    prob = problems["dense"]
    res = Trainer(cfg).fit(prob, Wave(num_rounds=2), seed=0)
    with pytest.warns(DeprecationWarning, match="repro.mc.Trainer"):
        st, hist = waves.fit(prob.data, prob.spec, cfg, jax.random.PRNGKey(0),
                             num_rounds=2)
    np.testing.assert_array_equal(np.asarray(res.state.U), np.asarray(st.U))
    assert res.history == hist

    res_s = Trainer(cfg).fit(prob, Sequential(num_iters=30), seed=0)
    with pytest.warns(DeprecationWarning, match="repro.mc.Trainer"):
        st_s, _ = sequential.fit(prob.data, prob.spec, cfg,
                                 jax.random.PRNGKey(0), num_iters=30)
    np.testing.assert_array_equal(np.asarray(res_s.state.U),
                                  np.asarray(st_s.U))
