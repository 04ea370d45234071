"""Streaming ingestion: sorted-splice append into the padded-COO store,
CompletionProblem.append on both layouts, Trainer.refit warm starts, and
the serve-side RecommendIndex/RecommendService.refresh hot swap."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import GossipMCConfig
from repro.core import waves
from repro.core.state import init_state, make_problem
from repro.core import grid as G
from repro.data import lowrank_problem
from repro.mc import (CompletionProblem, Incremental, Trainer, Wave,
                      make_schedule)
from repro import sparse
from repro.sparse import store

from test_sparse import check_sorted_store_invariants
from _tile_paths import PATHS, on_path


def _coo_problem(m=60, n=48, p=3, q=2, density=0.2, seed=0, base_frac=0.7,
                 bucket=32, headroom=96):
    """A COO ratings log split into (base store, streamed remainder)."""

    rng = np.random.default_rng(seed)
    mask = rng.random((m, n)) < density
    rr, cc = np.nonzero(mask)
    vv = rng.normal(size=len(rr)).astype(np.float32)
    perm = rng.permutation(len(rr))
    cut = int(base_frac * len(rr))
    base, stream = perm[:cut], perm[cut:]
    sp, _ = sparse.from_entries(rr[base], cc[base], vv[base], m, n, p, q,
                                bucket=bucket, headroom=headroom)
    return sp, (rr, cc, vv), (base, stream)


# ---------------------------------------------------------------------------
# append_entries: the sorted splice
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("seed,base_frac", [(0, 0.7), (1, 0.5), (2, 0.95)])
def test_append_matches_fresh_ingest(seed, base_frac, path):
    """Base ingest + append == one-shot ingest of the union, entry for
    entry (to_dense), and the appended store satisfies every sorted-layout
    invariant — the segment fast path never notices the splice.  A dense
    tile follows the splice cell for cell."""

    sp, (rr, cc, vv), (base, stream) = _coo_problem(seed=seed,
                                                    base_frac=base_frac)
    sp = on_path(sp, path)
    out = sparse.append_entries(sp, rr[stream], cc[stream], vv[stream])
    check_sorted_store_invariants(out)
    assert out.capacity == sp.capacity                 # no shape change
    assert out.has_tile == (path == "tile")
    ref, _ = sparse.from_entries(rr, cc, vv, 60, 48, 3, 2, bucket=32)
    xa, ma = sparse.to_dense(out)
    xb, mb = sparse.to_dense(ref)
    np.testing.assert_array_equal(ma, mb)
    np.testing.assert_array_equal(xa, xb)
    if path == "tile":
        fresh = np.asarray(sparse.with_tile(ref).entries.tile_vals)
        np.testing.assert_array_equal(np.asarray(out.entries.tile_vals),
                                      fresh)
        tm = np.asarray(out.entries.tile_mask)
        np.testing.assert_array_equal(tm[..., :mb.shape[-2], :mb.shape[-1]],
                                      mb > 0)
        assert tm.sum() == (mb > 0).sum()


@pytest.mark.parametrize("path", PATHS)
def test_append_keeps_segment_gradients_exact(path):
    """Gradients on an appended store match the dense oracle at 1e-5 — the
    incrementally patched CSR/CSC views feed the segment engine correctly,
    and the patched dense tile gives a fresh ingest's gradients exactly."""

    m, n, p, q, r = 48, 36, 3, 2, 4
    rng = np.random.default_rng(3)
    mask = (rng.random((m, n)) < 0.25).astype(np.float32)
    x = rng.normal(size=(m, n)).astype(np.float32) * mask
    rr, cc = np.nonzero(mask)
    perm = rng.permutation(len(rr))
    cut = int(0.7 * len(rr))
    sp, _ = sparse.from_entries(rr[perm[:cut]], cc[perm[:cut]],
                                x[rr, cc][perm[:cut]], m, n, p, q,
                                bucket=32, headroom=128)
    sp = on_path(sp, path)
    out = sparse.append_entries(sp, rr[perm[cut:]], cc[perm[cut:]],
                                x[rr, cc][perm[cut:]])
    assert out.has_tile == (path == "tile")
    spec = G.GridSpec(m, n, p, q, r)
    prob = make_problem(x, mask, spec)
    st = init_state(jax.random.PRNGKey(0), spec)
    gd = waves.full_gradients(prob, st.U, st.W, rho=0.1, lam=0.01)
    gs = waves.full_gradients(out, st.U, st.W, rho=0.1, lam=0.01)
    for a, b in zip(gs, gd):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5 * scale)
    if path == "tile":
        fresh, _ = sparse.from_entries(rr, cc, x[rr, cc], m, n, p, q,
                                       bucket=32, headroom=128)
        gf = waves.full_gradients(on_path(fresh, "tile"), st.U, st.W,
                                  rho=0.1, lam=0.01)
        for a, b in zip(gs, gf):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_append_empty_is_noop():
    sp, _, _ = _coo_problem()
    assert sparse.append_entries(sp, [], [], []) is sp


@pytest.mark.parametrize("path", PATHS)
def test_append_duplicate_updates_value_in_place(path):
    """An existing (row, col) pair costs no slot: nnz is unchanged and the
    stored value is replaced, in the dense tile too; within-batch
    duplicates resolve to the last occurrence."""

    sp, (rr, cc, vv), (base, _) = _coo_problem()
    sp = on_path(sp, path)
    r0, c0 = int(rr[base[0]]), int(cc[base[0]])
    out = sparse.append_entries(sp, [r0, r0], [c0, c0],
                                np.array([5.0, 9.0], np.float32))
    np.testing.assert_array_equal(np.asarray(out.nnz), np.asarray(sp.nnz))
    check_sorted_store_invariants(out)
    xa, _ = sparse.to_dense(out)
    mb, nb = sp.mb, sp.nb
    assert xa[r0 // mb, c0 // nb, r0 % mb, c0 % nb] == 9.0
    if path == "tile":
        tv = np.asarray(out.entries.tile_vals)
        assert tv[r0 // mb, c0 // nb, r0 % mb, c0 % nb] == 9.0


def test_append_overflow_raises_with_headroom_hint():
    """A full bucket fails loudly and tells the operator how much headroom
    would have absorbed the append."""

    sp, (rr, cc, vv), (base, _) = _coo_problem(headroom=0)
    free = int(np.asarray(sp.free_slots)[0, 0])
    # flood block (0, 0) with more new entries than it has free slots
    mb, nb = sp.mb, sp.nb
    have = {(int(r), int(c)) for r, c in zip(rr[base], cc[base])}
    newr, newc = zip(*[(r, c) for r in range(mb) for c in range(nb)
                       if (r, c) not in have][: free + 5])
    with pytest.raises(ValueError, match="headroom"):
        sparse.append_entries(sp, np.array(newr), np.array(newc),
                              np.ones(len(newr), np.float32))


def _half_star_problem(seed=0, m=60, n=48, p=3, q=2):
    """Centred half-star ratings (1 to 5 stars) split into a base store
    carrying its tile of 1-byte codes and a streamed remainder; the base
    holds every level, so the union has the base's grid."""

    rng = np.random.default_rng(seed)
    lin = rng.choice(m * n, 700, replace=False)
    rr, cc = lin // n, lin % n
    stars = np.arange(2, 11) * np.float32(0.5)
    vv = stars[rng.integers(0, len(stars), len(lin))]
    vv[:len(stars)] = stars
    mu = np.float32(vv.mean())
    vv = vv - mu
    base, stream = np.arange(500), np.arange(500, len(lin))
    sp, _ = sparse.from_entries(rr[base], cc[base], vv[base], m, n, p, q,
                                bucket=32, headroom=160)
    sp = sparse.with_tile(sp, "codes")
    return sp, (rr, cc, vv), (base, stream), mu


def test_append_on_grid_patches_codes_cell_for_cell():
    """Values on the tile's grid are written as their codes: the grid
    stays, only the appended cells change, and the tile equals a fresh
    ingest's of the union code for code."""

    sp, (rr, cc, vv), (base, stream), _ = _half_star_problem()
    out = sparse.append_entries(sp, rr[stream], cc[stream], vv[stream])
    check_sorted_store_invariants(out)
    assert out.entries.tile_encoding == "codes"
    np.testing.assert_array_equal(np.asarray(out.entries.tile_grid),
                                  np.asarray(sp.entries.tile_grid))
    before = np.asarray(sp.entries.tile_codes)
    after = np.asarray(out.entries.tile_codes)
    mb, nb = sp.mb, sp.nb
    touched = np.zeros(after.shape, bool)
    touched[rr[stream] // mb, cc[stream] // nb, rr[stream] % mb,
            cc[stream] % nb] = True
    np.testing.assert_array_equal(after[~touched], before[~touched])
    assert (after[touched] > 0).all()
    ref, _ = sparse.from_entries(rr, cc, vv, 60, 48, 3, 2, bucket=32)
    np.testing.assert_array_equal(
        after, np.asarray(sparse.with_tile(ref, "codes").entries.tile_codes))


@pytest.mark.parametrize("stars,enc", [(0.5, "codes"), (1.3, "f32")],
                         ids=["new-grid", "f32"])
def test_append_off_grid_encodes_the_tile_afresh(stars, enc):
    """A value off the tile's grid is not rounded onto it: the whole tile
    is encoded afresh from the store's values, as ingest encodes them.  A
    half star below every stored one moves the grid (still codes); 1.3
    stars fits no grid, so the tile becomes float32 values and a mask.
    Either way it is a fresh ingest's tile of the union, and the f-term
    is the segment engine's."""

    sp, (rr, cc, vv), (base, stream), mu = _half_star_problem(seed=1)
    new = np.float32(stars) - mu
    vals = vv[stream].copy()
    vals[0] = new
    out = sparse.append_entries(sp, rr[stream], cc[stream], vals)
    assert out.entries.tile_encoding == enc
    allv = vv.copy()
    allv[stream] = vals
    ref, _ = sparse.from_entries(rr, cc, allv, 60, 48, 3, 2, bucket=32)
    fresh = sparse.with_tile(ref).entries
    assert fresh.tile_encoding == enc
    for f in ("tile_vals", "tile_mask", "tile_codes", "tile_grid"):
        a, b = getattr(out.entries, f), getattr(fresh, f)
        assert (a is None) == (b is None), f
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    xa, _ = sparse.to_dense(out)
    r0, c0 = int(rr[stream[0]]), int(cc[stream[0]])
    mb, nb = sp.mb, sp.nb
    assert xa[r0 // mb, c0 // nb, r0 % mb, c0 % nb] == new
    key = jax.random.PRNGKey(0)
    U = jax.random.normal(key, (3, 2, mb, 4))
    W = jax.random.normal(key, (3, 2, nb, 4))
    ga = waves.full_gradients(out, U, W, rho=0.1, lam=0.01)
    gs = waves.full_gradients(sparse.drop_tile(out), U, W, rho=0.1, lam=0.01)
    for a, b in zip(ga, gs):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5 * scale)


def test_append_off_grid_follows_the_memory_rule(monkeypatch):
    """Off the grid, an append builds the tile ingest would build, by
    ``tile_rule`` on the store's capacity and the device's memory: where
    the memory holds the codes but not a float32 tile and mask, the new
    grid stays codes and values that fit no grid leave no tile, so the
    f-term goes back to the segment engine."""

    sp, (rr, cc, vv), (base, stream), mu = _half_star_problem(seed=2)
    p, q = sp.rows.shape[:2]
    tm, tn = store.tile_shape(sp.mb, sp.nb, "codes")
    limit = int(p * q * tm * tn * store.TILE_BYTES_PER_CELL["codes"]
                / store.TILE_MEMORY_SHARE)
    monkeypatch.setattr(store, "device_bytes_limit", lambda: limit)
    rule = dict(capacity=sp.capacity, mb=sp.mb, nb=sp.nb, blocks=p * q,
                backend=jax.default_backend(), bytes_limit=limit)
    assert store.tile_rule(**rule, encoding="codes")
    assert not store.tile_rule(**rule, encoding="f32")
    mb, nb = sp.mb, sp.nb
    r0, c0 = int(rr[stream[0]]), int(cc[stream[0]])
    for stars, enc in ((0.5, "codes"), (1.3, None)):
        vals = vv[stream].copy()
        vals[0] = np.float32(stars) - mu
        out = sparse.append_entries(sp, rr[stream], cc[stream], vals)
        assert out.entries.tile_encoding == enc
        assert out.capacity == sp.capacity
        xa, _ = sparse.to_dense(out)
        assert xa[r0 // mb, c0 // nb, r0 % mb, c0 % nb] == vals[0]
        if enc is not None:
            x, m = store.decode(out.entries.tile_codes,
                                out.entries.tile_grid)
            np.testing.assert_array_equal(
                np.asarray(x)[..., :mb, :nb] * np.asarray(m)[..., :mb, :nb],
                xa)


def test_append_validates_inputs():
    sp, _, _ = _coo_problem()
    with pytest.raises(ValueError, match="equal-length"):
        sparse.append_entries(sp, [1, 2], [1], [1.0])
    with pytest.raises(ValueError, match="out of range"):
        sparse.append_entries(sp, [10_000], [0], [1.0])


# ---------------------------------------------------------------------------
# CompletionProblem.append (both layouts)
# ---------------------------------------------------------------------------


M, N, P, Q, R = 96, 80, 3, 2, 4


@pytest.fixture(scope="module")
def split_ds():
    ds = lowrank_problem(M, N, R, density=0.25, seed=0)
    rr, cc = np.nonzero(ds.train_mask)
    vv = ds.x[rr, cc]
    rng = np.random.default_rng(1)
    perm = rng.permutation(len(rr))
    cut = int(0.8 * len(rr))
    return ds, (rr, cc, vv), (perm[:cut], perm[cut:])


def test_problem_append_layout_parity(split_ds):
    """Appending the same batch to the sparse and the dense layout yields
    the same problem: identical dense view, identical fit."""

    ds, (rr, cc, vv), (base, stream) = split_ds
    kw = dict(shape=(M, N), p=P, q=Q, rank=R)
    ps = CompletionProblem.from_entries(rr[base], cc[base], vv[base],
                                        headroom=256, **kw)
    pd = CompletionProblem.from_entries(rr[base], cc[base], vv[base],
                                        layout="dense", **kw)
    fs = ps.append(rr[stream], cc[stream], vv[stream])
    fd = pd.append(rr[stream], cc[stream], vv[stream])
    assert fs.layout == "sparse" and fd.layout == "dense"
    xa, ma = sparse.to_dense(fs.data, fs.spec.mb, fs.spec.nb)
    np.testing.assert_array_equal(xa, np.asarray(fd.data.xb))
    np.testing.assert_array_equal(ma, np.asarray(fd.data.maskb))
    np.testing.assert_array_equal(fs.seen_coo[0], fd.seen_coo[0])
    np.testing.assert_array_equal(fs.seen_coo[1], fd.seen_coo[1])
    cfg = GossipMCConfig(m=fs.spec.m, n=fs.spec.n, p=P, q=Q, rank=R)
    res_s = Trainer(cfg).fit(fs, Wave(num_rounds=2), seed=0)
    res_d = Trainer(cfg).fit(fd, Wave(num_rounds=2), seed=0)
    np.testing.assert_allclose(np.asarray(res_s.state.U),
                               np.asarray(res_d.state.U),
                               rtol=1e-5, atol=1e-5)


def test_problem_append_equals_full_ingest(split_ds):
    """Base-then-append equals ingesting the whole log at once (same
    capacity via headroom), including the seen-item table."""

    ds, (rr, cc, vv), (base, stream) = split_ds
    kw = dict(shape=(M, N), p=P, q=Q, rank=R)
    grown = CompletionProblem.from_entries(
        rr[base], cc[base], vv[base], headroom=256, **kw
    ).append(rr[stream], cc[stream], vv[stream])
    xa, ma = sparse.to_dense(grown.data, grown.spec.mb, grown.spec.nb)
    full = CompletionProblem.from_entries(rr, cc, vv, **kw)
    xb, mb = sparse.to_dense(full.data, full.spec.mb, full.spec.nb)
    np.testing.assert_array_equal(ma, mb)
    np.testing.assert_array_equal(xa, xb)
    np.testing.assert_array_equal(grown.seen_coo[0], full.seen_coo[0])
    np.testing.assert_array_equal(grown.seen_coo[1], full.seen_coo[1])


def test_problem_append_mean_center_and_validation(split_ds):
    ds, (rr, cc, vv), (base, stream) = split_ds
    prob = CompletionProblem.from_entries(
        rr[base], cc[base], vv[base], shape=(M, N), p=P, q=Q, rank=R,
        headroom=256, mean_center=True,
    )
    assert prob.mu != 0.0
    grown = prob.append(rr[stream], cc[stream], vv[stream])
    assert grown.mu == prob.mu                       # μ frozen at ingest
    xa, _ = sparse.to_dense(grown.data, grown.spec.mb, grown.spec.nb)
    r0, c0 = int(rr[stream][0]), int(cc[stream][0])
    got = xa[r0 // grown.spec.mb, c0 // grown.spec.nb,
             r0 % grown.spec.mb, c0 % grown.spec.nb]
    np.testing.assert_allclose(got, vv[stream][0] - prob.mu, rtol=1e-6)
    assert prob.append([], [], []) is prob
    with pytest.raises(ValueError, match="out of range"):
        prob.append([M + 5], [0], [1.0])             # new user -> re-ingest


# ---------------------------------------------------------------------------
# Trainer.refit + serve refresh
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fitted(split_ds):
    ds, (rr, cc, vv), (base, stream) = split_ds
    prob = CompletionProblem.from_entries(
        rr[base], cc[base], vv[base], shape=(M, N), p=P, q=Q, rank=R,
        headroom=256, dataset=ds,
    )
    cfg = GossipMCConfig(m=prob.spec.m, n=prob.spec.n, p=P, q=Q, rank=R,
                         a=1e-3, b=1e-5, rho=1e2)
    trainer = Trainer(cfg)
    result = trainer.fit(prob, Wave(num_rounds=40), seed=0)
    return trainer, prob, result


def test_refit_is_warm_start_fit(fitted, split_ds):
    """refit == fit(state=result.state) on the grown problem: the warm
    start is the whole trick, the schedule is a plain short Wave."""

    ds, (rr, cc, vv), (base, stream) = split_ds
    trainer, prob, result = fitted
    grown = prob.append(rr[stream], cc[stream], vv[stream])
    ref = trainer.refit(result, grown, num_rounds=5, seed=1)
    assert ref.schedule == "incremental"
    direct = trainer.fit(grown, Incremental(num_rounds=5), seed=1,
                         state=result.state)
    np.testing.assert_array_equal(np.asarray(ref.state.U),
                                  np.asarray(direct.state.U))
    np.testing.assert_array_equal(np.asarray(ref.state.W),
                                  np.asarray(direct.state.W))
    # the paper's clock carries over (γ_t keeps decaying) ...
    assert ref.t > result.t
    # ... unless reset_clock restarts the schedule
    ref0 = trainer.refit(result, grown, num_rounds=5, seed=1,
                         reset_clock=True)
    assert ref0.t < ref.t


def test_refit_beats_cold_fit_at_half_rounds():
    """The acceptance gate at test scale: from a *converged* base fit, a
    warm refit at a quarter of the rounds reaches the cold fit's held-out
    RMSE (±1e-3) after an append.  (examples/online_serving.py asserts the
    same gate at the quickstart size.)"""

    ds = lowrank_problem(M, N, R, density=0.5, seed=0)
    rr, cc = np.nonzero(ds.train_mask)
    vv = ds.x[rr, cc]
    rng = np.random.default_rng(1)
    perm = rng.permutation(len(rr))
    cut = int(0.9 * len(rr))
    base, stream = perm[:cut], perm[cut:]
    prob = CompletionProblem.from_entries(
        rr[base], cc[base], vv[base], shape=(M, N), p=P, q=Q, rank=R,
        headroom=512, dataset=ds,
    )
    cfg = GossipMCConfig(m=prob.spec.m, n=prob.spec.n, p=P, q=Q, rank=R,
                         a=2e-3, b=2e-5, rho=1e2)
    trainer = Trainer(cfg)
    result = trainer.fit(prob, Wave(num_rounds=400), seed=0)
    grown = prob.append(rr[stream], cc[stream], vv[stream])
    refit = trainer.refit(result, grown, num_rounds=100)
    cold = trainer.fit(grown, Wave(num_rounds=400), seed=0)
    assert refit.rmse() <= cold.rmse() + 1e-3


def test_refit_validates_problem(fitted):
    trainer, prob, result = fitted
    with pytest.raises(TypeError, match="CompletionProblem"):
        trainer.refit(result, prob.data)
    other = CompletionProblem.from_dense(
        np.zeros((M, N + Q), np.float32), np.ones((M, N + Q), np.float32),
        P, Q, R)
    with pytest.raises(ValueError, match="matching factor shapes"):
        trainer.refit(result, other)
    # defaults: problem = result.problem, schedule = Incremental
    again = trainer.refit(result, num_rounds=1)
    assert isinstance(make_schedule(again.schedule), Incremental)


def test_serve_refresh_hot_swap(fitted, split_ds):
    """RecommendService.refresh swaps factors + seen table in place: the
    appended pairs stop being served, the index matches the refit."""

    ds, (rr, cc, vv), (base, stream) = split_ds
    trainer, prob, result = fitted
    svc = result.to_service(k=5)
    old_index = svc.index
    grown = prob.append(rr[stream], cc[stream], vv[stream])
    refit = trainer.refit(result, grown, num_rounds=10)
    assert svc.refresh(refit) is svc
    assert svc.index is not old_index
    np.testing.assert_array_equal(np.asarray(svc.index.u),
                                  np.asarray(refit.to_recommend_index().u))
    # every appended (user, item) pair is now excluded from that user's top-k
    users = np.unique(rr[stream]).astype(np.int32)
    items, _ = svc.recommend(users)
    served = {int(u): set(row.tolist()) for u, row in zip(users, items)}
    for u, c in zip(rr[stream], cc[stream]):
        assert int(c) not in served[int(u)]


def test_index_refresh_rejects_reshaped_fit(fitted):
    trainer, prob, result = fitted
    index = result.to_recommend_index()
    small = CompletionProblem.from_dataset(
        lowrank_problem(M // 2, N // 2, R, density=0.3, seed=2),
        P, Q, R)
    cfg = GossipMCConfig(m=small.spec.m, n=small.spec.n, p=P, q=Q, rank=R)
    other = Trainer(cfg).fit(small, Wave(num_rounds=1), seed=0)
    with pytest.raises(ValueError, match="factor shapes"):
        index.refresh(other)
