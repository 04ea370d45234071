"""Sparse block engine: store round-trips and sorted-layout invariants,
sparse-vs-dense equivalence of objective/gradients (1e-5, segment and
scatter methods, with and without the dense masked tile), the rule that
builds the tile and its exact 1-byte encoding, SDDMM kernel vs oracle,
minibatch sampler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import GossipMCConfig
from repro.core import grid as G
from repro.core import objective as obj
from repro.core import sequential, waves
from repro.core.state import build_tables, init_state, make_problem
from repro.data import lowrank_problem
from repro.kernels.sddmm import sddmm_factor_grad, sddmm_factor_grad_ref
from repro import obs, sparse
from repro.sparse import objective as sparse_obj
from repro.sparse import store as store_mod

from _tile_paths import PATHS, on_path


def _problem(m=96, n=80, p=3, q=2, r=4, density=0.2, seed=0):
    spec = G.GridSpec(m, n, p, q, r)
    ds = lowrank_problem(m, n, r, density=density, seed=seed)
    prob = make_problem(ds.x, ds.train_mask, spec)
    sp = sparse.from_blocks(prob.xb, prob.maskb, bucket=64)
    cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)
    return spec, cfg, prob, sp


# ---------------------------------------------------------------------------
# Store round-trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("density", [0.0, 0.05, 0.3, 1.0])
def test_store_roundtrip(density):
    rng = np.random.default_rng(3)
    p, q, mb, nb = 2, 3, 10, 14
    mask = (rng.random((p, q, mb, nb)) < density).astype(np.float32)
    x = rng.normal(size=(p, q, mb, nb)).astype(np.float32) * mask
    sp = sparse.from_blocks(x, mask, bucket=32)
    assert sp.capacity % 32 == 0
    xb2, mb2 = sparse.to_dense(sp, mb, nb)
    np.testing.assert_array_equal(xb2, x)
    np.testing.assert_array_equal(mb2, mask)
    assert int(jnp.sum(sp.nnz)) == int(mask.sum())


def test_pad_blockify_unblockify_roundtrip():
    rng = np.random.default_rng(0)
    m, n, p, q = 37, 53, 4, 3                     # not divisible by the grid
    x = rng.normal(size=(m, n)).astype(np.float32)
    mask = (rng.random((m, n)) < 0.4).astype(np.float32)
    xp, mp_, mpad, npad = G.pad_to_grid(x, mask, p, q)
    assert mpad % p == 0 and npad % q == 0
    np.testing.assert_array_equal(xp[:m, :n], x)
    assert float(mp_[m:].sum()) == 0.0 and float(mp_[:, n:].sum()) == 0.0
    spec = G.GridSpec(mpad, npad, p, q, 2)
    xb, mb = G.blockify(xp, mp_, spec)
    np.testing.assert_array_equal(G.unblockify(xb, spec), xp)
    np.testing.assert_array_equal(G.unblockify(mb, spec), mp_)


def check_sorted_store_invariants(sp):
    """Shared sorted-store invariant checker (reused by
    tests/test_streaming.py on appended stores): per block, real entries in
    (row, col) lexicographic order with a non-decreasing padding tail,
    CSR/CSC offsets equal to per-row/col counts, and col_perm a valid
    column-sorted permutation whose padding slots never hit real entries."""

    rows, cols = np.asarray(sp.rows), np.asarray(sp.cols)
    nnz = np.asarray(sp.nnz)
    rptr, cptr = np.asarray(sp.row_ptr), np.asarray(sp.col_ptr)
    perm = np.asarray(sp.col_perm)
    mb, nb = sp.mb, sp.nb
    p, q = nnz.shape
    for i in range(p):
        for j in range(q):
            k = int(nnz[i, j])
            r_, c_ = rows[i, j, :k], cols[i, j, :k]
            # (row, col)-lexicographic order over the real entries, and the
            # padding tail (rows = mb-1) keeps the full stream non-decreasing
            # — the sorted-gather contract of the segment engine
            assert np.all(np.diff(rows[i, j]) >= 0)
            same_row = np.diff(r_) == 0
            assert np.all(np.diff(c_)[same_row] > 0)
            # CSR offsets == per-row counts; closing offset == nnz
            np.testing.assert_array_equal(
                np.diff(rptr[i, j]), np.bincount(r_, minlength=mb))
            assert rptr[i, j, 0] == 0 and rptr[i, j, -1] == k
            # CSC view: real entries hit exactly once, cols sorted
            pm = perm[i, j, :k]
            assert sorted(pm) == list(range(k))
            assert np.all(np.diff(c_[pm]) >= 0)
            np.testing.assert_array_equal(
                np.diff(cptr[i, j]), np.bincount(c_, minlength=nb))
            assert cptr[i, j, -1] == k
            # padding references in the dual view never hit real entries
            assert np.all(perm[i, j, k:] >= k)


@pytest.mark.parametrize("density,seed", [(0.0, 0), (0.07, 1), (0.4, 2), (1.0, 3)])
def test_from_blocks_sorted_layout_invariants(density, seed):
    """The store is segment-sorted: rows non-decreasing (cols within a row
    increasing), CSR/CSC offsets consistent with per-row/col counts, and
    col_perm a valid column-sorted view of the real entries."""

    rng = np.random.default_rng(seed)
    p, q, mb, nb = 2, 3, 11, 7
    mask = (rng.random((p, q, mb, nb)) < density).astype(np.float32)
    x = rng.normal(size=(p, q, mb, nb)).astype(np.float32) * mask
    sp = sparse.from_blocks(x, mask, bucket=32)
    check_sorted_store_invariants(sp)


def test_bucketed_capacity_guard():
    assert sparse.bucketed_capacity(100, 64) == 128
    assert sparse.bucketed_capacity(0, 64) == 64
    with pytest.raises(ValueError):
        sparse.bucketed_capacity(100, 0)
    with pytest.raises(ValueError):
        sparse.bucketed_capacity(100, -8)


def test_bucketed_capacity_accounts_for_headroom():
    """The capacity report includes the pre-allocated append slack: a store
    ingested with headroom=h is guaranteed ≥ h free slots per block."""

    assert sparse.bucketed_capacity(100, 64, headroom=0) == 128
    assert sparse.bucketed_capacity(100, 64, headroom=70) == 192
    assert sparse.bucketed_capacity(0, 64, headroom=1) == 64
    with pytest.raises(ValueError, match="headroom"):
        sparse.bucketed_capacity(100, 64, headroom=-1)

    spec, cfg, prob, sp = _problem(density=0.2)
    sp_h = sparse.from_blocks(prob.xb, prob.maskb, bucket=64, headroom=100)
    assert sp_h.capacity >= sp.capacity + 100 - 64      # slack really exists
    assert int(jnp.min(sp_h.free_slots)) >= 100
    # headroom is storage, not data: density must not see it
    assert sparse.density(sp_h, spec) == sparse.density(sp, spec)
    np.testing.assert_array_equal(np.asarray(sp_h.nnz), np.asarray(sp.nnz))


def test_density_block_shape_sources():
    spec, cfg, prob, sp = _problem(density=0.2)
    d_spec = sparse.density(sp, spec)                  # GridSpec overload
    d_self = sparse.density(sp)                        # store's own offsets
    d_ints = sparse.density(sp, spec.mb, spec.nb)      # legacy ints
    expected = float(np.asarray(prob.maskb).mean())
    np.testing.assert_allclose(d_spec, expected, rtol=1e-6)
    assert d_spec == d_self == d_ints
    with pytest.raises(TypeError):
        sparse.density(sp, spec.mb)                    # mb without nb


def test_from_dataset_matches_dense_problem():
    ds = lowrank_problem(50, 38, 3, density=0.25, seed=1)
    sp, spec = sparse.from_dataset(ds, p=3, q=2, r=3)
    xp, mp_, _, _ = G.pad_to_grid(ds.x, ds.train_mask, 3, 2)
    xb, mb = G.blockify(xp * mp_, mp_, spec)
    xb2, mb2 = sparse.to_dense(sp, spec.mb, spec.nb)
    np.testing.assert_array_equal(xb2, xb)
    np.testing.assert_array_equal(mb2, mb)


# ---------------------------------------------------------------------------
# Sparse == dense objective / gradients
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("pq,density,seed", [
    ((2, 2), 0.05, 0), ((3, 2), 0.2, 1), ((2, 4), 0.5, 2), ((4, 4), 0.1, 3),
])
def test_objective_matches_dense(pq, density, seed, path):
    p, q = pq
    spec, cfg, prob, sp = _problem(m=16 * p, n=12 * q, p=p, q=q,
                                   density=density, seed=seed)
    sp = on_path(sp, path)
    st = init_state(jax.random.PRNGKey(seed), spec)
    c_d = float(obj.total_cost(prob, st.U, st.W, cfg.lam))
    c_s = float(obj.total_cost(sp, st.U, st.W, cfg.lam))
    np.testing.assert_allclose(c_s, c_d, rtol=1e-5)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("pq,density,seed", [
    ((2, 2), 0.05, 0), ((3, 2), 0.2, 1), ((2, 4), 0.5, 2), ((4, 4), 0.1, 3),
])
def test_full_gradients_match_dense(pq, density, seed, path):
    p, q = pq
    spec, cfg, prob, sp = _problem(m=16 * p, n=12 * q, p=p, q=q,
                                   density=density, seed=seed)
    sp = on_path(sp, path)
    st = init_state(jax.random.PRNGKey(seed + 10), spec)
    gU_d, gW_d = waves.full_gradients(prob, st.U, st.W, rho=cfg.rho, lam=cfg.lam)
    gU_s, gW_s = waves.full_gradients(sp, st.U, st.W, rho=cfg.rho, lam=cfg.lam)
    scale = float(jnp.max(jnp.abs(gU_d))) + 1e-12
    np.testing.assert_allclose(np.asarray(gU_s), np.asarray(gU_d),
                               rtol=1e-5, atol=1e-5 * scale)
    scale = float(jnp.max(jnp.abs(gW_d))) + 1e-12
    np.testing.assert_allclose(np.asarray(gW_s), np.asarray(gW_d),
                               rtol=1e-5, atol=1e-5 * scale)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_segment_and_scatter_methods_agree_with_dense(use_kernel, path):
    """Sorted (segment), unsorted (scatter) and dense ∇L agree at 1e-5; the
    Pallas implementations of both methods agree too (interpret on CPU),
    whether or not the store carries its dense tile."""

    spec, cfg, prob, sp = _problem(m=48, n=36, p=3, q=2, density=0.15, seed=4)
    sp = on_path(sp, path)
    st = init_state(jax.random.PRNGKey(21), spec)
    gd = waves.full_gradients(prob, st.U, st.W, rho=cfg.rho, lam=cfg.lam)
    for method in ("segment", "scatter"):
        gs = sparse_obj.full_gradients_sparse(
            sp, st.U, st.W, rho=cfg.rho, lam=cfg.lam,
            use_kernel=use_kernel, method=method,
        )
        for a, b in zip(gs, gd):
            scale = float(jnp.max(jnp.abs(b))) + 1e-12
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5 * scale)
    with pytest.raises(ValueError):
        sparse_obj.f_grads_sparse(
            sp.entries.gather(0, 0), st.U[0, 0], st.W[0, 0], method="csr",
        )


def test_f_grads_sparse_legacy_positional_shape_warns():
    """The pre-BlockEntries 9-positional signature still works but warns."""

    spec, cfg, prob, sp = _problem(m=48, n=36, p=3, q=2, density=0.15, seed=4)
    st = init_state(jax.random.PRNGKey(21), spec)
    # the positional shape carries no dense tile: it takes the segment path
    want = sparse_obj.f_grads_sparse(sp.entries.gather(0, 0).without_tile(),
                                     st.U[0, 0], st.W[0, 0])
    with pytest.warns(DeprecationWarning):
        got = sparse_obj.f_grads_sparse(
            sp.rows[0, 0], sp.cols[0, 0], sp.vals[0, 0], sp.valid[0, 0],
            sp.col_perm[0, 0], sp.row_ptr[0, 0], sp.col_ptr[0, 0],
            st.U[0, 0], st.W[0, 0],
        )
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("path", PATHS)
def test_sequential_step_matches_dense(path):
    """Same PRNG key -> same sampled structure -> identical update."""

    spec, cfg, prob, sp = _problem()
    sp = on_path(sp, path)
    st = init_state(jax.random.PRNGKey(2), spec)
    tables = build_tables(spec.p, spec.q, G.enumerate_structures(spec.p, spec.q))
    k = jax.random.PRNGKey(7)
    kw = dict(rho=cfg.rho, lam=cfg.lam, a=cfg.a, b=cfg.b)
    st_d = sequential.sgd_structure_step(prob, st, tables, k, **kw)
    st_s = sequential.sgd_structure_step(sp, st, tables, k, **kw)
    np.testing.assert_allclose(np.asarray(st_s.U), np.asarray(st_d.U),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_s.W), np.asarray(st_d.W),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", PATHS)
def test_wave_fit_sparse_layout_matches_dense(path):
    spec, cfg, prob, sp = _problem()
    key = jax.random.PRNGKey(0)
    st_d, hist_d = waves._fit(prob, spec, cfg, key, num_rounds=3)
    st_s, hist_s = waves._fit(on_path(sparse.ensure_layout(prob, "sparse"),
                                      path),
                              spec, cfg, key, num_rounds=3, layout="sparse")
    np.testing.assert_allclose(np.asarray(st_s.U), np.asarray(st_d.U),
                               rtol=1e-5, atol=1e-5)
    assert hist_s[-1][0] == hist_d[-1][0]
    np.testing.assert_allclose(hist_s[-1][1], hist_d[-1][1], rtol=1e-5)


# ---------------------------------------------------------------------------
# The dense masked tile: every f-term entry point, and the rule
# ---------------------------------------------------------------------------


def _padded_problem(r=3, seed=5):
    """50 x 37 ratings on a 3 x 2 grid (padding rows and columns), with
    block (2, 1) left without a single rating: an all-padding block."""

    m, n, p, q = 50, 37, 3, 2
    rng = np.random.default_rng(seed)
    mask = rng.random((m, n)) < 0.3
    mask[34:, 19:] = False                      # block (2, 1): mb 17, nb 19
    rows, cols = np.nonzero(mask)
    vals = rng.normal(size=len(rows)).astype(np.float32)
    sp, (mp, n_p) = sparse.from_entries(rows, cols, vals, m, n, p, q,
                                        bucket=64)
    spec = G.GridSpec(mp, n_p, p, q, r)
    x = np.zeros((m, n), np.float32)
    x[rows, cols] = vals
    xp, mpad, _, _ = G.pad_to_grid(x, mask.astype(np.float32), p, q)
    prob = make_problem(xp, mpad, spec)
    return spec, prob, sp


def _close(a, b, rtol=1e-5):
    a, b = np.asarray(a), np.asarray(b)
    scale = float(np.max(np.abs(b))) + 1e-12
    np.testing.assert_allclose(a, b, rtol=rtol, atol=rtol * scale)


@pytest.mark.parametrize("path", PATHS)
def test_f_term_entry_points_match_dense_with_grid_padding(path):
    """f_grads_sparse, f_cost_sparse, structure_grads_sparse,
    full_gradients_sparse and total_report_cost_sparse against the dense
    Problem at 1e-5, on a padded grid with an all-padding block, with the
    dense tile and without it."""

    spec, prob, sp = _padded_problem()
    sp = on_path(sp, path)
    assert int(sp.nnz[2, 1]) == 0
    st = init_state(jax.random.PRNGKey(3), spec)
    for i in range(spec.p):
        for j in range(spec.q):
            ent = sp.entries.gather(i, j)
            u, w = st.U[i, j], st.W[i, j]
            want = obj.f_grads(prob.xb[i, j], prob.maskb[i, j], u, w)
            got = sparse_obj.f_grads_sparse(ent, u, w)
            for a, b in zip(got, want):
                _close(a, b)
            _close(sparse_obj.f_cost_sparse(ent, u, w),
                   obj.f_cost(prob.xb[i, j], prob.maskb[i, j], u, w))
    _, gu, gw = sparse_obj.f_grads_sparse(sp.entries.gather(2, 1),
                                          st.U[2, 1], st.W[2, 1])
    assert float(jnp.abs(gu).max()) == 0.0 == float(jnp.abs(gw).max())

    tables = build_tables(spec.p, spec.q,
                          G.enumerate_structures(spec.p, spec.q))
    for s in range(tables.blocks.shape[0]):
        bi, bj = tables.blocks[s, :, 0], tables.blocks[s, :, 1]
        args = (st.U[bi, bj], st.W[bi, bj], tables.cf[s], tables.cu[s],
                tables.cw[s])
        want = obj.structure_grads(prob.xb[bi, bj], prob.maskb[bi, bj],
                                   *args, rho=1.0, lam=0.1)
        got = obj.structure_grads_sparse(sp.entries.gather(bi, bj), *args,
                                         rho=1.0, lam=0.1)
        for a, b in zip(got, want):
            _close(a, b)

    gd = waves.full_gradients(prob, st.U, st.W, rho=1.0, lam=0.1)
    gs = sparse_obj.full_gradients_sparse(sp, st.U, st.W, rho=1.0, lam=0.1)
    for a, b in zip(gs, gd):
        _close(a, b)
    _close(sparse_obj.total_report_cost_sparse(sp, st.U, st.W, 0.1),
           obj.total_report_cost(prob.xb, prob.maskb, st.U, st.W, 0.1))


def test_tile_and_segment_paths_agree_in_every_wave():
    """One wave_step of every wave: the tile path (blocks sliced one by one
    inside their products) against the segment path and the dense one."""

    spec, cfg, prob, sp = _problem(m=64, n=48, p=4, q=4, density=0.3,
                                   seed=6)
    st = init_state(jax.random.PRNGKey(4), spec)
    kw = dict(rho=cfg.rho, lam=cfg.lam, a=1e-2, b=cfg.b)
    for tables in waves.wave_tables(spec.p, spec.q):
        tile = waves.wave_step(on_path(sp, "tile"), st, tables, **kw)
        seg = waves.wave_step(on_path(sp, "segment"), st, tables, **kw)
        dense = waves.wave_step(prob, st, tables, **kw)
        for a, b, c in zip(tile[:2], seg[:2], dense[:2]):
            _close(a, c)
            _close(b, c)
        assert int(tile.t) == int(seg.t) == int(dense.t)


def test_scatter_method_and_kernel_keep_their_engine():
    """A store with a tile still honours an explicit engine: ``scatter``
    and ``use_kernel`` never take the tile, the default does."""

    spec, cfg, prob, sp = _problem(m=48, n=36, p=3, q=2, density=0.15, seed=4)
    sp = on_path(sp, "tile")
    ent = sp.entries
    assert sparse_obj.takes_tile(ent)
    assert not sparse_obj.takes_tile(ent, method="scatter")
    assert not sparse_obj.takes_tile(ent, use_kernel=True)
    assert not sparse_obj.takes_tile(ent.without_tile())


def test_tile_rule_reads_density_backend_and_memory():
    rule = store_mod.tile_rule
    cpu = store_mod.TILE_CROSSOVER["cpu"]
    mb, nb = 100, 80
    above = int(np.ceil(cpu * mb * nb))
    for enc in ("codes", "f32"):
        assert rule(above, mb, nb, 16, "cpu", None, enc)
        assert not rule(above - 1, mb, nb, 16, "cpu", None, enc)
        assert not rule(above, mb, nb, 16, "no-such-backend", None, enc)
    # padded to whole layout tiles of the dtype: 104 rows of 32 bits,
    # 128 rows of 8; a float32 value and a bool mask, or one code a cell
    assert store_mod.tile_shape(mb, nb, "f32") == (104, 128)
    assert store_mod.tile_shape(mb, nb, "codes") == (128, 128)
    share = store_mod.TILE_MEMORY_SHARE
    for enc, tiles in (("f32", 16 * 104 * 128 * 5),
                       ("codes", 16 * 128 * 128 * 1)):
        assert store_mod.TILE_BYTES_PER_CELL[enc] * 16 * np.prod(
            store_mod.tile_shape(mb, nb, enc)) == tiles
        assert rule(above, mb, nb, 16, "cpu", int(np.ceil(tiles / share)),
                    enc)
        assert not rule(above, mb, nb, 16, "cpu", int(tiles / share) - 1,
                        enc)
    tpu = store_mod.TILE_CROSSOVER["tpu"]
    for enc in ("codes", "f32"):                              # ML-1M, v5e
        assert rule(61440, 1510, 927, 16, "tpu", 16 * 2**30, enc)
        assert not rule(int(tpu * 1510 * 927) - 1, 1510, 927, 16, "tpu",
                        None, enc)
    # ML-10M on one v5e: 16 code tiles take 0.77 GB, under 1/8 of the
    # chip; float32 tiles and their mask (3.85 GB) do not fit
    assert rule(573440, 17892, 2671, 16, "tpu", 16 * 2**30, "codes")
    assert not rule(573440, 17892, 2671, 16, "tpu", 16 * 2**30, "f32")


def test_ingest_builds_tile_by_the_rule(monkeypatch):
    """Above the crossover every block carries its tile and the gauge
    counts them; below it, or over the memory share, none does."""

    ds = lowrank_problem(48, 36, 3, density=0.2, seed=2)
    rows, cols = np.nonzero(ds.train_mask)
    vals = ds.x[rows, cols]

    def ingest():
        sp, _ = sparse.from_entries(rows, cols, vals, 48, 36, 3, 2,
                                    bucket=32)
        return sp, obs.gauge("ingest_tile_blocks").value

    monkeypatch.setitem(store_mod.TILE_CROSSOVER, "cpu", 0.01)
    sp, gauge = ingest()
    assert sp.has_tile and gauge == 6
    # continuous values fit no code grid: the float32 tile and its mask
    assert sp.entries.tile_encoding == "f32"
    assert store_mod.tile_shape(16, 18, "f32") == (16, 128)  # layout tiles
    assert sp.entries.tile_vals.shape == (3, 2, 16, 128)
    assert sp.entries.tile_mask.dtype == jnp.bool_
    xb, maskb = sparse.to_dense(sp)
    tv = np.asarray(sp.entries.tile_vals)
    tm = np.asarray(sp.entries.tile_mask)
    np.testing.assert_array_equal(tv[..., :18], xb)
    np.testing.assert_array_equal(tm[..., :18], maskb > 0)
    assert not tm[..., 18:].any() and not tv[..., 18:].any()
    np.testing.assert_array_equal(
        np.asarray(sparse.with_tile(sparse.drop_tile(sp)).entries.tile_vals),
        tv)

    monkeypatch.setattr(store_mod, "device_bytes_limit", lambda: 1000)
    sp, gauge = ingest()
    assert not sp.has_tile and gauge == 0

    monkeypatch.setattr(store_mod, "device_bytes_limit", lambda: None)
    monkeypatch.setitem(store_mod.TILE_CROSSOVER, "cpu", 2.0)
    sp, gauge = ingest()
    assert not sp.has_tile and gauge == 0


# ---------------------------------------------------------------------------
# The tile's exact 1-byte encoding
# ---------------------------------------------------------------------------


def _rated(levels, m=60, n=44, count=900, seed=0):
    """COO ratings drawn from ``levels`` minus their float32 mean, as
    ``CompletionProblem.from_entries(mean_center=True)`` stores them."""

    rng = np.random.default_rng(seed)
    lin = rng.choice(m * n, count, replace=False)
    vals = np.asarray(levels, np.float32)[rng.integers(0, len(levels),
                                                       count)]
    vals[:len(levels)] = levels                     # every level is drawn
    return lin // n, lin % n, vals - float(vals.mean())


HALF_STARS = np.arange(1, 11) * 0.5
STARS = np.arange(1, 6, dtype=np.float64)


@pytest.mark.parametrize("levels,step", [(HALF_STARS, 0.5), (STARS, 1.0)],
                         ids=["half-stars", "integers"])
def test_code_grid_round_trips_centred_ratings(levels, step):
    """Centred half-star and integer ratings fit one grid, and every
    stored value decodes from its code to itself bit for bit."""

    _, _, vals = _rated(levels)
    lo, got = store_mod.code_grid(vals)
    assert got == np.float32(step) and lo == vals.min()
    codes, ok = store_mod.encode(vals, lo, got)
    assert ok.all() and codes.dtype == np.uint8
    assert codes.min() == 1 and codes.max() == len(levels)
    back = lo + (codes.astype(np.float32) - 1) * got
    np.testing.assert_array_equal(back.view(np.uint32),
                                  vals.view(np.uint32))


@pytest.mark.parametrize("case", ["off-grid", "over-255-levels",
                                  "continuous"])
def test_code_grid_falls_back_to_float32_tile(case):
    """Values no 255-level grid holds exactly fit no codes: ``code_grid``
    says so, and an ingest above the crossover keeps the float32 tile and
    its mask, every value as given (none is rounded onto a grid)."""

    rows, cols, vals = _rated(HALF_STARS)
    if case == "off-grid":
        vals[7] += np.float32(0.1)          # 0.1 off a half star
    elif case == "over-255-levels":
        vals = np.arange(len(vals), dtype=np.float32) * 0.25   # 900 levels
    else:
        vals = np.random.default_rng(1).normal(size=len(vals)).astype(
            np.float32)
    assert store_mod.code_grid(vals) is None
    sp, _ = sparse.from_entries(rows, cols, vals, 60, 44, 3, 2, bucket=32)
    sp = sparse.with_tile(sp)
    assert sp.entries.tile_encoding == "f32"
    assert sp.entries.tile_codes is None and sp.entries.tile_grid is None
    xb, maskb = sparse.to_dense(sp)
    tv = np.asarray(sp.entries.tile_vals)[..., :sp.mb, :sp.nb]
    np.testing.assert_array_equal(tv.view(np.uint32), xb.view(np.uint32))
    with pytest.raises(ValueError, match="no 1-byte code grid"):
        sparse.with_tile(sparse.drop_tile(sp), "codes")


def test_code_grid_edge_cases():
    """A single value or none still has a grid; an empty store ingests a
    tile of unobserved cells; a gap lost to float32 rounding has none."""

    assert store_mod.code_grid(np.float32([2.5, 2.5])) == (2.5, 1.0)
    assert store_mod.code_grid(np.float32([])) == (0.0, 1.0)
    tiny = np.float32([1.0, np.nextafter(np.float32(1), np.float32(2))])
    assert store_mod.code_grid(tiny) is not None          # 2 levels, exact
    assert store_mod.code_grid(np.float32([0, 1e-40])) is None  # denormal
    assert store_mod.code_grid(np.float32([0, np.nan])) is None
    assert store_mod.code_grid(np.float32([0, np.inf])) is None


@pytest.mark.parametrize("case,enc,nbytes", [
    ("half-stars", "codes", 1), ("off-grid", "f32", 5),
    ("below-crossover", None, 0)])
def test_ingest_tile_bytes_per_cell_gauge(monkeypatch, case, enc, nbytes):
    """Every ingest sets ``ingest_tile_bytes_per_cell`` beside
    ``ingest_tile_blocks``: 1 for a code tile, 5 for the float32 tile and
    its mask, 0 for no tile."""

    rows, cols, vals = _rated(HALF_STARS)
    if case == "off-grid":
        vals[3] += np.float32(0.01)
    monkeypatch.setitem(store_mod.TILE_CROSSOVER, "cpu",
                        2.0 if case == "below-crossover" else 0.0)
    sp, _ = sparse.from_entries(rows, cols, vals, 60, 44, 3, 2, bucket=32)
    assert sp.entries.tile_encoding == enc
    assert obs.gauge("ingest_tile_bytes_per_cell").value == nbytes
    assert obs.gauge("ingest_tile_blocks").value == (6 if enc else 0)
    if enc == "codes":
        tc = sp.entries.tile_codes
        assert tc.dtype == jnp.uint8 and tc.shape == (3, 2, 32, 128)
        assert sp.entries.tile_grid.shape == (3, 2, 2)
        xb, maskb = sparse.to_dense(sp)
        codes = np.asarray(tc)[..., :sp.mb, :sp.nb]
        np.testing.assert_array_equal(codes > 0, maskb > 0)
        assert not np.asarray(tc)[..., sp.mb:, :].any()
        assert not np.asarray(tc)[..., sp.nb:].any()


def test_code_tile_memory_rule_admits_what_float32_would_not(monkeypatch):
    """At a device memory the float32 tile passes and the code tile does
    not, ingest of on-grid values builds the code tile."""

    rows, cols, vals = _rated(HALF_STARS)
    monkeypatch.setitem(store_mod.TILE_CROSSOVER, "cpu", 0.0)
    f32_bytes = 6 * 24 * 128 * 5            # mb 20 -> 24 rows of 32 bits
    monkeypatch.setattr(store_mod, "device_bytes_limit",
                        lambda: int(f32_bytes / store_mod.TILE_MEMORY_SHARE)
                        - 1)
    sp, _ = sparse.from_entries(rows, cols, vals, 60, 44, 3, 2, bucket=32)
    assert sp.entries.tile_encoding == "codes"
    vals[3] += np.float32(0.01)
    sp, _ = sparse.from_entries(rows, cols, vals, 60, 44, 3, 2, bucket=32)
    assert not sp.has_tile


def _three_paths(levels, seed):
    rows, cols, vals = _rated(levels, seed=seed)
    sp, _ = sparse.from_entries(rows, cols, vals, 60, 44, 3, 2, bucket=32)
    return (sparse.with_tile(sp, "codes"),
            sparse.with_tile(sparse.drop_tile(sp), "f32"),
            sparse.drop_tile(sp))


@pytest.mark.parametrize("levels,seed", [(HALF_STARS, 0), (STARS, 1)],
                         ids=["half-stars", "integers"])
def test_code_tile_terms_equal_float32_tile_bit_for_bit(levels, seed):
    """A code tile's (f, gU, gW), one block per loop step as ``wave_step``
    visits them, and its chunk cost equal the float32 tile's of the same
    store bit for bit (the decode gives the stored values exactly and the
    f-term sums the block's own cells whatever the padding), and the
    segment engine's within 1e-5."""

    codes, f32, seg = _three_paths(levels, seed)
    key = jax.random.PRNGKey(seed)
    ku, kw = jax.random.split(key)
    U = jax.random.normal(ku, (3, 2, codes.mb, 4))
    W = jax.random.normal(kw, (3, 2, codes.nb, 4))
    bi = jnp.array([[0, 1, 2], [2, 0, 1]])
    bj = jnp.array([[0, 1, 1], [0, 0, 1]])
    u3, w3 = U[bi, bj], W[bi, bj]
    a = sparse_obj.f_grads_at(codes.entries, bi, bj, u3, w3)
    b = sparse_obj.f_grads_at(f32.entries, bi, bj, u3, w3)
    c = sparse_obj.f_grads_at(seg.entries, bi, bj, u3, w3)
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        _close(x, z)
    def block(sp):                          # one block, vmap-style
        one = jax.tree.map(lambda t: t[1, 0], sp.entries)
        return sparse_obj.f_grads_sparse(one, U[1, 0], W[1, 0])

    for x, y in zip(block(codes), block(f32)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    costs = [sparse_obj.total_report_cost_sparse(s, U, W, 0.1)
             for s in (codes, f32, seg)]
    assert float(costs[0]) == float(costs[1])
    _close(costs[0], costs[2])


def test_minibatch_and_placed_stores_carry_no_tile():
    from repro.mesh import MeshPlan, build_mesh
    from repro.sparse.sharded import ShardedEntries

    spec, cfg, prob, sp = _problem(m=48, n=36, p=3, q=2, density=0.3, seed=1)
    sp = on_path(sp, "tile")
    key = jax.random.PRNGKey(0)
    assert not sparse.sample_minibatch(key, sp, 16).has_tile
    assert not sparse.MinibatchStream(sp, 16, seed=0).batch_at(3).has_tile
    plan = MeshPlan.build(3, 2, mesh=build_mesh((1, 1), ("data", "model")))
    assert not plan.place_entries(sp).has_tile
    assert not ShardedEntries.from_problem(sp, plan).sp.has_tile
    ds = lowrank_problem(48, 36, 3, density=0.3, seed=1)
    rows, cols = np.nonzero(ds.train_mask)
    sharded, _ = ShardedEntries.from_coo(rows, cols, ds.x[rows, cols],
                                         48, 36, plan)
    assert not sharded.sp.has_tile
    appended = sharded.append(rows[:3], (cols[:3] + 1) % 36,
                              np.ones(3, np.float32))
    assert not appended.sp.has_tile


def test_grad_engine_counter_counts_the_path_each_fit_takes():
    import dataclasses

    from repro.mc import CompletionProblem, FullGD, Gossip, Trainer, Wave

    ds = lowrank_problem(48, 36, 3, density=0.3, seed=1)
    prob = CompletionProblem.from_dataset(ds, 3, 2, 3, layout="sparse")
    tiled = dataclasses.replace(prob, data=on_path(prob.data, "tile"))
    segment = dataclasses.replace(prob, data=on_path(prob.data, "segment"))
    dense = prob.with_layout("dense")

    def count(path):
        return obs.counter("grad_engine_fits_total", path=path).value

    cases = [(tiled, Wave(num_rounds=1), "tile"),
             (tiled, FullGD(num_rounds=1), "tile"),
             (segment, Wave(num_rounds=1), "segment"),
             (tiled.with_engine(method="scatter"), Wave(num_rounds=1),
              "scatter"),
             (tiled, Gossip(num_rounds=1), "segment"),
             (dense, Wave(num_rounds=1), "dense")]
    for problem, sched, path in cases:
        before = count(path)
        Trainer().fit(problem, sched, seed=0)
        assert count(path) == before + 1, (sched, path)


def test_ensure_layout():
    spec, cfg, prob, sp = _problem()
    assert sparse.ensure_layout(sp, None) is sp         # inferred from type
    assert sparse.ensure_layout(prob, None) is prob
    assert sparse.ensure_layout(sp, "sparse") is sp
    assert sparse.ensure_layout(prob, "dense") is prob
    conv = sparse.ensure_layout(prob, "sparse")
    assert isinstance(conv, sparse.SparseProblem)
    with pytest.raises(ValueError):
        sparse.ensure_layout(sp, "dense")
    with pytest.raises(ValueError):
        sparse.ensure_layout(prob, "csr")


# ---------------------------------------------------------------------------
# SDDMM kernel vs oracle (interpret mode on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,N,r,density", [
    (8, 8, 1, 0.5), (60, 90, 5, 0.1), (128, 128, 16, 0.05),
    (33, 257, 3, 0.3), (256, 100, 8, 0.02),
])
def test_sddmm_kernel_matches_ref(M, N, r, density):
    rng = np.random.default_rng(M + N + r)
    mask = rng.random((M, N)) < density
    rr, cc = np.nonzero(mask)
    E = max(128, (len(rr) + 127) // 128 * 128)
    rows = np.zeros(E, np.int32)
    cols = np.zeros(E, np.int32)
    vals = np.zeros(E, np.float32)
    valid = np.zeros(E, np.float32)
    rows[: len(rr)], cols[: len(rr)] = rr, cc
    vals[: len(rr)] = rng.normal(size=len(rr)).astype(np.float32)
    valid[: len(rr)] = 1.0
    u = rng.normal(size=(M, r)).astype(np.float32)
    w = rng.normal(size=(N, r)).astype(np.float32)

    entries = sparse.BlockEntries.from_coo(rows, cols, vals, valid)
    l1, gu1, gw1 = sddmm_factor_grad_ref(entries, u, w)
    l2, gu2, gw2 = sddmm_factor_grad(entries, u, w)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gu2), np.asarray(gu1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw2), np.asarray(gw1),
                               rtol=1e-4, atol=1e-4)


def test_sddmm_all_padding_is_zero():
    E, M, N, r = 128, 16, 16, 4
    z = np.zeros(E, np.float32)
    u = np.ones((M, r), np.float32)
    w = np.ones((N, r), np.float32)
    loss, gu, gw = sddmm_factor_grad(
        sparse.BlockEntries.from_coo(z.astype(np.int32), z.astype(np.int32),
                                     z, z), u, w
    )
    assert float(loss) == 0.0
    assert float(np.abs(gu).max()) == 0.0
    assert float(np.abs(gw).max()) == 0.0


# ---------------------------------------------------------------------------
# Minibatch sampler
# ---------------------------------------------------------------------------


def test_minibatch_samples_only_observed_entries():
    spec, cfg, prob, sp = _problem(density=0.15)
    mb = sparse.sample_minibatch(jax.random.PRNGKey(5), sp, 32)
    assert mb.rows.shape == (spec.p, spec.q, 32)
    xb, maskb = np.asarray(prob.xb), np.asarray(prob.maskb)
    rows, cols = np.asarray(mb.rows), np.asarray(mb.cols)
    vals, valid = np.asarray(mb.vals), np.asarray(mb.valid)
    for i in range(spec.p):
        for j in range(spec.q):
            for k in range(32):
                if valid[i, j, k]:
                    assert maskb[i, j, rows[i, j, k], cols[i, j, k]] == 1.0
                    assert vals[i, j, k] == xb[i, j, rows[i, j, k], cols[i, j, k]]


def test_minibatch_stream_is_restart_exact():
    spec, cfg, prob, sp = _problem()
    s1 = sparse.MinibatchStream(sp, batch=16, seed=3)
    s2 = sparse.MinibatchStream(sp, batch=16, seed=3)
    a = s1.batch_at(7)
    b = s2.batch_at(7)
    np.testing.assert_array_equal(np.asarray(a.rows), np.asarray(b.rows))
    np.testing.assert_array_equal(np.asarray(a.vals), np.asarray(b.vals))
    c = s1.batch_at(8)
    assert not np.array_equal(np.asarray(a.rows), np.asarray(c.rows))


def test_minibatch_grad_scale():
    spec, cfg, prob, sp = _problem()
    scale = sparse.minibatch_grad_scale(sp, 16)
    np.testing.assert_allclose(
        np.asarray(scale), np.asarray(sp.nnz, np.float32) / 16.0
    )


def test_minibatch_stream_batch_at_identical_across_instances():
    """batch_at(step) is a pure function of (seed, step): every field of the
    sampled store — including the sorted-layout offsets — replays exactly."""

    spec, cfg, prob, sp = _problem(density=0.3, seed=5)
    s1 = sparse.MinibatchStream(sp, batch=24, seed=11)
    s2 = sparse.MinibatchStream(sp, batch=24, seed=11)
    for step in (0, 3, 1000):
        a, b = s1.batch_at(step), s2.batch_at(step)
        for fa, fb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    other = sparse.MinibatchStream(sp, batch=24, seed=12).batch_at(3)
    assert not np.array_equal(np.asarray(other.rows),
                              np.asarray(s1.batch_at(3).rows))


def test_minibatch_sorted_batch_invariants():
    """Minibatches stay on the segment-reduce fast path: rows non-decreasing,
    CSR/CSC offsets consistent with the sampled entries, nnz == batch for
    non-empty blocks."""

    spec, cfg, prob, sp = _problem(density=0.15, seed=6)
    batch = 40
    mbat = sparse.sample_minibatch(jax.random.PRNGKey(9), sp, batch)
    rows = np.asarray(mbat.rows)
    cols = np.asarray(mbat.cols)
    rptr = np.asarray(mbat.row_ptr)
    cptr = np.asarray(mbat.col_ptr)
    perm = np.asarray(mbat.col_perm)
    nnz = np.asarray(mbat.nnz)
    assert rptr.shape == (spec.p, spec.q, spec.mb + 1)
    assert cptr.shape == (spec.p, spec.q, spec.nb + 1)
    for i in range(spec.p):
        for j in range(spec.q):
            r_, c_ = rows[i, j], cols[i, j]
            assert nnz[i, j] == batch          # no empty blocks at this density
            assert np.all(np.diff(r_) >= 0)    # row-sorted draw
            np.testing.assert_array_equal(
                np.diff(rptr[i, j]), np.bincount(r_, minlength=spec.mb))
            assert rptr[i, j, -1] == batch
            pm = perm[i, j]
            assert sorted(pm) == list(range(batch))
            assert np.all(np.diff(c_[pm]) >= 0)
            np.testing.assert_array_equal(
                np.diff(cptr[i, j]), np.bincount(c_, minlength=spec.nb))


def test_minibatch_empty_block_sampling():
    """A block with no observations samples all-invalid slots, zero nnz, and
    a zero f-gradient through the segment path."""

    from repro.sparse import objective as sparse_obj

    rng = np.random.default_rng(0)
    p, q, mb, nb, r = 2, 2, 12, 10, 3
    mask = (rng.random((p, q, mb, nb)) < 0.3).astype(np.float32)
    mask[0, 1] = 0.0                               # empty block
    x = rng.normal(size=(p, q, mb, nb)).astype(np.float32) * mask
    sp = sparse.from_blocks(x, mask, bucket=32)
    batch = 16
    mbat = sparse.sample_minibatch(jax.random.PRNGKey(1), sp, batch)
    assert int(mbat.nnz[0, 1]) == 0
    assert float(jnp.sum(mbat.valid[0, 1])) == 0.0
    U = jnp.asarray(rng.normal(size=(p, q, mb, r)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(p, q, nb, r)), jnp.float32)
    gU, gW = sparse_obj.full_gradients_sparse(mbat, U, W, rho=0.0, lam=0.0)
    assert float(jnp.max(jnp.abs(gU[0, 1]))) == 0.0
    assert float(jnp.max(jnp.abs(gW[0, 1]))) == 0.0
    # non-empty blocks: segment and scatter agree on the sampled batch
    gU2, gW2 = sparse_obj.full_gradients_sparse(
        mbat, U, W, rho=0.0, lam=0.0, method="scatter")
    np.testing.assert_allclose(np.asarray(gU), np.asarray(gU2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gW), np.asarray(gW2),
                               rtol=1e-5, atol=1e-5)
