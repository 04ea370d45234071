"""The segment engine for tile-less blocks at deployment shapes.

``segment_reduce`` sums contiguous segments hierarchically (a within-chunk
prefix, one gathered row per boundary, the chunk totals one level up), so
every prefix difference spans one chunk of its level.  These tests hold
it, and the gradient built on it, to float64 sums, the order-agnostic
scatter oracle and the dense masked path: at rank 64, with rows longer
than a chunk, empty rows and columns, padding slots and a block at full
capacity; at the ML-10M block's size; and through ``Trainer.fit`` Wave
rounds against the dense layout and Algorithm 1.  All on the CPU, with
seeded random factors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sparse
from repro.config import GossipMCConfig
from repro.core import grid as G
from repro.core import objective as obj
from repro.core import sequential, waves
from repro.core.state import State, build_tables
from repro.kernels.sddmm import (
    sddmm_factor_grad_ref,
    sddmm_segment_grad_ref,
    segment_reduce,
)
from repro.kernels.sddmm.autotune import FALLBACK_CHUNK
from repro.mc import CompletionProblem, Trainer, Wave
from repro.mc.problem import EngineOptions
from repro.sparse import objective as sparse_obj


def _close(a, b, rtol=1e-5):
    a, b = (np.asarray(v).astype(np.float64) for v in (a, b))
    scale = float(np.max(np.abs(b))) + 1e-12
    np.testing.assert_allclose(a, b, rtol=rtol, atol=rtol * scale)


def _block(M, N, r, nnz, capacity, seed, long_row=0, empty=()):
    """One block's store: ``nnz`` random entries at ``capacity`` slots,
    row 0 holding ``long_row`` of them, the rows and columns in ``empty``
    holding none; with random rank-``r`` factors and the dense twin."""

    rng = np.random.default_rng(seed)
    free = np.ones((M, N), bool)
    for k in empty:
        free[k, :] = free[:, k] = False
    lin = np.flatnonzero(free[1:]) + N
    lin = rng.choice(lin, nnz - long_row, replace=False)
    lin = np.concatenate([np.flatnonzero(free[0])[:long_row], lin])
    vals = rng.normal(size=nnz).astype(np.float32)
    sp, _ = sparse.from_entries(lin // N, lin % N, vals, M, N, 1, 1,
                                bucket=capacity, headroom=capacity - nnz)
    entries = jax.tree.map(lambda a: a[0, 0], sparse.drop_tile(sp).entries)
    u = (rng.normal(size=(M, r)) / np.sqrt(r)).astype(np.float32)
    w = (rng.normal(size=(N, r)) / np.sqrt(r)).astype(np.float32)
    xb, maskb = sparse.to_dense(sp)
    return entries, u, w, xb[0, 0], maskb[0, 0]


@pytest.mark.parametrize("chunk", [4, 32, 128])
@pytest.mark.parametrize("case", ["long_rows", "empty", "full"])
def test_rank64_gradient_matches_scatter_and_dense(case, chunk):
    """Rank 64: rows longer than several chunks, empty rows and columns
    among padding slots, and a block whose every slot holds a rating."""

    M, N, r = 90, 70, 64
    if case == "long_rows":
        args = dict(nnz=900, capacity=1024, long_row=N - 3)
    elif case == "empty":
        args = dict(nnz=300, capacity=640, empty=(0, 5, 17, 40))
    else:
        args = dict(nnz=2048, capacity=2048)
    entries, u, w, x, m = _block(M, N, r, seed=len(case) + chunk, **args)
    got = sddmm_segment_grad_ref(entries, u, w, chunk=chunk)
    for want in (sddmm_factor_grad_ref(entries, u, w),
                 obj.f_grads(x, m, u, w)):
        for a, b in zip(got, want):
            _close(a, b)
    if case == "empty":
        gu, gw = np.asarray(got[1]), np.asarray(got[2])
        assert not gu[list(args["empty"])].any()
        assert not gw[list(args["empty"])].any()


@pytest.mark.parametrize("chunk", [8, FALLBACK_CHUNK["tpu"]])
def test_segment_reduce_at_the_ml10m_block_matches_float64(chunk):
    """The ML-10M block's shape (573,440 slots, 17,892 row segments, Zipf
    row lengths, padding at the end) with contributions of mean 1, where
    a difference of prefixes of the whole stream loses short segments to
    rounding (it errs by up to ~4.6e5 × 2⁻²⁴ × Σ|x| of a segment here).
    The hierarchical sum takes differences inside one chunk only, so each
    segment lies within 4·chunk × 2⁻²⁴ × Σ|x| of its float64 sum."""

    rng = np.random.default_rng(5)
    E, S, nnz = 573440, 17892, 571235
    zipf = 1 / np.arange(1, S + 1) ** 0.6
    lengths = rng.permutation(rng.multinomial(nnz, zipf / zipf.sum()))
    ptr = np.concatenate([[0], np.cumsum(lengths)])
    x = (1.0 + rng.normal(size=(E, 4))).astype(np.float32)
    x[nnz:] = 0.0
    got = np.asarray(segment_reduce(jnp.asarray(x),
                                    jnp.asarray(ptr, jnp.int32), chunk))
    c = np.concatenate([np.zeros((1, 4)), np.cumsum(x, 0, np.float64)])
    a = np.concatenate([np.zeros((1, 4)),
                        np.cumsum(np.abs(x), 0, np.float64)])
    want = c[ptr[1:]] - c[ptr[:-1]]
    bound = 4 * chunk * 2.0**-24 * (a[ptr[1:]] - a[ptr[:-1]])
    assert np.all(np.abs(got - want) <= bound)


def _grid_store(m, n, p, q, r, density, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((m, n)) < density
    rows, cols = np.nonzero(mask)
    vals = rng.normal(size=len(rows)).astype(np.float32)
    sp, (mp, n_p) = sparse.from_entries(rows, cols, vals, m, n, p, q,
                                        bucket=32)
    spec = G.GridSpec(mp, n_p, p, q, r)
    key = jax.random.PRNGKey(seed)
    st = State(*(0.3 * jax.random.normal(k, s) for k, s in zip(
        jax.random.split(key), [(p, q, spec.mb, r), (p, q, spec.nb, r)])),
        jnp.zeros((), jnp.int32))
    return sparse.drop_tile(sp), st, (rows, cols, vals)


def test_blockwise_visit_equals_vmapped():
    """``f_grads_at`` (one block per loop step) against the vmapped
    per-block gradient of the same blocks."""

    sp, st, _ = _grid_store(60, 48, 4, 3, 8, 0.2, seed=2)
    bi = jnp.asarray([[0, 1, 0], [3, 2, 3]], jnp.int32)
    bj = jnp.asarray([[0, 0, 1], [2, 2, 1]], jnp.int32)
    u, w = st.U[bi, bj], st.W[bi, bj]
    got = sparse_obj.f_grads_at(sp.entries, bi, bj, u, w)
    want = jax.vmap(jax.vmap(sparse_obj.f_grads_sparse))(
        sp.entries.gather(bi, bj), u, w)
    for a, b in zip(got, want):
        _close(a, b, rtol=1e-6)
    cost = sparse_obj.total_report_cost_sparse(sp, st.U, st.W, 0.1)
    per = jax.vmap(jax.vmap(sparse_obj.f_cost_sparse))(sp.entries, st.U,
                                                        st.W)
    _close(cost, jnp.sum(per) + 0.1 * (jnp.sum(st.U ** 2)
                                       + jnp.sum(st.W ** 2)), rtol=1e-6)


def test_tileless_wave_step_is_algorithm1_over_the_wave():
    """With a constant step (b = 0) one wave of disjoint structures is
    Algorithm 1's updates of those structures, one after another: the
    tile-less wave step against ``sgd_structure_step`` on the sparse and
    the dense layout."""

    sp, st, (rows, cols, vals) = _grid_store(48, 40, 4, 4, 6, 0.25, seed=3)
    spec = G.GridSpec(48, 40, 4, 4, 6)
    x = np.zeros((48, 40), np.float32)
    x[rows, cols] = vals
    mask = np.zeros((48, 40), np.float32)
    mask[rows, cols] = 1.0
    from repro.core.state import make_problem

    dense = make_problem(x, mask, spec)
    kw = dict(rho=10.0, lam=1e-3, a=1e-2, b=0.0)
    for wave in G.wave_schedule(4, 4):
        got = waves.wave_step(sp, st, build_tables(4, 4, wave), **kw)
        for problem in (sp, dense):
            seq = st
            for s in wave:
                seq = sequential.sgd_structure_step(
                    problem, seq, build_tables(4, 4, s[None]),
                    jax.random.PRNGKey(0), **kw)
            _close(got.U, seq.U)
            _close(got.W, seq.W)
        assert int(got.t) == len(wave)


def test_tileless_wave_fit_matches_dense_layout():
    """A few Wave rounds through ``Trainer.fit`` on a store whose blocks
    carry no tile against the same rounds on the dense layout."""

    rng = np.random.default_rng(4)
    m, n = 128, 96
    lin = rng.choice(m * n, 300, replace=False)
    rows, cols = lin // n, lin % n
    vals = rng.normal(size=300).astype(np.float32)
    eng = EngineOptions(bucket=8)
    seg = CompletionProblem.from_entries(rows, cols, vals, (m, n), 4, 4, 8,
                                         engine=eng)
    den = CompletionProblem.from_entries(rows, cols, vals, (m, n), 4, 4, 8,
                                         layout="dense")
    assert seg.grad_path == "segment" and den.grad_path == "dense"
    spec = seg.spec
    trainer = Trainer(GossipMCConfig(m=spec.m, n=spec.n, p=4, q=4, rank=8,
                                     rho=10.0, lam=1e-3, a=1e-2, b=1e-4))
    key = jax.random.PRNGKey(1)
    ku, kw = jax.random.split(key)
    st = State(0.3 * jax.random.normal(ku, (4, 4, spec.mb, 8)),
               0.3 * jax.random.normal(kw, (4, 4, spec.nb, 8)),
               jnp.zeros((), jnp.int32))
    a = trainer.fit(seg, Wave(num_rounds=3), state=st, key=key)
    b = trainer.fit(den, Wave(num_rounds=3), state=st, key=key)
    _close(a.state.U, b.state.U)
    _close(a.state.W, b.state.W)
    _close(a.final_cost, b.final_cost)
