"""Segment-sorted SDDMM gradient engine: XLA segment-reduce and the Pallas
sequential-scan kernel vs the order-agnostic scatter oracle (interpret mode
on CPU), plus the raw segment_reduce primitive, and the dense-tile
arithmetic of the same f-term.  All gradient entry points take a single
BlockEntries bundle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import sparse
from repro.kernels.sddmm import (
    sddmm_factor_grad_ref,
    sddmm_segment_grad,
    sddmm_segment_grad_ref,
    segment_reduce,
)
from repro.sparse.entries import BlockEntries
from repro.sparse.objective import tile_f_grads, tile_f_grads_at



def _sorted_block(M, N, r, density, seed, bucket=64):
    rng = np.random.default_rng(seed)
    mask = (rng.random((1, 1, M, N)) < density).astype(np.float32)
    x = rng.normal(size=(1, 1, M, N)).astype(np.float32) * mask
    sp = sparse.from_blocks(x, mask, bucket=bucket)
    u = rng.normal(size=(M, r)).astype(np.float32)
    w = rng.normal(size=(N, r)).astype(np.float32)
    return sp.entries.gather(0, 0), u, w


@pytest.mark.parametrize("chunk", [4, 8, 32])
@pytest.mark.parametrize("E,S", [(37, 5), (64, 9), (128, 1), (6, 10)])
def test_segment_reduce_matches_numpy(chunk, E, S):
    rng = np.random.default_rng(E * S + chunk)
    contrib = rng.normal(size=(E, 3)).astype(np.float32)
    cuts = np.sort(rng.integers(0, E + 1, S - 1))
    ptr = np.concatenate([[0], cuts, [E]]).astype(np.int32)
    got = np.asarray(segment_reduce(jnp.asarray(contrib), jnp.asarray(ptr),
                                    chunk=chunk))
    want = np.stack([contrib[ptr[s]:ptr[s + 1]].sum(0) for s in range(S)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("M,N,r,density", [
    (8, 8, 1, 0.5), (60, 90, 5, 0.1), (128, 128, 16, 0.05),
    (33, 257, 3, 0.3), (256, 100, 8, 0.02), (40, 24, 4, 1.0),
])
def test_segment_ref_matches_scatter(M, N, r, density):
    entries, u, w = _sorted_block(M, N, r, density, seed=M + N + r)
    l0, gu0, gw0 = sddmm_factor_grad_ref(entries, u, w)
    l1, gu1, gw1 = sddmm_segment_grad_ref(entries, u, w)
    scale = float(jnp.max(jnp.abs(gu0))) + 1e-6
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gu1), np.asarray(gu0),
                               rtol=1e-4, atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw0),
                               rtol=1e-4, atol=1e-4 * scale)


@pytest.mark.parametrize("chunk", [8, 64])
def test_segment_ref_chunk_size_is_pure_performance(chunk):
    """The engine-option chunk size never changes results beyond float
    reassociation (the knob swept by sparse_vs_dense --chunks)."""

    entries, u, w = _sorted_block(60, 90, 5, 0.2, seed=7)
    base = sddmm_segment_grad_ref(entries, u, w)
    got = sddmm_segment_grad_ref(entries, u, w, chunk=chunk)
    scale = float(jnp.max(jnp.abs(base[1]))) + 1e-6
    for a, b in zip(got, base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4 * scale)


@pytest.mark.parametrize("M,N,r,density", [
    (8, 8, 1, 0.5), (60, 90, 5, 0.1), (128, 128, 16, 0.05),
    (33, 257, 3, 0.3), (256, 100, 8, 0.02),
])
def test_segment_kernel_matches_scatter(M, N, r, density):
    entries, u, w = _sorted_block(M, N, r, density, seed=2 * M + N + r)
    l0, gu0, gw0 = sddmm_factor_grad_ref(entries, u, w)
    l2, gu2, gw2 = sddmm_segment_grad(entries, u, w)
    scale = float(jnp.max(jnp.abs(gu0))) + 1e-6
    np.testing.assert_allclose(float(l2), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gu2), np.asarray(gu0),
                               rtol=1e-4, atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(gw2), np.asarray(gw0),
                               rtol=1e-4, atol=1e-4 * scale)


def test_segment_kernel_full_capacity_boundary():
    """nnz == capacity: the closing offset equals E and must still land on a
    boundary lane (ops pads the entry stream by at least one slot)."""

    M = N = 16
    r = 4
    entries, u, w = _sorted_block(M, N, r, density=1.0, seed=0, bucket=256)
    assert int(entries.row_ptr[-1]) == M * N == entries.capacity
    l0, gu0, gw0 = sddmm_factor_grad_ref(entries, u, w)
    l2, gu2, gw2 = sddmm_segment_grad(entries, u, w)
    np.testing.assert_allclose(float(l2), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gu2), np.asarray(gu0),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gw2), np.asarray(gw0),
                               rtol=1e-4, atol=1e-3)


def test_segment_kernel_all_padding_is_zero():
    E, M, N, r = 128, 16, 16, 4
    z = np.zeros(E, np.float32)
    entries = BlockEntries(
        z.astype(np.int32), z.astype(np.int32), z, z,
        col_perm=np.arange(E, dtype=np.int32),
        row_ptr=np.zeros(M + 1, np.int32),
        col_ptr=np.zeros(N + 1, np.int32),
    )
    loss, gu, gw = sddmm_segment_grad(
        entries, np.ones((M, r), np.float32), np.ones((N, r), np.float32)
    )
    assert float(loss) == 0.0
    assert float(np.abs(gu).max()) == 0.0
    assert float(np.abs(gw).max()) == 0.0


@pytest.mark.parametrize("M,N,r,density", [
    (8, 8, 1, 0.5), (60, 90, 5, 0.1), (128, 128, 16, 0.05),
    (33, 257, 3, 0.3), (256, 100, 8, 0.02), (40, 24, 4, 1.0),
])
def test_tile_matches_scatter(M, N, r, density):
    """The dense masked tile's three products give the scatter oracle's
    loss and gradients, from the same store."""

    rng = np.random.default_rng(3 * M + N + r)
    mask = (rng.random((1, 1, M, N)) < density).astype(np.float32)
    x = rng.normal(size=(1, 1, M, N)).astype(np.float32) * mask
    sp = sparse.with_tile(sparse.from_blocks(x, mask, bucket=64))
    entries = sp.entries.gather(0, 0)
    u = rng.normal(size=(M, r)).astype(np.float32)
    w = rng.normal(size=(N, r)).astype(np.float32)
    l0, gu0, gw0 = sddmm_factor_grad_ref(entries, u, w)
    l1, gu1, gw1 = tile_f_grads(entries, u, w)
    scale = float(jnp.max(jnp.abs(gu0))) + 1e-6
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gu1), np.asarray(gu0),
                               rtol=1e-4, atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw0),
                               rtol=1e-4, atol=1e-4 * scale)


def test_tile_all_padding_is_zero():
    M, N, r = 16, 12, 4
    entries = BlockEntries(None, None, None, None,
                           tile_vals=jnp.zeros((M, N), jnp.float32),
                           tile_mask=jnp.zeros((M, N), bool))
    loss, gu, gw = tile_f_grads(entries, np.ones((M, r), np.float32),
                                np.ones((N, r), np.float32))
    assert float(loss) == 0.0
    assert float(np.abs(gu).max()) == 0.0
    assert float(np.abs(gw).max()) == 0.0


def test_tile_at_blocks_equals_per_block_tiles():
    """``tile_f_grads_at`` (one dynamic slice per block, unrolled) returns
    exactly what the per-block arithmetic gives on the gathered tiles."""

    rng = np.random.default_rng(11)
    p, q, M, N, r = 3, 4, 10, 7, 3
    mask = (rng.random((p, q, M, N)) < 0.4).astype(np.float32)
    x = rng.normal(size=(p, q, M, N)).astype(np.float32) * mask
    sp = sparse.with_tile(sparse.from_blocks(x, mask, bucket=32))
    bi = jnp.asarray([[0, 1, 0], [2, 1, 2]], jnp.int32)
    bj = jnp.asarray([[0, 0, 1], [3, 3, 2]], jnp.int32)
    u = jnp.asarray(rng.normal(size=(2, 3, M, r)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 3, N, r)), jnp.float32)
    got = tile_f_grads_at(sp.entries, bi, bj, u, w)
    for s in range(2):
        for k in range(3):
            want = tile_f_grads(sp.entries.gather(int(bi[s, k]),
                                                  int(bj[s, k])),
                                u[s, k], w[s, k])
            for a, b in zip(got, want):
                np.testing.assert_allclose(np.asarray(a[s, k]),
                                           np.asarray(b), rtol=1e-6,
                                           atol=1e-6)
