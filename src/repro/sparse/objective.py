"""Sparse (nnz-proportional) evaluation of the paper's objective.

Same math as ``core/objective.py`` / ``core/waves.py`` restricted to
observed entries: the f-term and its factor gradients are computed from the
segment-sorted padded-COO store (O(nnz·r) instead of O(mb·nb·r) per block),
while the consensus and regularization terms — which only touch the factors
— are unchanged.  Gradients agree with the dense masked path to float
rounding; tests pin the equivalence at 1e-5.

Every per-block function takes a single ``BlockEntries`` bundle
(``sparse/entries.py``) instead of exploded positional aux arrays — the
whole sparse call surface routes through one pytree, so adding a store
field never again touches the schedulers (the old 9-positional shape is
kept as a deprecated shim on :func:`f_grads_sparse`).

The default gradient ``method="segment"`` streams contiguous segment
reductions over the store's CSR view (gU) and CSC dual view (gW) — see
``kernels/sddmm/segment.py``; ``method="scatter"`` is the order-agnostic
scatter-add reference kept for A/B validation and as the path for stores of
unknown order.  ``use_kernel`` swaps in the Pallas implementation of the
selected method; ``chunk`` tunes the segment-reduce chunk size (an engine
option surfaced by ``repro.mc.EngineOptions`` and swept by
``benchmarks/sparse_vs_dense.py``).

A block whose store carries the dense masked tile (``entries.has_tile``,
built at ingest by ``sparse.store.tile_rule`` where the block is dense
enough) computes the same f-term from it instead: ``R = M ⊙ (X − U Wᵀ)``,
``‖R‖²``, ``−2 R W`` and ``−2 Rᵀ U`` as three matrix products at
``Precision.HIGHEST``, all float32 — no row gathers, no segment reduction.
A tile stored as 1-byte codes is decoded inside the residual, ``M = code
≠ 0`` and ``X = lo + (code − 1)·step``, to the stored values bit for bit:
the arithmetic is the float32 tile's, only the bytes read are fewer.
The cost and the default (``"segment"``, no kernel) gradient take it;
``method="scatter"`` and ``use_kernel=True`` name their engine explicitly
and keep it.

This module depends only on the sddmm kernel package so both
``core.objective`` and ``core.waves`` can import it without cycles.
"""

from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.sddmm import autotune as sddmm_autotune
from repro.kernels.sddmm import ops as sddmm_ops
from repro.kernels.sddmm import ref as sddmm_ref
from repro.kernels.sddmm import segment as sddmm_seg
from repro.sparse.entries import BlockEntries
from repro.sparse.store import SparseProblem, decode


def _tile_cells(entries: BlockEntries, i=None, j=None):
    """(values, mask) of a block's dense tile, float32 and bool: decoded
    from its 1-byte codes (``sparse.store.decode``), or as stored.  With ``(i, j)`` the block is that of a (p, q, ...) store,
    each field a dynamic slice read in place by the op that decodes it."""

    get = (lambda t: t) if i is None else (lambda t: _block(t, i, j))
    if entries.tile_encoding == "codes":
        return decode(get(entries.tile_codes), get(entries.tile_grid))
    return get(entries.tile_vals), get(entries.tile_mask)


def _tile_residual(vals, mask, u, w):
    """R = M ⊙ (X − U Wᵀ) on a block's dense tile, float32 throughout.
    The tile may be larger than the block (padded to whole layout tiles,
    masked out): the factors get zero rows to match."""

    tm, tn = vals.shape
    u = jnp.pad(u.astype(jnp.float32), ((0, tm - u.shape[0]), (0, 0)))
    w = jnp.pad(w.astype(jnp.float32), ((0, tn - w.shape[0]), (0, 0)))
    pred = jnp.matmul(u, w.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.where(mask, vals - pred, 0.0), u, w


def _tile_terms(vals, mask, u, w):
    """(f, gU, gW) from a block's dense values and mask: three matrix
    products at ``HIGHEST`` (at the TPU's default precision they would
    take their float32 operands as bfloat16)."""

    r, up, wp = _tile_residual(vals, mask, u, w)
    hi = jax.lax.Precision.HIGHEST
    gu = -2.0 * jnp.matmul(r, wp, precision=hi)[:u.shape[0]]
    gw = -2.0 * jnp.matmul(r.T, up, precision=hi)[:w.shape[0]]
    return _sum_sq(r, u, w), gu.astype(u.dtype), gw.astype(w.dtype)


def _sum_sq(r, u, w):
    """‖R‖² over the block's own cells: the same sum, in the same order,
    whatever layout padding the tile's encoding adds."""

    r = r[:u.shape[0], :w.shape[0]]
    return jnp.sum(r * r)


def tile_f_grads(entries: BlockEntries, u, w):
    """(f, gU, gW) for one block from its dense masked tile."""

    return _tile_terms(*_tile_cells(entries), u, w)


def takes_tile(entries: BlockEntries, method: str = "segment",
               use_kernel: bool = False) -> bool:
    """Whether a block's f-gradients come from its dense tile: the store
    carries one and the caller named no other engine (``"scatter"`` or
    the Pallas kernel)."""

    return entries.has_tile and method == "segment" and not use_kernel


def _block(t, i, j):
    """Block ``(i, j)`` of a (p, q, ...) leaf, as a dynamic slice."""

    return jax.lax.dynamic_slice(t, (i, j) + (0,) * (t.ndim - 2),
                                 (1, 1) + t.shape[2:])[0, 0]


def _blockwise(visit, bi, bj, u, w):
    """(f, gU, gW) of blocks ``(bi, bj)``, one block per loop step.

    ``bi``/``bj`` are int arrays of one static shape, ``u``/``w`` the
    blocks' factors stacked the same way; ``visit(bi, bj, u, w, k)`` gives
    the k-th block's terms from the flattened stacks.  Only one block's
    temporaries are live at a time, and each block's data is a dynamic
    slice read in place: a vmapped ``leaf[bi, bj]`` is a gather that XLA
    first copies into a stacked array."""

    lead = bi.shape
    bi, bj = bi.reshape(-1), bj.reshape(-1)
    u = u.reshape((-1,) + u.shape[len(lead):])
    w = w.reshape((-1,) + w.shape[len(lead):])

    def step(k, acc):
        f, gu, gw = acc
        fk, guk, gwk = visit(bi, bj, u, w, k)
        return f.at[k].set(fk), gu.at[k].set(guk), gw.at[k].set(gwk)

    init = (jnp.zeros(bi.shape, jnp.float32), jnp.zeros_like(u),
            jnp.zeros_like(w))
    f, gu, gw = jax.lax.fori_loop(0, bi.shape[0], step, init)
    return (f.reshape(lead), gu.reshape(lead + gu.shape[1:]),
            gw.reshape(lead + gw.shape[1:]))


def tile_f_grads_at(entries: BlockEntries, bi, bj, u, w):
    """(f, gU, gW) of blocks ``(bi, bj)`` of a tiled (p, q, ...) store.

    One block per loop step (:func:`_blockwise`), its tile a dynamic slice
    read inside its own products: a vmapped ``tile[bi, bj]`` made a wave
    2.2× slower on a TPU v5 lite (DESIGN.md §3); unrolled, the blocks read
    in place too, but the program takes several times longer to compile."""

    def visit(bi, bj, u, w, k):
        return _tile_terms(*_tile_cells(entries, bi[k], bj[k]), u[k], w[k])

    return _blockwise(visit, bi, bj, u, w)


def f_grads_at(entries: BlockEntries, bi, bj, u, w, *,
               use_kernel: bool = False, method: str = "segment",
               chunk: int | None = None):
    """(f, gU, gW) of blocks ``(bi, bj)`` of a (p, q, ...) store, one
    block per loop step, shaped like ``bi``.

    A tiled store (with the default engine) takes :func:`tile_f_grads_at`;
    otherwise each block's entries are sliced out and go through
    :func:`f_grads_sparse`, so only one block's per-entry (E, r)
    temporaries are live at a time — vmapped over a wave's twelve
    ML-10M blocks they took gigabytes (DESIGN.md §3)."""

    if takes_tile(entries, method, use_kernel):
        return tile_f_grads_at(entries, bi, bj, u, w)
    entries = entries.without_tile()

    def visit(bi, bj, u, w, k):
        block = jax.tree.map(lambda t: _block(t, bi[k], bj[k]), entries)
        return f_grads_sparse(block, u[k], w[k], use_kernel=use_kernel,
                              method=method, chunk=chunk)

    return _blockwise(visit, bi, bj, u, w)


def f_cost_sparse(entries: BlockEntries, u, w):
    """‖valid ⊙ (vals − ⟨U[rows], W[cols]⟩)‖² for one block (from the
    dense tile where the block carries one)."""

    if entries.has_tile:
        r, _, _ = _tile_residual(*_tile_cells(entries), u, w)
        return _sum_sq(r, u, w)
    e = sddmm_ref.sddmm_residuals(entries, u, w)
    return jnp.sum(e * e)


def f_grads_sparse(entries, u, w, *legacy, use_kernel: bool = False,
                   method: str = "segment", chunk: int | None = None):
    """(f, gU, gW) for one block from its ``BlockEntries``; closed form.

    ``method="segment"`` (default) requires the row-sorted layout the store
    guarantees (``entries.has_sorted_aux``) and reduces contiguous CSR/CSC
    segments; ``"scatter"`` is the order-agnostic scatter-add reference.
    ``use_kernel`` selects the Pallas implementation of the chosen method
    (the XLA paths double as fallbacks for VMEM-oversized blocks);
    ``chunk`` tunes the XLA segment-reduce chunk size.  With neither
    ``"scatter"`` nor ``use_kernel``, a block carrying its dense tile
    (``entries.has_tile``) takes :func:`tile_f_grads` instead.

    The pre-BlockEntries positional shape
    ``(rows, cols, vals, valid, col_perm, row_ptr, col_ptr, u, w)`` is
    still accepted with a DeprecationWarning."""

    if legacy:
        if len(legacy) != 6:
            raise TypeError(
                "f_grads_sparse takes (entries, u, w) — or the deprecated "
                "9-positional (rows, cols, vals, valid, col_perm, row_ptr, "
                f"col_ptr, u, w) shape; got {3 + len(legacy)} positional "
                "arguments (use_kernel/method/chunk are keyword-only)"
            )
        warnings.warn(
            "f_grads_sparse(rows, cols, vals, valid, col_perm, row_ptr, "
            "col_ptr, u, w) is deprecated; pass a single BlockEntries: "
            "f_grads_sparse(entries, u, w)",
            DeprecationWarning, stacklevel=2,
        )
        entries = BlockEntries(entries, u, w, legacy[0], col_perm=legacy[1],
                               row_ptr=legacy[2], col_ptr=legacy[3])
        u, w = legacy[4], legacy[5]
    if method == "scatter":
        if use_kernel:
            return sddmm_ops.sddmm_factor_grad(entries, u, w)
        return sddmm_ref.sddmm_factor_grad_ref(entries, u, w)
    if method != "segment":
        raise ValueError(f"unknown method {method!r}; 'segment' or 'scatter'")
    if takes_tile(entries, method, use_kernel):
        return tile_f_grads(entries, u, w)
    # chunk=None -> the committed --chunks sweep's winner for this backend
    # (kernels/sddmm/autotune.py); an explicit chunk always wins
    chunk = sddmm_autotune.resolve_chunk(chunk)
    if use_kernel:
        return sddmm_ops.sddmm_segment_grad(entries, u, w, chunk=chunk)
    return sddmm_seg.sddmm_segment_grad_ref(entries, u, w, chunk=chunk)


def _block_cost(entries: BlockEntries, u, w, *, lam: float):
    return (f_cost_sparse(entries, u, w)
            + lam * jnp.sum(u * u) + lam * jnp.sum(w * w))


@partial(jax.jit, static_argnames=("lam",))
def _blockwise_cost(entries: BlockEntries, U, W, *, lam: float):
    """The cost of a store, one block per loop step, so only one block's
    temporaries are live: its (E, r) row gathers, or its tile's residual
    and product (vmapped over sixteen ML-10M tiles, 16 × 192 MB each).
    Each block is a dynamic slice read in place: a ``lax.map`` over the
    stack reshaped to (p·q, ...) made XLA copy all the ML-10M codes to a
    transposed layout first (compiled for a v5e).  Jitted: traced once
    per shape, where an op-by-op loop would trace and compile its body
    at every call."""

    p, q = U.shape[:2]

    def step(k, per):
        i, j = k // q, k % q
        block = jax.tree.map(lambda t: _block(t, i, j), (entries, U, W))
        return per.at[k].set(_block_cost(*block, lam=lam))

    return jnp.sum(jax.lax.fori_loop(0, p * q, step,
                                     jnp.zeros(p * q, jnp.float32)))


def total_report_cost_sparse(sp: SparseProblem, U, W, lam: float):
    """Paper Table-2 cost Σ f_ij + λ‖U_ij‖² + λ‖W_ij‖², nnz-proportional,
    one block per loop step whether or not the blocks carry a tile
    (:func:`_blockwise_cost`)."""

    return _blockwise_cost(sp.entries, U, W, lam=lam)


def consensus_pulls(A: jax.Array, axis: int) -> jax.Array:
    """Σ of forward+backward neighbour pulls along a block-grid axis with
    zeros at the boundary: grad_consensus = 2ρ · consensus_pulls.  The one
    copy of this sign-sensitive stencil — the dense path (waves.py) imports
    it too; it lives here because this module is a cycle-free leaf."""

    d = jnp.diff(A, axis=axis)                   # A[k+1] - A[k]
    zshape = list(A.shape)
    zshape[axis] = 1
    z = jnp.zeros(zshape, A.dtype)
    fwd = jnp.concatenate([-d, z], axis=axis)    # A[k] - A[k+1]
    bwd = jnp.concatenate([z, d], axis=axis)     # A[k] - A[k-1]
    return fwd + bwd


@partial(jax.jit, static_argnames=("rho", "lam", "use_kernel", "method",
                                   "chunk"))
def full_gradients_sparse(
    sp: SparseProblem, U: jax.Array, W: jax.Array, *,
    rho: float, lam: float, use_kernel: bool = False, method: str = "segment",
    chunk: int | None = None, f_scale: jax.Array | None = None,
):
    """∇L of the collapsed objective, f-part from the sparse store.

    ``f_scale`` (per-block (p, q), minibatch rounds) multiplies only the
    f-part: with ``sp`` a sampled minibatch and ``f_scale = nnz/batch`` of
    the full store the stochastic gradient is unbiased; the consensus and
    regularization terms are deterministic and stay unscaled."""

    _, gu_f, gw_f = jax.vmap(jax.vmap(
        lambda entries, u, w: f_grads_sparse(
            entries, u, w, use_kernel=use_kernel, method=method, chunk=chunk,
        )
    ))(sp.entries, U, W)
    if f_scale is not None:
        gu_f = gu_f * f_scale[..., None, None]
        gw_f = gw_f * f_scale[..., None, None]
    gU = gu_f + 2.0 * lam * U + 2.0 * rho * consensus_pulls(U, axis=1)
    gW = gw_f + 2.0 * lam * W + 2.0 * rho * consensus_pulls(W, axis=0)
    return gU, gW


def full_objective_sparse(sp: SparseProblem, U, W, rho: float, lam: float):
    """Eq. (3) collapsed objective (see objective.full_objective)."""

    total = total_report_cost_sparse(sp, U, W, lam)
    du = jnp.sum((U[:, 1:] - U[:, :-1]) ** 2)
    dw = jnp.sum((W[1:] - W[:-1]) ** 2)
    return total + rho * (du + dw)
