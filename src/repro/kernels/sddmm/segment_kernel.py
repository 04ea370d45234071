"""Pallas TPU kernel: segment-sorted SDDMM factor gradient.

Segment-reduce sibling of ``kernel.py``: instead of scattering every entry's
contribution through a one-hot MXU matmul, it exploits the store's sorted
order (``sparse/store.py``) and accumulates a **running prefix scan** over
the entry stream, finishing each factor row with a boundary-difference
matmul.  Per entry tile of ``be`` sorted entries it computes

    ue = 1h(rows) U,  we = 1h(cols) W          (MXU one-hot gathers)
    e  = valid ⊙ (vals − Σ_r ue ⊙ we)          (SDDMM residual, VPU)
    f += ‖e‖²                                   (SMEM accumulator)
    c  = −2 e ⊙ we                              (per-entry contributions)
    S  = carry + TRIexcl · c                    (tile-local exclusive prefix
                                                 scan as one (be×be)·(be×r)
                                                 MXU matmul)
    g += (1h(hi) − 1h(lo)) · S                  (boundary-difference matmul:
                                                 row s gets S[ptr[s+1]] −
                                                 S[ptr[s]] once the matching
                                                 boundary streams past)
    carry += Σ_k c_k                            (VMEM scratch, persists
                                                 across the sequential grid)

``lo``/``hi`` are the segment offsets (``row_ptr[:-1]``/``row_ptr[1:]`` for
gU; the CSC ``col_ptr`` pair for gW, with entries pre-gathered through
``col_perm`` by ops.py).  Each boundary value b ∈ [0, E) matches exactly one
(tile, lane) position, so summed over the sequential grid every factor row
receives exactly S[hi] − S[lo] = its contiguous segment sum.  ops.py pads
the entry stream so every offset is strictly below the padded capacity.

One pallas_call produces one side (gU or gW); ops.py invokes it twice.  The
FLOP shape stays rank-2 MXU work — nnz·(M+N)·r for the gathers plus
nnz·(be+S)·r for scan+boundary — with no serialized VMEM scatter anywhere.
U, W, g are grid-resident VMEM blocks; the carry is VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# every matmul here multiplies a 0/1 matrix by f32 values: at full f32
# precision it gathers or sums them to f32 accuracy, where one bf16 pass
# (the TPU's default) would keep 8 bits of each value
_EXACT = jax.lax.Precision.HIGHEST

def _make_kernel(side: str):
    def _kernel(rows_ref, cols_ref, vals_ref, valid_ref, lo_ref, hi_ref,
                u_ref, w_ref, loss_ref, g_ref, carry_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            loss_ref[0, 0] = jnp.float32(0.0)
            g_ref[...] = jnp.zeros_like(g_ref)
            carry_ref[...] = jnp.zeros_like(carry_ref)

        rows = rows_ref[0, :]                       # (be,) int32
        cols = cols_ref[0, :]
        vals = vals_ref[0, :].astype(jnp.float32)
        valid = valid_ref[0, :].astype(jnp.float32)
        u = u_ref[...].astype(jnp.float32)          # (M, r)
        w = w_ref[...].astype(jnp.float32)          # (N, r)

        be = rows.shape[0]
        m, n = u.shape[0], w.shape[0]
        oh_r = (rows[:, None] == jax.lax.broadcasted_iota(jnp.int32, (be, m), 1)
                ).astype(jnp.float32)               # (be, M)
        oh_c = (cols[:, None] == jax.lax.broadcasted_iota(jnp.int32, (be, n), 1)
                ).astype(jnp.float32)               # (be, N)
        ue = jax.lax.dot_general(                   # gather U[rows]: (be, r)
            oh_r, u, (((1,), (0,)), ((), ())),
            precision=_EXACT, preferred_element_type=jnp.float32,
        )
        we = jax.lax.dot_general(                   # gather W[cols]: (be, r)
            oh_c, w, (((1,), (0,)), ((), ())),
            precision=_EXACT, preferred_element_type=jnp.float32,
        )
        e = valid * (vals - jnp.sum(ue * we, axis=1))       # (be,)
        loss_ref[0, 0] += jnp.sum(e * e)

        c = (-2.0 * e)[:, None] * (we if side == "u" else ue)   # (be, r)

        # tile-local exclusive prefix scan as a strictly-lower-triangular
        # matmul; the carry scratch holds the prefix of all earlier tiles.
        ii = jax.lax.broadcasted_iota(jnp.int32, (be, be), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (be, be), 1)
        tri = (jj < ii).astype(jnp.float32)
        prefix = carry_ref[0:1, :] + jax.lax.dot_general(
            tri, c, (((1,), (0,)), ((), ())),
            precision=_EXACT, preferred_element_type=jnp.float32,
        )                                           # (be, r): S at each lane

        # boundary-difference accumulation: row s of D is +1 at hi[s]'s lane
        # and −1 at lo[s]'s lane when those offsets fall in this tile.
        base = t * be
        pos = jax.lax.broadcasted_iota(jnp.int32, (lo_ref.shape[1], be), 1) + base
        lo = lo_ref[0, :]                           # (S,) int32
        hi = hi_ref[0, :]
        d_sel = ((hi[:, None] == pos).astype(jnp.float32)
                 - (lo[:, None] == pos).astype(jnp.float32))    # (S, be)
        g_ref[...] += jax.lax.dot_general(
            d_sel, prefix, (((1,), (0,)), ((), ())),
            precision=_EXACT, preferred_element_type=jnp.float32,
        )
        carry_ref[0:1, :] += jnp.sum(c, axis=0, keepdims=True)

    return _kernel


@functools.partial(jax.jit, static_argnames=("side", "be", "interpret"))
def sddmm_segment_grad_pallas(rows, cols, vals, valid, lo, hi, u, w, *,
                              side: str, be: int, interpret: bool):
    """Padded-shape Pallas call for one gradient side.

    Entry arrays are (1, E) with be|E and every lo/hi offset < E; lo/hi are
    (1, S) with S the (padded) output row count; factor shapes already
    tile-aligned (ops.py handles padding and the col_perm pre-gather)."""

    E = rows.shape[1]
    m, r = u.shape
    n = w.shape[0]
    s = lo.shape[1]
    grid = (E // be,)

    loss, g = pl.pallas_call(
        _make_kernel(side),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, be), lambda t: (0, t)),      # rows
            pl.BlockSpec((1, be), lambda t: (0, t)),      # cols
            pl.BlockSpec((1, be), lambda t: (0, t)),      # vals
            pl.BlockSpec((1, be), lambda t: (0, t)),      # valid
            pl.BlockSpec((1, s), lambda t: (0, 0)),       # lo (resident)
            pl.BlockSpec((1, s), lambda t: (0, 0)),       # hi (resident)
            pl.BlockSpec((m, r), lambda t: (0, 0)),       # U (resident)
            pl.BlockSpec((n, r), lambda t: (0, 0)),       # W (resident)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # loss (1,1)
            pl.BlockSpec((s, r), lambda t: (0, 0)),       # g (resident)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, r), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((8, r), jnp.float32),              # running prefix carry
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(rows, cols, vals, valid, lo, hi, u, w)
    return loss[0, 0], g
