"""Pallas TPU kernel: fused SDDMM residual + sparse factor gradients.

Sparse sibling of ``masked_factor_grad``: instead of sweeping the dense
(M×N) block and multiplying by a 0/1 mask, it sweeps the block's padded COO
entry list in tiles of ``be`` entries and touches only observed data.  Per
tile it computes

    ue = 1h(rows) U,  we = 1h(cols) W        (MXU one-hot gathers)
    e  = valid ⊙ (vals − Σ_r ue ⊙ we)        (SDDMM residual, VPU)
    f += ‖e‖²                                 (SMEM accumulator)
    gU += 1h(rows)ᵀ (−2 e ⊙ we)              (MXU one-hot scatter-add)
    gW += 1h(cols)ᵀ (−2 e ⊙ ue)

One-hot gather/scatter is the TPU idiom for data-dependent addressing: the
MXU eats the (be×M)·(M×r) products, there is no serialized VMEM gather, and
everything stays rank-2.  HBM traffic is nnz-proportional (the dense X/mask
tiles of the masked path are never read); the one-hot FLOPs scale with
nnz·(M+N)·r, so this kernel targets the paper's regime of many small/medium
blocks resident in VMEM.  For very large blocks ops.py falls back to the
gather-based XLA reference, whose FLOPs are exactly O(nnz·r).

U, W, gU, gW are grid-resident VMEM blocks (index map pinned to (0,0));
ops.py enforces the VMEM budget before choosing this kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the one-hot matmuls gather and scatter f32 values: at full f32 precision
# to f32 accuracy, where one bf16 pass (the TPU's default) keeps 8 bits
_EXACT = jax.lax.Precision.HIGHEST


def _kernel(rows_ref, cols_ref, vals_ref, valid_ref, u_ref, w_ref,
            loss_ref, gu_ref, gw_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        loss_ref[0, 0] = jnp.float32(0.0)
        gu_ref[...] = jnp.zeros_like(gu_ref)
        gw_ref[...] = jnp.zeros_like(gw_ref)

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
        oh_r, u, (((1,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32,
    )
    we = jax.lax.dot_general(                   # gather W[cols]: (be, r)
        oh_c, w, (((1,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32,
    )
    e = valid * (vals - jnp.sum(ue * we, axis=1))       # (be,)
    loss_ref[0, 0] += jnp.sum(e * e)

    d = -2.0 * e[:, None]                       # (be, 1)
    # scatter-add into the resident accumulators: contract the entry axis.
    gu_ref[...] += jax.lax.dot_general(
        oh_r, d * we, (((0,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32,
    )
    gw_ref[...] += jax.lax.dot_general(
        oh_c, d * ue, (((0,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("be", "interpret"))
def sddmm_factor_grad_pallas(rows, cols, vals, valid, u, w, *,
                             be: int, interpret: bool):
    """Padded-shape Pallas call.  Entry arrays are (1, E) with be|E; factor
    shapes already tile-aligned (ops.py handles padding)."""

    E = rows.shape[1]
    m, r = u.shape
    n = w.shape[0]
    grid = (E // be,)

    loss, gu, gw = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, be), lambda t: (0, t)),      # rows
            pl.BlockSpec((1, be), lambda t: (0, t)),      # cols
            pl.BlockSpec((1, be), lambda t: (0, t)),      # vals
            pl.BlockSpec((1, be), lambda t: (0, t)),      # valid
            pl.BlockSpec((m, r), lambda t: (0, 0)),       # U (resident)
            pl.BlockSpec((n, r), lambda t: (0, 0)),       # W (resident)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # loss (1,1)
            pl.BlockSpec((m, r), lambda t: (0, 0)),       # gU (resident)
            pl.BlockSpec((n, r), lambda t: (0, 0)),       # gW (resident)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, r), jnp.float32),
            jax.ShapeDtypeStruct((n, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(rows, cols, vals, valid, u, w)
    return loss[0, 0], gu, gw
