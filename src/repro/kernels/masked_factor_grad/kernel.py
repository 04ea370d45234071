"""Pallas TPU kernel: fused masked residual + factor gradients.

One pass over the (M×N) block computes

    R  = mask ⊙ (X − U Wᵀ)         (SDDMM-style: dense MXU matmul + mask)
    f  = ‖R‖²                       (scalar, SMEM accumulator)
    gU = −2 R W                     (accumulated over the N grid axis)
    gW = −2 Rᵀ U                    (accumulated over the M grid axis)

Tiling: grid (I, J) = (M/bm, N/bn), row-major (J fastest).  Per step the
VMEM working set is the (bm×bn) X/mask tiles, the (bm×r) U tile, the (bn×r)
W tile, the (bm×r) gU accumulator tile and the *full* (N×r) gW accumulator
(gW revisits are non-consecutive under J-fastest iteration, so it lives as a
single always-resident block — r is small for matrix completion, so N·r
easily fits VMEM; ops.py asserts this).  All matmuls hit the MXU with
float32 accumulation via ``preferred_element_type``.

X is never re-read: the three products reuse the residual tile from
registers/VMEM — this is the fusion the paper's inner loop wants (arithmetic
intensity ≈ r vs ≈ r/3 for the unfused three-pass version).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, m_ref, u_ref, w_ref, loss_ref, gu_ref, gw_ref, *, bn: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_loss():
        loss_ref[0, 0] = jnp.float32(0.0)

    @pl.when(j == 0)
    def _init_gu():
        gu_ref[...] = jnp.zeros_like(gu_ref)

    @pl.when(i == 0)
    def _init_gw():
        gw_ref[pl.ds(j * bn, bn), :] = jnp.zeros((bn, gw_ref.shape[1]), gw_ref.dtype)

    x = x_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)

    # R = mask * (X - U W^T): (bm, r) x (bn, r) -> (bm, bn) on the MXU.
    pred = jax.lax.dot_general(
        u, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    r = m * (x - pred)

    loss_ref[0, 0] += jnp.sum(r * r)
    # gU tile accumulates over j: -2 R W  -> (bm, r)
    gu_ref[...] += -2.0 * jax.lax.dot_general(
        r, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # gW slice accumulates over i: -2 R^T U -> (bn, r); contract over bm
    # without materializing the transpose.
    gw_ref[pl.ds(j * bn, bn), :] += -2.0 * jax.lax.dot_general(
        r, u, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def masked_factor_grad_pallas(x, mask, u, w, *, bm: int, bn: int, interpret: bool):
    """Padded-shape Pallas call.  Shapes must already satisfy
    bm|M, bn|N, and r a multiple of 128 (ops.py handles padding)."""

    M, N = x.shape
    r = u.shape[1]
    grid = (M // bm, N // bn)

    kernel = functools.partial(_kernel, bn=bn)
    loss, gu, gw = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),          # x
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),          # mask
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),           # u
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),           # w
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # loss (1,1)
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),           # gU
            pl.BlockSpec((N, r), lambda i, j: (0, 0)),            # gW (resident)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((M, r), jnp.float32),
            jax.ShapeDtypeStruct((N, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x, mask, u, w)
    return loss[0, 0], gu, gw
