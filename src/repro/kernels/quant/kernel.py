"""Pallas TPU kernel: fused int8 dequantize-score matmul.

The serving hot loop is ``scores = U[batch] @ Wᵀ`` over a quantized index
(serve/quant.py): int8 factor tiles with one f32 scale per row.  Done
naively that is a dequantize pass (int8 → f32, full n×r traffic) *plus*
the matmul; this kernel fuses the two so the catalog crosses HBM exactly
once, as int8:

    acc  = Q_u · Q_wᵀ            (int8 MXU matmul, int32 accumulate —
                                  exact: |q| ≤ 127 keeps any rank's dot
                                  inside int32)
    out  = acc ⊙ s_u ⊙ s_wᵀ      (f32 epilogue: per-row scales fold into
                                  a rank-1 outer product, VPU)

The grid runs over **item-axis tiles** of ``bn`` rows of W — the user
batch (one serving bucket, ≤1024) and its scales stay VMEM-resident
while the quantized catalog streams through, so VMEM holds
``B·r + bn·r`` int8 bytes plus the (B, bn) f32 output tile regardless of
catalog size.  Output tiles are disjoint per grid step (pure map over
item tiles → ``parallel`` dimension semantics).

ops.py owns padding (r → 128 lanes, B → 32 int8 sublanes, n → bn
multiples; padded rows carry q = 0, scale = 0 and are sliced away) and
the method/backoff switch; ``ref.fused_score_xla`` is this arithmetic
verbatim in XLA, so parity tests pin exact equality.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(uq_ref, us_ref, wq_ref, ws_ref, out_ref):
    acc = jax.lax.dot_general(                    # (B, bn) int32, exact
        uq_ref[...], wq_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out_ref[...] = acc.astype(jnp.float32) * us_ref[...] * ws_ref[...]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def dequant_score_pallas(u_q, u_scale, w_q, w_scale, *,
                         bn: int, interpret: bool):
    """Padded-shape Pallas call.

    ``u_q`` (B, r) int8 and ``u_scale`` (B, 1) f32 are grid-resident;
    ``w_q`` (n, r) int8 and ``w_scale`` (1, n) f32 stream in item tiles
    of ``bn`` rows (bn | n; ops.py aligns everything).  Returns (B, n)
    f32 scores."""

    b, r = u_q.shape
    n = w_q.shape[0]
    grid = (n // bn,)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, r), lambda j: (0, 0)),    # Q_u (resident)
            pl.BlockSpec((b, 1), lambda j: (0, 0)),    # s_u (resident)
            pl.BlockSpec((bn, r), lambda j: (j, 0)),   # Q_w item tile
            pl.BlockSpec((1, bn), lambda j: (0, j)),   # s_w item tile
        ],
        out_specs=pl.BlockSpec((b, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(u_q, u_scale, w_q, w_scale)
