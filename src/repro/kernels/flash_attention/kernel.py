"""Pallas TPU flash attention (tiled online softmax).

Variants folded into one kernel: causal, sliding-window (gemma2 local
layers), attention-logit softcap (gemma2), GQA (the K/V BlockSpec index map
does the Hq→Hkv head-group mapping, so grouped heads re-read the same KV
tile out of VMEM, never materializing `repeat`).

Tiling: grid (BHq, Lq/bq, Lk/bk), K-axis fastest (the online-softmax
accumulation axis).  Running max/denominator live in VMEM scratch broadcast
across 128 lanes (canonical TPU layout); the output tile is written once,
on the last K step — Lq·D traffic, not Lq·D·num_k_blocks.

Block-level early-out: fully-masked K tiles (above the causal diagonal /
outside the sliding window) are skipped with @pl.when, so causal attention
does ~half the MXU work and local attention is O(Lq·window).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bq: int, bk: int, lk: int, causal: bool, window: int,
    softcap: float, scale: float, q_offset: int,
):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions of this tile
    q_lo = iq * bq + q_offset            # first absolute q position
    k_lo = jk * bk
    # block-level reachability (early-out for fully masked tiles)
    live = True
    if causal:
        live = jnp.logical_and(live, q_lo + bq - 1 >= k_lo)
    if window:
        live = jnp.logical_and(live, q_lo < k_lo + bk + window - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(                          # (bq, bk)
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < lk                                  # pad keys
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]                             # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        # zero out fully-masked rows (exp(-inf - -inf) traps): mask again
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        pv = jax.lax.dot_general(                         # (bq, D)
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                   # fully-masked rows
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bq", "bk", "causal", "window", "softcap", "group",
        "q_offset", "lk_valid", "interpret",
    ),
)
def flash_attention_pallas(
    q, k, v, *, bq: int, bk: int, causal: bool, window: int,
    softcap: float, group: int, q_offset: int, lk_valid: int, interpret: bool,
):
    """Padded-shape call: q (BH, Lq, D), k/v (BHkv, Lk, D); bq|Lq, bk|Lk."""

    BH, Lq, D = q.shape
    Lk = k.shape[1]
    grid = (BH, Lq // bq, Lk // bk)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, lk=lk_valid, causal=causal, window=window,
        softcap=softcap, scale=1.0 / (D ** 0.5), q_offset=q_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, jk, g=group: (bh // g, jk, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, jk, g=group: (bh // g, jk, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, jk: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((bq, D), jnp.float32),        # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
