"""Jitted public wrapper around the masked_factor_grad Pallas kernel.

Handles padding to hardware-aligned tiles (M→bm·k, N→bn·k with mask=0 so
padded entries contribute nothing; r→multiple of 128 with zero factor
columns, whose gradients are exactly zero and are sliced away), picks
interpret mode automatically off-TPU, and falls back to the jnp reference
for shapes where the kernel buys nothing (tiny blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.masked_factor_grad.kernel import masked_factor_grad_pallas
from repro.kernels.masked_factor_grad.ref import masked_factor_grad_ref

_LANE = 128
_SUBLANE = 8
# VMEM budget for the resident gW accumulator (see kernel.py docstring).
_MAX_RESIDENT_BYTES = 8 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad2(a, target_m, target_n):
    pm, pn = target_m - a.shape[0], target_n - a.shape[1]
    if pm or pn:
        a = jnp.pad(a, ((0, pm), (0, pn)))
    return a


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "interpret", "force_kernel")
)
def masked_factor_grad(
    x,
    mask,
    u,
    w,
    *,
    bm: int = 256,
    bn: int = 256,
    interpret: bool | None = None,
    force_kernel: bool = False,
):
    """(loss, gU, gW) for one block — fused Pallas path.

    loss = ‖mask⊙(X−UWᵀ)‖²,  gU = −2RW,  gW = −2RᵀU.
    """

    M, N = x.shape
    r = u.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    r_pad = _round_up(max(r, _LANE), _LANE)
    bm_eff = min(bm, _round_up(M, _SUBLANE))
    bn_eff = min(bn, _round_up(N, _LANE))
    Mp = _round_up(M, bm_eff)
    Np = _round_up(N, bn_eff)

    resident = Np * r_pad * 4
    if resident > _MAX_RESIDENT_BYTES and not force_kernel:
        # gW accumulator would not fit VMEM — the factor rank is too large
        # for the fused layout; use the reference (XLA fuses adequately).
        obs.counter("kernel_fallbacks_total", kernel="masked_factor_grad",
                    reason="vmem").inc()
        return masked_factor_grad_ref(x, mask, u, w)

    xp = _pad2(x, Mp, Np)
    mp = _pad2(mask, Mp, Np)
    up = _pad2(u, Mp, r_pad)
    wp = _pad2(w, Np, r_pad)

    loss, gu, gw = masked_factor_grad_pallas(
        xp, mp, up, wp, bm=bm_eff, bn=bn_eff, interpret=interpret
    )
    return loss, gu[:M, :r].astype(u.dtype), gw[:N, :r].astype(w.dtype)
