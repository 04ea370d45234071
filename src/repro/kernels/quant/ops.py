"""Jitted public wrapper around the fused dequantize-score kernel.

:func:`dequant_score` is the one entry point the serving stack calls
(``serve.recommend.recommend_topk`` and the sharded two-stage query both
route through it when handed a quantized index).  It follows the
``kernels/sddmm/ops.py`` kernel-switch pattern:

* ``method`` picks the arithmetic — ``"fused"`` (int8 MXU matmul, scale
  epilogue; the Pallas kernel) or ``"dequant"`` (materialize f32 rows,
  plain matmul); ``None`` resolves per backend from the committed sweep
  table (``autotune.resolve_method``), exactly like
  ``EngineOptions.chunk``;
* off-TPU the fused method lowers to its XLA emulation
  (``ref.fused_score_xla`` — the same int32-accumulate arithmetic, so
  results are identical); ``force_kernel=True`` runs the Pallas kernel
  anyway (interpret mode off-TPU — the kernel-correctness tests use it);
* the kernel path VMEM-tiles the **item axis** (``bn`` catalog rows per
  grid step) and backs off to the XLA emulation when the resident batch
  tile would not fit.

Both fused-method fallbacks (``reason="interpret"`` off-TPU,
``reason="vmem"`` over budget) increment
``kernel_fallbacks_total{kernel="quant_fused", reason=...}`` while the
caller's program is traced, once per compiled program.

Padding contract: rank pads to the 128-lane boundary, the user batch to
int8 sublane multiples, the catalog to ``bn`` multiples — padded rows
carry ``q = 0, scale = 0`` (score exactly 0) and are sliced away before
returning, so callers always see a dense (B, n) f32 score block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.quant.autotune import resolve_method
from repro.kernels.quant.kernel import dequant_score_pallas
from repro.kernels.quant.ref import dequant_score_ref, fused_score_xla

_LANE = 128
_SUBLANE_I8 = 32
# VMEM budget for the resident batch tile + one streaming item tile.
_MAX_VMEM_BYTES = 10 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit, static_argnames=("method", "bn", "interpret", "force_kernel")
)
def dequant_score(
    u_q,
    u_scale,
    w_q,
    w_scale,
    *,
    method: str | None = None,
    bn: int = 512,
    interpret: bool | None = None,
    force_kernel: bool = False,
):
    """(B, n) f32 scores for an int8 user batch against an int8 catalog.

    ``u_q`` (B, r) int8 with ``u_scale`` (B,) f32, ``w_q`` (n, r) int8
    with ``w_scale`` (n,) f32 — symmetric per-row quantization
    (serve/quant.py).  ``scores[i, j] = s_u[i] · s_w[j] · ⟨q_u[i], q_w[j]⟩``.
    """

    B, r = u_q.shape
    n = w_q.shape[0]
    method = resolve_method(method)
    if method == "dequant":
        return dequant_score_ref(u_q, u_scale, w_q, w_scale)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret and not force_kernel:
        # fused arithmetic without Mosaic: the XLA emulation is the same
        # int32-accumulate + epilogue, bit-identical to the kernel.
        obs.counter("kernel_fallbacks_total", kernel="quant_fused",
                    reason="interpret").inc()
        return fused_score_xla(u_q, u_scale, w_q, w_scale)

    r_pad = _round_up(max(r, _LANE), _LANE)
    b_pad = _round_up(max(B, _SUBLANE_I8), _SUBLANE_I8)
    bn_eff = min(bn, _round_up(max(n, 1), _LANE))
    n_pad = _round_up(n, bn_eff)

    vmem = (
        (b_pad + bn_eff) * r_pad                  # int8 factor tiles
        + (b_pad + bn_eff) * 4                    # scale rows
        + b_pad * bn_eff * 4                      # f32 output tile
    )
    if vmem > _MAX_VMEM_BYTES and not force_kernel:
        obs.counter("kernel_fallbacks_total", kernel="quant_fused",
                    reason="vmem").inc()
        return fused_score_xla(u_q, u_scale, w_q, w_scale)

    uq = jnp.pad(u_q, ((0, b_pad - B), (0, r_pad - r)))
    us = jnp.pad(u_scale.astype(jnp.float32), (0, b_pad - B))[:, None]
    wq = jnp.pad(w_q, ((0, n_pad - n), (0, r_pad - r)))
    ws = jnp.pad(w_scale.astype(jnp.float32), (0, n_pad - n))[None, :]
    scores = dequant_score_pallas(uq, us, wq, ws, bn=bn_eff,
                                  interpret=interpret)
    return scores[:B, :n]
