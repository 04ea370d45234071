"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels run in interpret mode (Python), so
wall-time is meaningless for them; we time the XLA-lowered equivalents
(ref / flashref paths, which XLA fuses) and report logical FLOP/s, plus the
kernels' *structural* numbers (VMEM working set, arithmetic intensity) that
determine TPU behaviour.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.xla import flash_attention_xla
from repro.kernels.masked_factor_grad.ref import masked_factor_grad_ref


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
        jax.tree.leaves(out)[0].block_until_ready()
    return (time.time() - t0) / iters * 1e6          # us


def bench_masked_factor_grad(out=print):
    f = jax.jit(masked_factor_grad_ref)
    for (M, N, r) in [(512, 512, 8), (2048, 2048, 16), (4096, 4096, 64)]:
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
        m = jnp.asarray(rng.random((M, N)) < 0.2, jnp.float32)
        u = jnp.asarray(rng.normal(size=(M, r)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(N, r)), jnp.float32)
        us = _time(f, x, m, u, w)
        flops = 6 * M * N * r                        # 3 matmuls
        # VMEM working set of the fused Pallas layout (kernel.py): tiles +
        # resident gW accumulator
        bm, bn, rp = min(256, M), min(256, N), max(128, r)
        vmem = (2 * bm * bn + bm * rp + N * rp + bn * rp + bm * rp) * 4
        out(f"mfg_{M}x{N}_r{r},{us:.0f},gflops={flops/us/1e3:.2f};"
            f"vmem_kb={vmem//1024};intensity={r}")


def bench_dequant_score(out=print):
    """Fused dequantize-score (kernels/quant) vs its two rivals.

    Three rows per geometry: the f32 matmul it replaces, the XLA
    dequantize-then-matmul fallback (``method="dequant"``), and the fused
    int32-accumulate path (``method="fused"`` — on CPU this times the XLA
    emulation, the exact arithmetic twin of the Pallas kernel).  These
    timings feed the ``FALLBACK_METHOD`` table in
    ``kernels/quant/autotune.py``; the serving-geometry sweep that
    ``method=None`` actually resolves from is the committed
    ``BENCH_quant.json`` (``serving_traffic.py --quant``).

    TODO(tpu): add a real-TPU row timing ``dequant_score_pallas`` itself
    (compiled, not interpret) once this runs on hardware — same standing
    item as the sddmm segment kernel; until then the structural VMEM
    numbers below are the TPU-relevant output."""

    from repro.kernels.quant import dequant_score
    from repro.serve.quant import quantize_rows

    for (B, n, r) in [(256, 2000, 32), (1024, 10000, 48)]:
        rng = np.random.default_rng(0)
        u = jnp.asarray(rng.normal(size=(B, r)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(n, r)), jnp.float32)
        u_q, u_s = quantize_rows(u)
        w_q, w_s = quantize_rows(w)
        f32 = jax.jit(lambda a, b: a @ b.T)
        deq = lambda a, b, c, d: dequant_score(a, b, c, d, method="dequant")
        fus = lambda a, b, c, d: dequant_score(a, b, c, d, method="fused")
        f32(u, w).block_until_ready()              # compile outside timing
        deq(u_q, u_s, w_q, w_s).block_until_ready()
        fus(u_q, u_s, w_q, w_s).block_until_ready()
        us_f32 = _time(f32, u, w)
        us_deq = _time(deq, u_q, u_s, w_q, w_s)
        us_fus = _time(fus, u_q, u_s, w_q, w_s)
        flops = 2 * B * n * r
        # VMEM working set of the Pallas layout (kernel.py): resident int8
        # user tile + streamed int8 item tile + scale rows + f32 out tile
        bn, rp, bp = min(512, n), max(128, r), max(32, B)
        vmem = (bp + bn) * rp + (bp + bn) * 4 + bp * bn * 4
        out(f"dequant_score_{B}x{n}_r{r}_f32,{us_f32:.0f},"
            f"gflops={flops/us_f32/1e3:.2f}")
        out(f"dequant_score_{B}x{n}_r{r}_dequant,{us_deq:.0f},"
            f"gflops={flops/us_deq/1e3:.2f};vs_f32={us_deq/us_f32:.2f}x")
        out(f"dequant_score_{B}x{n}_r{r}_fused,{us_fus:.0f},"
            f"gflops={flops/us_fus/1e3:.2f};vs_f32={us_fus/us_f32:.2f}x;"
            f"vmem_kb={vmem//1024}")


def bench_flash_attention(out=print):
    for (B, H, L, D) in [(1, 8, 1024, 128), (1, 8, 4096, 128)]:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.bfloat16)
        f = jax.jit(lambda a, b, c: flash_attention_xla(a, b, c, causal=True))
        us = _time(f, q, k, v)
        flops = 4 * B * H * L * L * D / 2            # causal half
        out(f"flash_attn_B{B}H{H}L{L}D{D},{us:.0f},gflops={flops/us/1e3:.2f}")


def main(out=print):
    bench_masked_factor_grad(out)
    bench_dequant_score(out)
    bench_flash_attention(out)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
