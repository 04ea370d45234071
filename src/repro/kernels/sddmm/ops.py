"""Jitted public wrappers around the SDDMM Pallas kernels.

Both entry points take a single ``BlockEntries`` bundle (sparse/entries.py
— duck-typed here so the kernel package stays a leaf) instead of the
exploded positional aux arrays of earlier revisions.  Internally they pad
the entry list to a multiple of the entry tile (padding slots get valid=0
so they contribute nothing), pad r to the 128-lane boundary and M/N to
sublane multiples (zero factor rows whose gradients are exactly zero and
are sliced away), pick interpret mode automatically off-TPU, and fall back
to the XLA path whenever the resident working set would blow the VMEM
budget.  Each such fallback increments
``kernel_fallbacks_total{kernel=..., reason="vmem"}`` while the caller's
program is traced (once per compiled program, not per call), so a run
that asked for the kernel can check that it got it.  The raw
``*_pallas`` functions keep exploded padded-array signatures: that is the
kernel ABI (tile-aligned device buffers), not the sparse API surface.

Two entry points: :func:`sddmm_factor_grad` (order-agnostic one-hot
scatter kernel, ``kernel.py``) and :func:`sddmm_segment_grad`
(segment-sorted sequential-scan kernel, ``segment_kernel.py``, the default
for the sorted store).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.sddmm.kernel import sddmm_factor_grad_pallas
from repro.kernels.sddmm.ref import sddmm_factor_grad_ref
from repro.kernels.sddmm.segment import sddmm_segment_grad_ref
from repro.kernels.sddmm.segment_kernel import sddmm_segment_grad_pallas

_LANE = 128
_SUBLANE = 8
# VMEM budget for the resident factors/accumulators + one-hot tiles.
_MAX_VMEM_BYTES = 10 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def segment_vmem_bytes(M: int, N: int, r: int, E: int, be: int = 512) -> int:
    """VMEM the segment kernel keeps resident for one (M, N, rank r)
    block of capacity E: U/W and a gradient accumulator, the one-hot and
    scan-triangle tiles, and the boundary-difference matrix."""

    r_pad = _round_up(max(r, _LANE), _LANE)
    m_pad = _round_up(M, _SUBLANE)
    n_pad = _round_up(N, _SUBLANE)
    be_eff = min(be, _round_up(E + 1, _LANE))
    return (
        2 * (m_pad + n_pad) * r_pad * 4          # U/W + g accumulators
        + be_eff * (m_pad + n_pad + be_eff) * 4  # one-hots + scan triangle
        + max(m_pad, n_pad) * be_eff * 4         # boundary-difference matrix
    )


def _pad_rows(a, target):
    pm = target - a.shape[0]
    if pm:
        a = jnp.pad(a, ((0, pm), (0, 0)))
    return a


@functools.partial(
    jax.jit, static_argnames=("be", "interpret", "force_kernel")
)
def sddmm_factor_grad(
    entries,
    u,
    w,
    *,
    be: int = 512,
    interpret: bool | None = None,
    force_kernel: bool = False,
):
    """(loss, gU, gW) from one block's padded COO entries — fused Pallas path.

    loss = Σ_k valid_k (vals_k − ⟨U[rows_k], W[cols_k]⟩)²,
    gU/gW are the −2eW / −2eᵀU scatter-adds (see ref.py).  Order-agnostic:
    the sorted-aux fields of ``entries`` are ignored.
    """

    E = entries.rows.shape[0]
    M, r = u.shape
    N = w.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    r_pad = _round_up(max(r, _LANE), _LANE)
    m_pad = _round_up(M, _SUBLANE)
    n_pad = _round_up(N, _SUBLANE)
    be_eff = min(be, _round_up(max(E, 1), _LANE))
    e_pad = _round_up(max(E, 1), be_eff)

    vmem = 2 * (m_pad + n_pad) * r_pad * 4 + be_eff * (m_pad + n_pad) * 4
    if vmem > _MAX_VMEM_BYTES and not force_kernel:
        # resident one-hot layout does not fit: the XLA scatter path
        obs.counter("kernel_fallbacks_total", kernel="sddmm_scatter",
                    reason="vmem").inc()
        return sddmm_factor_grad_ref(entries, u, w)

    def pad_e(a, fill):
        pe = e_pad - E
        if pe:
            a = jnp.pad(a, (0, pe), constant_values=fill)
        return a[None, :]                       # (1, E) lane-aligned layout

    rp = pad_e(entries.rows.astype(jnp.int32), 0)
    cp = pad_e(entries.cols.astype(jnp.int32), 0)
    vp = pad_e(entries.vals.astype(jnp.float32), 0.0)
    mp = pad_e(entries.valid.astype(jnp.float32), 0.0)
    up = _pad_rows(jnp.pad(u.astype(jnp.float32), ((0, 0), (0, r_pad - r))), m_pad)
    wp = _pad_rows(jnp.pad(w.astype(jnp.float32), ((0, 0), (0, r_pad - r))), n_pad)

    loss, gu, gw = sddmm_factor_grad_pallas(
        rp, cp, vp, mp, up, wp, be=be_eff, interpret=interpret
    )
    return loss, gu[:M, :r].astype(u.dtype), gw[:N, :r].astype(w.dtype)


@functools.partial(
    jax.jit, static_argnames=("be", "interpret", "force_kernel", "chunk")
)
def sddmm_segment_grad(
    entries,
    u,
    w,
    *,
    be: int = 512,
    interpret: bool | None = None,
    force_kernel: bool = False,
    chunk: int | None = None,
):
    """(loss, gU, gW) from one block's *row-sorted* padded COO entries —
    Pallas segment-reduce path (see ``segment_kernel.py``).

    One call per gradient side: gU streams the CSR view directly, gW
    streams the CSC dual view (entries gathered through ``col_perm``),
    each with its segment offsets as boundary-difference selectors.
    ``chunk`` only affects the XLA fallback (the Pallas kernel's tile size
    is ``be``).
    """

    E = entries.rows.shape[0]
    M, r = u.shape
    N = w.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    r_pad = _round_up(max(r, _LANE), _LANE)
    m_pad = _round_up(M, _SUBLANE)
    n_pad = _round_up(N, _SUBLANE)
    be_eff = min(be, _round_up(E + 1, _LANE))
    # every segment offset must sit strictly inside the padded stream so its
    # boundary lane exists: pad at least one slot past E.
    e_pad = _round_up(E + 1, be_eff)

    if segment_vmem_bytes(M, N, r, E, be) > _MAX_VMEM_BYTES \
            and not force_kernel:
        # resident layout does not fit: the XLA segment path
        obs.counter("kernel_fallbacks_total", kernel="sddmm_segment",
                    reason="vmem").inc()
        return sddmm_segment_grad_ref(entries, u, w, chunk=chunk)

    def pad_e(a, fill):
        pe = e_pad - E
        if pe:
            a = jnp.pad(a, (0, pe), constant_values=fill)
        return a[None, :]                       # (1, E) lane-aligned layout

    def pad_ptr(ptr, target):
        # padded output rows see hi == lo == the closing offset, i.e. empty
        # segments with exactly zero gradient
        close = jnp.broadcast_to(ptr[-1], (target - ptr.shape[0] + 1,))
        lo = jnp.concatenate([ptr[:-1], close])
        hi = jnp.concatenate([ptr[1:], close])
        return lo[None, :].astype(jnp.int32), hi[None, :].astype(jnp.int32)

    up = _pad_rows(jnp.pad(u.astype(jnp.float32), ((0, 0), (0, r_pad - r))), m_pad)
    wp = _pad_rows(jnp.pad(w.astype(jnp.float32), ((0, 0), (0, r_pad - r))), n_pad)

    rp = pad_e(entries.rows.astype(jnp.int32), 0)
    cp = pad_e(entries.cols.astype(jnp.int32), 0)
    vp = pad_e(entries.vals.astype(jnp.float32), 0.0)
    mp = pad_e(entries.valid.astype(jnp.float32), 0.0)
    lo_r, hi_r = pad_ptr(entries.row_ptr, m_pad)
    loss, gu = sddmm_segment_grad_pallas(
        rp, cp, vp, mp, lo_r, hi_r, up, wp,
        side="u", be=be_eff, interpret=interpret,
    )

    perm = entries.col_perm.astype(jnp.int32)
    rc = pad_e(jnp.take(entries.rows.astype(jnp.int32), perm, mode="clip"), 0)
    cc = pad_e(jnp.take(entries.cols.astype(jnp.int32), perm, mode="clip"), 0)
    vc = pad_e(jnp.take(entries.vals.astype(jnp.float32), perm, mode="clip"),
               0.0)
    mc = pad_e(jnp.take(entries.valid.astype(jnp.float32), perm, mode="clip"),
               0.0)
    lo_c, hi_c = pad_ptr(entries.col_ptr, n_pad)
    _, gw = sddmm_segment_grad_pallas(
        rc, cc, vc, mc, lo_c, hi_c, up, wp,
        side="w", be=be_eff, interpret=interpret,
    )
    return loss, gu[:M, :r].astype(u.dtype), gw[:N, :r].astype(w.dtype)
