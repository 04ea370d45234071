"""Segment-sorted (CSR/CSC) SDDMM factor gradient — streaming XLA path.

Operates on one block's *row-sorted* padded COO entry list (see
``sparse/store.py``): entries come in (row, col) lexicographic order, so
each factor row's contributions form a contiguous segment delimited by
``row_ptr``; the column-sorted dual view is reached through the ``col_perm``
gather with ``col_ptr`` offsets.  With factors U (M×r), W (N×r):

    e_k = valid_k · (vals_k − ⟨U[rows_k], W[cols_k]⟩)
    gU[m] = −2 Σ_{k ∈ [row_ptr[m], row_ptr[m+1])} e_k · W[cols_k]
    gW[n] = −2 Σ_{k' ∈ [col_ptr[n], col_ptr[n+1])} e_k' · U[rows_k']

Replacing the random scatter-add of ``ref.py`` with contiguous segment
reductions is what moves the CPU sparse/dense crossover past 5% density
(DESIGN.md §3): gathers advertise ``indices_are_sorted`` and the reduction
is a **two-level chunked segment sum** — vectorized per-chunk totals, a
tiny chunk-prefix cumsum, and a triangular boundary correction — instead of
XLA's serialized scatter loop or a full-length cumsum.  All accumulation in
float32.  This module is a dependency-free leaf so both ``sparse.objective``
and the Pallas wrapper (``ops.py``) can import it without cycles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SEG_CHUNK = 32


@functools.lru_cache(maxsize=None)
def _tri(chunk: int) -> np.ndarray:
    """(chunk+1, chunk) prefix-selection matrix: TRI[o, k] = 1 iff k < o."""

    return np.tril(np.ones((chunk + 1, chunk), np.float32), -1)


def segment_reduce(contrib, ptr, chunk: int = SEG_CHUNK):
    """Sum contiguous segments of ``contrib`` (E, r) delimited by ``ptr``.

    ``ptr`` is (S+1,) non-decreasing int32 with values in [0, E]; returns
    (S, r) with out[s] = Σ contrib[ptr[s]:ptr[s+1]].  Two-level scheme:
    chunk totals are plain vectorized reshapes+sums, the prefix at each
    segment boundary is chunk_prefix[b // chunk] plus a ≤chunk-wide
    triangular correction, and segment sums are boundary-prefix differences.
    """

    E, r = contrib.shape
    nc = -(-E // chunk)
    pad = nc * chunk - E
    if pad:
        contrib = jnp.pad(contrib, ((0, pad), (0, 0)))
    ch = contrib.reshape(nc, chunk, r)
    cpre = jnp.concatenate(
        [jnp.zeros((1, r), contrib.dtype), jnp.cumsum(jnp.sum(ch, axis=1), 0)]
    )                                              # (nc+1, r) exclusive chunk prefix
    ci, ofs = ptr // chunk, ptr % chunk
    base = jnp.take(cpre, ci, axis=0, indices_are_sorted=True, mode="clip")
    sel = jnp.take(ch, ci, axis=0, indices_are_sorted=True, mode="clip")
    tri = jnp.take(jnp.asarray(_tri(chunk)), ofs, axis=0, mode="clip")
    # the 0/1 selection must not round ``sel``: at the TPU's default
    # precision the matmul would take its f32 operands as bf16
    s = base + jnp.einsum("bc,bcr->br", tri, sel,  # prefix at each boundary
                          precision=jax.lax.Precision.HIGHEST)
    return s[1:] - s[:-1]


def sddmm_segment_grad_ref(entries, u, w, chunk: int | None = None):
    """(loss, gU, gW) from one block's row-sorted entry list; O(nnz·r).

    ``entries`` is a ``BlockEntries`` bundle (sparse/entries.py — duck-typed
    so this module stays a leaf) whose sorted-aux fields must be attached.
    ``chunk`` overrides the segment-reduce chunk size (default SEG_CHUNK) —
    an engine tunable swept by ``benchmarks/sparse_vs_dense.py``."""

    chunk = SEG_CHUNK if chunk is None else chunk
    uf = u.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    ue = jnp.take(uf, entries.rows, axis=0, indices_are_sorted=True,
                  mode="clip")
    we = jnp.take(wf, entries.cols, axis=0, mode="clip")
    pred = jnp.sum(ue * we, axis=-1)
    e = entries.valid.astype(jnp.float32) * (
        entries.vals.astype(jnp.float32) - pred
    )
    loss = jnp.sum(e * e)
    d = -2.0 * e[:, None]
    gu = segment_reduce(d * we, entries.row_ptr, chunk)
    cw = jnp.take(d * ue, entries.col_perm, axis=0, mode="clip")
    gw = segment_reduce(cw, entries.col_ptr, chunk)
    return loss, gu.astype(u.dtype), gw.astype(w.dtype)
