"""Segment-sorted (CSR/CSC) SDDMM factor gradient — streaming XLA path.

Operates on one block's *row-sorted* padded COO entry list (see
``sparse/store.py``): entries come in (row, col) lexicographic order, so
each factor row's contributions form a contiguous segment delimited by
``row_ptr``; the column-sorted dual view is reached through ``col_perm``
with ``col_ptr`` offsets.  With factors U (M×r), W (N×r):

    e_k = valid_k · (vals_k − ⟨U[rows_k], W[cols_k]⟩)
    gU[m] = −2 Σ_{k ∈ [row_ptr[m], row_ptr[m+1])} e_k · W[cols_k]
    gW[n] = −2 Σ_{k' ∈ [col_ptr[n], col_ptr[n+1])} e_k' · U[rows_k']

Replacing the random scatter-add of ``ref.py`` with contiguous segment
reductions is what moves the CPU sparse/dense crossover past 5% density
(DESIGN.md §3): gathers advertise ``indices_are_sorted`` and the reduction
is a **hierarchical chunked segment sum** (:func:`segment_reduce`) — a
within-chunk prefix per chunk of the stream, a few gathered rows per
segment, and the same reduction again over the chunk totals for the
whole chunks a segment covers — instead of XLA's serialized scatter loop
or a full-length cumsum.  Its temporaries are O(E·r + levels·S·r) for E
entries and S segments, and every prefix difference it takes lies inside
one chunk of its level, so no sum cancels against a prefix of the whole
stream.  All accumulation in float32.  The
stages carry ``jax.named_scope`` names (``segment.*``) for a profile read
by hand.  This module is a dependency-free leaf so both
``sparse.objective`` and the Pallas wrapper (``ops.py``) can import it
without cycles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SEG_CHUNK = 32


@functools.lru_cache(maxsize=None)
def _tri(chunk: int) -> np.ndarray:
    """(chunk, chunk) inclusive prefix matrix: TRI[o, k] = 1 iff k ≤ o."""

    return np.tril(np.ones((chunk, chunk), np.float32))


def segment_reduce(contrib, ptr, chunk: int = SEG_CHUNK):
    """Sum contiguous segments of ``contrib`` (E, r) delimited by ``ptr``.

    ``ptr`` is (S+1,) non-decreasing int32 with values in [0, E]; returns
    (S, r) with out[s] = Σ contrib[ptr[s]:ptr[s+1]] (see
    :func:`_segment_sums`)."""

    return _segment_sums(contrib, ptr[:-1], ptr[1:], chunk)


def _segment_sums(x, lo, hi, chunk: int):
    """(S, r): out[s] = Σ x[lo[s]:hi[s]] for lo ≤ hi, 0 where lo = hi.

    The stream is cut into chunks, and one matrix product with a 0/1
    triangle gives every chunk's inclusive prefixes, the last its total.
    B(p), the sum of p's chunk before p, is one gathered row a boundary
    (the stream's end counts as the end of the last chunk).  A segment
    inside one chunk is B(hi) − B(lo).  Otherwise it is the tail of its
    first chunk (that chunk's total − B(lo)), B(hi) of its last, and the
    whole chunks between: the same sum over the chunk totals one level
    up, which is empty for most segments.  So every difference is one of
    two prefixes of one chunk of its level, never of prefixes of the
    whole stream, and a segment's error stays within a few chunks'
    rounding (float32 throughout)."""

    E, r = x.shape
    if E <= chunk:                       # one chunk: two of its prefixes
        pre = jnp.concatenate([jnp.zeros((1, r), x.dtype),
                               jnp.cumsum(x, axis=0)])
        return (pre.at[hi].get(mode="promise_in_bounds")
                - pre.at[lo].get(mode="promise_in_bounds"))
    nc = -(-E // chunk)
    if nc * chunk > E:
        x = jnp.pad(x, ((0, nc * chunk - E), (0, 0)))
    # the 0/1 triangle must not round ``x``: at the TPU's default
    # precision the matmul would take its f32 operand as bf16
    incl = jnp.einsum("ok,ckr->cor", jnp.asarray(_tri(chunk)),
                      x.reshape(nc, chunk, r),
                      precision=jax.lax.Precision.HIGHEST)
    c_lo = jnp.minimum(lo // chunk, nc - 1)
    c_hi = jnp.minimum(hi // chunk, nc - 1)
    at = jnp.concatenate([lo, hi, c_lo * chunk + chunk]) - 1
    b_lo, b_hi, tot_lo = jnp.split(incl.reshape(nc * chunk, r).at[
        jnp.maximum(at, 0)].get(mode="promise_in_bounds"), 3)
    b_lo = jnp.where((lo > c_lo * chunk)[:, None], b_lo, 0.0)
    b_hi = jnp.where((hi > c_hi * chunk)[:, None], b_hi, 0.0)
    out = jnp.where((c_lo == c_hi)[:, None], b_hi - b_lo,
                    (tot_lo - b_lo) + b_hi)
    return out + _segment_sums(incl[:, -1], c_lo + 1,
                               jnp.maximum(c_hi, c_lo + 1), chunk)


def sddmm_segment_grad_ref(entries, u, w, chunk: int | None = None):
    """(loss, gU, gW) from one block's row-sorted entry list; O(nnz·r).

    ``entries`` is a ``BlockEntries`` bundle (sparse/entries.py — duck-typed
    so this module stays a leaf) whose sorted-aux fields must be attached.
    ``chunk`` overrides the segment-reduce chunk size (default SEG_CHUNK) —
    an engine tunable (``kernels/sddmm/autotune.py``).  The CSC side
    permutes the (E, r) contributions by ``col_perm``: one row gather,
    where gathering U's rows at ``rows[col_perm]`` needs two scalar
    gathers besides, which took twice as long at the ML-10M block on a
    TPU v5 lite (DESIGN.md §3)."""

    chunk = SEG_CHUNK if chunk is None else chunk
    uf = u.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    with jax.named_scope("segment.gather"):
        ue = jnp.take(uf, entries.rows, axis=0, indices_are_sorted=True,
                      mode="clip")
        we = jnp.take(wf, entries.cols, axis=0, mode="clip")
    with jax.named_scope("segment.residual"):
        pred = jnp.sum(ue * we, axis=-1)
        e = entries.valid.astype(jnp.float32) * (
            entries.vals.astype(jnp.float32) - pred
        )
        loss = jnp.sum(e * e)
        d = -2.0 * e
    with jax.named_scope("segment.reduce_rows"):
        gu = segment_reduce(d[:, None] * we, entries.row_ptr, chunk)
    with jax.named_scope("segment.col_view"):
        cw = jnp.take(d[:, None] * ue, entries.col_perm, axis=0, mode="clip")
    with jax.named_scope("segment.reduce_cols"):
        gw = segment_reduce(cw, entries.col_ptr, chunk)
    return loss, gu.astype(u.dtype), gw.astype(w.dtype)
