"""``BlockEntries`` — the one pytree that carries a block's sparse entries.

PR 2 left the sparse gradient surface exploded: every consumer threaded
``(rows, cols, vals, valid, col_perm, row_ptr, col_ptr)`` positionally, so
adding one field (the CSR/CSC aux arrays did exactly this) touched every
scheduler, every vmap lambda and every kernel wrapper.  This module is the
fix: a single NamedTuple pytree accepted by ``sparse/objective.py``,
``kernels/sddmm/*`` and ``core/{sequential,waves,gossip}.py``.  Adding a
field now means editing this class and the code that actually uses the
field — never the schedulers.

Layout contract (see ``sparse/store.py`` for the full story):

    rows     : (..., E) int32   — intra-block row index per entry
    cols     : (..., E) int32   — intra-block col index
    vals     : (..., E) float32 — observed value
    valid    : (..., E) float32 — 1 real entry, 0 padding
    col_perm : (..., E) int32   — gather to column-sorted (CSC) order
    row_ptr  : (..., mb+1) int32 — CSR segment offsets over the entry axis
    col_ptr  : (..., nb+1) int32 — CSC segment offsets (in col_perm order)
    tile_codes: (..., mb', nb') uint8  — the block's values as exact 1-byte
                                         codes, 0 unseen
    tile_grid : (..., 2) float32       — (lo, step): value = lo + (code−1)·step
    tile_vals : (..., mb', nb') float32 — the block's values, dense (0 unseen)
    tile_mask : (..., mb', nb') bool    — the block's observation mask, dense
    (mb' ≥ mb, nb' ≥ nb: padded to whole device layout tiles, masked out;
    a store has one grid, repeated per block so that every field leads
    with the block axes and a block's slice carries its own)

The three aux fields default to ``None`` (an empty pytree node, so vmap /
tree_map / shard_map specs all compose): an unsorted COO bundle built with
:meth:`from_coo` is a valid input for the order-agnostic ``scatter``
gradient method, while the ``segment`` fast path requires
:attr:`has_sorted_aux`.

The tile fields are a second arithmetic for the same f-term, present
only where the store built them (``sparse/store.py`` ``tile_rule``: a
block dense enough that three matrix products beat the segment engine's
row gathers, and tiles that fit the device).  A store carries one of two
encodings of the same tile: ``tile_codes``/``tile_grid`` where every
value sits on an affine grid of at most 255 levels and decodes to itself
bit for bit (rating scales do), else ``tile_vals``/``tile_mask``.  The
other pair, and all four where there is no tile, are ``None`` —
minibatches and mesh-placed stores never carry a tile.

Leading batch axes are free: the store stacks blocks as (p, q, ...), the
schedulers gather structure trios as (3, ...), and ``jax.vmap`` peels axes
off every leaf at once — that is the point of making this a pytree.

The entry capacity E is fixed at ingest (max block nnz + headroom, rounded
to a bucket) and **never changes afterwards**: streaming appends
(``sparse.append_entries``) splice new entries into the sorted prefix and
patch the aux views inside the same capacity, so a bundle's shapes — and
every jitted consumer compiled against them — survive online ingestion
unchanged (DESIGN.md §11).

This module is a dependency-free leaf (jax only) so every layer can import
it without cycles.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax

# the segment-sorted store's own fields: what every store carries, and
# all that a mesh-placed store carries (its blocks take no tile)
SORTED_FIELDS = ("rows", "cols", "vals", "valid", "col_perm", "row_ptr",
                 "col_ptr")
# the dense tile's fields, in either encoding
TILE_FIELDS = ("tile_vals", "tile_mask", "tile_codes", "tile_grid")


class BlockEntries(NamedTuple):
    """Padded-COO entries of one block (or a stack of blocks)."""

    rows: jax.Array
    cols: jax.Array
    vals: jax.Array
    valid: jax.Array
    col_perm: Optional[jax.Array] = None
    row_ptr: Optional[jax.Array] = None
    col_ptr: Optional[jax.Array] = None
    tile_vals: Optional[jax.Array] = None
    tile_mask: Optional[jax.Array] = None
    tile_codes: Optional[jax.Array] = None
    tile_grid: Optional[jax.Array] = None

    @property
    def capacity(self) -> int:
        """Per-block entry capacity E (padding included)."""

        return self.rows.shape[-1]

    @property
    def has_sorted_aux(self) -> bool:
        """True when the CSR/CSC dual-view offsets are attached — the
        precondition of the ``segment`` gradient method."""

        return (
            self.col_perm is not None
            and self.row_ptr is not None
            and self.col_ptr is not None
        )

    @property
    def tile_encoding(self) -> Optional[str]:
        """``"codes"`` or ``"f32"``: the encoding of the dense tile the
        entries carry, ``None`` without one."""

        if self.tile_codes is not None:
            return "codes"
        return "f32" if self.tile_vals is not None else None

    @property
    def has_tile(self) -> bool:
        """True when the dense masked tile is attached, in either
        encoding: the f-term and its gradients then come from matrix
        products, not segment reductions."""

        return self.tile_encoding is not None

    def without_tile(self) -> "BlockEntries":
        """The same entries with the dense tile dropped (segment path)."""

        return self._replace(**dict.fromkeys(TILE_FIELDS))

    @property
    def mb(self) -> int:
        """Block row count, from the CSR offsets (sorted stores only)."""

        return self.row_ptr.shape[-1] - 1

    @property
    def nb(self) -> int:
        """Block col count, from the CSC offsets (sorted stores only)."""

        return self.col_ptr.shape[-1] - 1

    @classmethod
    def from_coo(cls, rows, cols, vals, valid) -> "BlockEntries":
        """Order-agnostic bundle (no sorted aux) — scatter-method input."""

        return cls(rows, cols, vals, valid)

    def gather(self, *idx) -> "BlockEntries":
        """Index every field identically: ``entries.gather(bi, bj)`` pulls
        the same (possibly advanced-indexed) blocks out of all leaves, e.g.
        a structure's three blocks as (3, ...) stacks.  ``None`` aux fields
        pass through untouched."""

        return jax.tree.map(lambda f: f[idx], self)
