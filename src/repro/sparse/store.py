"""Segment-sorted padded-COO sparse block store for the gossip grid.

The dense path materializes (p, q, mb, nb) value/mask tensors, so every
objective/gradient evaluation costs O(m·n) regardless of how sparse the
ratings are.  MovieLens/Netflix-style workloads are ≤5% dense; this store
keeps, per grid block, only the observed entries, bundled as a single
``BlockEntries`` pytree (sparse/entries.py) stacked over the (p, q) grid:

    entries.rows     : (p, q, E)    int32   — intra-block row index
    entries.cols     : (p, q, E)    int32   — intra-block col index
    entries.vals     : (p, q, E)    float32 — observed value
    entries.valid    : (p, q, E)    float32 — 1 real, 0 padding
    entries.col_perm : (p, q, E)    int32   — permutation to col-sorted order
    entries.row_ptr  : (p, q, mb+1) int32   — CSR segment offsets
    entries.col_ptr  : (p, q, nb+1) int32   — CSC segment offsets
    entries.tile_codes: (p, q, mb', nb') uint8 — dense 1-byte value codes
    entries.tile_grid : (p, q, 2)     float32 — their grid (lo, step)
    entries.tile_vals : (p, q, mb', nb') float32 — or dense values
    entries.tile_mask : (p, q, mb', nb') bool    — and their mask
    nnz              : (p, q)       int32   — real entry count per block

Entries are **segment-sorted** (DESIGN.md §3): real entries come first, in
(row, col) lexicographic order, so each block row is a contiguous segment
delimited by ``row_ptr`` and the factor gradients reduce over contiguous
streams instead of random scatter-adds.  ``col_perm`` is the dual (CSC)
view: gathering the entry axis through it yields column-sorted entries with
``col_ptr`` segment offsets.  Padding slots carry rows=mb−1 (so the row
stream stays non-decreasing end to end and gathers may legally advertise
``indices_are_sorted``), cols=0, vals=0, valid=0 and contribute nothing to
any sum.

A block dense enough also carries its values and mask as a dense tile
(:func:`tile_rule`): three matrix products on the tile then cost less than
the segment engine's per-entry row gathers, so ``sparse/objective.py``
takes the tile wherever it is present.  The tile is one exact 1-byte code
a cell, ``value = lo + (code − 1)·step`` and 0 unobserved, where every
stored value decodes to itself bit for bit on one grid of at most 255
levels (:func:`code_grid`: any rating scale, minus a float32 mean); else a
float32 value and a bool mask a cell.  No value is rounded onto a grid.
The rule reads the block's capacity density, the backend, the device's
memory and the encoding's bytes a cell; the tile is built once at ingest
and kept in step by :func:`append_entries`; mb' × nb' is (mb, nb) padded
to whole device layout tiles of the encoding's dtype (:func:`tile_shape`).

``E`` is the per-block entry capacity: the maximum block nnz plus the
requested *headroom* (pre-allocated append slack for streaming ingestion),
rounded up to a *bucket* multiple, so recompilation only triggers when
occupancy crosses a bucket boundary, never per-matrix.  New ratings arrive
through :func:`append_entries`: each entry is routed to its block, spliced
into the (row, col) sorted order inside the existing capacity, and the
``col_perm``/``row_ptr``/``col_ptr`` aux views are patched incrementally —
no full re-sort, no shape change, so every jitted consumer keeps its
compiled executable (DESIGN.md §11).  The leading (p, q) axes shard exactly
like the dense tensors (P(row_axes, col_axes)), so the distributed gossip
step reuses its halo protocol unchanged.  Placement — which device owns
block (i, j) and the shard specs of every leaf — is answered by
``repro.mesh.MeshPlan`` (``SparseProblem.pspec`` is a back-compat thin
delegate); the device-owned view lives in ``sparse/sharded.py``
(``ShardedEntries``: per-device packing, owner-routed appends, per-shard
minibatch sampling).
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import grid as G
from repro.data.synthetic import MCDataset
from repro.sparse.entries import TILE_FIELDS, BlockEntries

DEFAULT_BUCKET = 256

# Capacity density E / (mb·nb) at and above which a block's f-term costs
# less from its dense masked tile than from the segment engine, per
# backend.  cpu: DESIGN.md §3's table (2048², 4×4, rank 8) has the sorted
# engine winning through 5 % (16.4 ms against dense 51 ms) and growing by
# 2.6–4 ms a point of density, so the dense form overtakes it near 15 %.
# tpu: one block visit timed on a TPU v5 lite at the ML-1M block
# (1,510 × 927, rank 32; benchmarks/tile_crossover.py, DESIGN.md §3):
# segment 894.8 / 364.2 / 284.2 / 257.5 µs at capacity 61,440 / 15,360 /
# 3,840 / 960 against the tile's 60.4–63.4 µs.  The segment engine keeps a
# floor (its per-row slab gathers do not shrink with the capacity), so the
# tile won at every density measured; the constant is the lowest of them,
# 960 / (1,510 · 927).  A backend with no measurement keeps the segment
# path.
TILE_CROSSOVER = {"cpu": 0.15, "tpu": 6.8e-4}
# Share of the device's memory the whole grid's tiles may take: they stay
# resident beside everything else a fit holds, and a wave step adds a
# float32 residual as large as each tile it visits.
TILE_MEMORY_SHARE = 1 / 8
# Bytes a tile cell takes, per encoding: a 1-byte value code (0 unobserved)
# where the store's values fit a grid (:func:`code_grid`), else a float32
# value and a bool mask.
TILE_BYTES_PER_CELL = {"codes": 1, "f32": 4 + 1}
# A tile's rows and columns are padded to whole tiles of the TPU's memory
# layout for its dtype, (32, 128) for 8 bits and (8, 128) for 32, the
# padding masked out.  Unpadded (1,510 × 927 at ML-1M), the device lays
# the (p, q, mb, nb) stack out with the grid axis q among its minor
# dimensions (the layout with least padding), so one block is no longer a
# contiguous slab and every read of a block became a strided copy: over
# half the device time of a round in a trace on a TPU v5 lite.
TILE_ALIGN = {"codes": (32, 128), "f32": (8, 128)}
# Codes 1..255 name grid levels; 0 is an unobserved cell.
CODE_LEVELS = 255


class SparseProblem(NamedTuple):
    """Blockified matrix-completion problem, observed entries only,
    segment-sorted by row with a precomputed column-sorted dual view.

    Two fields: the grid-stacked ``BlockEntries`` pytree plus the per-block
    ``nnz`` counts.  The flat per-field accessors (``sp.rows`` etc.) are
    kept as read-only properties for interop."""

    entries: BlockEntries  # every field stacked over the leading (p, q)
    nnz: jax.Array         # (p, q) int32

    # -- flat accessors (legacy surface; new code should use .entries) ----
    @property
    def rows(self) -> jax.Array:
        return self.entries.rows

    @property
    def cols(self) -> jax.Array:
        return self.entries.cols

    @property
    def vals(self) -> jax.Array:
        return self.entries.vals

    @property
    def valid(self) -> jax.Array:
        return self.entries.valid

    @property
    def col_perm(self) -> jax.Array:
        return self.entries.col_perm

    @property
    def row_ptr(self) -> jax.Array:
        return self.entries.row_ptr

    @property
    def col_ptr(self) -> jax.Array:
        return self.entries.col_ptr

    @property
    def capacity(self) -> int:
        return self.entries.capacity

    @property
    def has_tile(self) -> bool:
        """True when every block carries its dense masked tile."""

        return self.entries.has_tile

    @property
    def free_slots(self) -> jax.Array:
        """(p, q) append slack per block: capacity − nnz, i.e. how many
        entries :func:`append_entries` can still splice in before the
        bucket (incl. ingest headroom) overflows."""

        return self.capacity - self.nnz

    @property
    def mb(self) -> int:
        """Block row count (from the CSR offsets — the true shape source)."""

        return self.entries.mb

    @property
    def nb(self) -> int:
        """Block col count (from the CSC offsets)."""

        return self.entries.nb

    @classmethod
    def pspec(cls, spec2) -> "SparseProblem":
        """Matching pytree of PartitionSpecs: every leaf shards on its
        leading (p, q) axes.  Thin back-compat delegate —
        ``repro.mesh.MeshPlan`` is the single source of placement truth;
        prefer ``plan.entries_spec()``."""

        from repro.mesh.plan import entries_spec_like  # local: avoid cycle

        return entries_spec_like(spec2)


def bucketed_capacity(max_nnz: int, bucket: int = DEFAULT_BUCKET,
                      headroom: int = 0) -> int:
    """Per-block capacity: largest block nnz plus the requested append
    headroom, rounded up to a bucket multiple (≥ one bucket).  The
    headroom is part of the reported capacity — a store ingested with
    ``headroom=h`` is guaranteed ≥ h free slots in every block."""

    if bucket <= 0:
        raise ValueError(f"bucket must be positive, got {bucket}")
    if headroom < 0:
        raise ValueError(f"headroom must be non-negative, got {headroom}")
    return max(bucket, (max_nnz + headroom + bucket - 1) // bucket * bucket)


def device_bytes_limit() -> int | None:
    """The default device's memory in bytes, where the backend reports it
    (the TPU does; the CPU backend reports nothing)."""

    stats = jax.devices()[0].memory_stats()
    return int(stats["bytes_limit"]) if stats and "bytes_limit" in stats \
        else None


def tile_shape(mb: int, nb: int, encoding: str) -> tuple[int, int]:
    """A block's tile shape in ``encoding``: (mb, nb) padded to whole
    layout tiles of its dtype (:data:`TILE_ALIGN`)."""

    am, an = TILE_ALIGN[encoding]
    return -(-mb // am) * am, -(-nb // an) * an


def tile_rule(capacity: int, mb: int, nb: int, blocks: int, backend: str,
              bytes_limit: int | None, encoding: str) -> bool:
    """Whether a store's blocks carry a dense masked tile in ``encoding``.

    Both must hold: the capacity density ``capacity / (mb·nb)`` is at or
    above ``backend``'s crossover (the segment engine's cost grows with
    the capacity, the tile's with mb·nb), and the ``blocks`` tiles, at
    the encoding's :data:`TILE_BYTES_PER_CELL`, fit under
    :data:`TILE_MEMORY_SHARE` of ``bytes_limit`` where one is known.  A
    backend without a measured crossover builds none."""

    crossover = TILE_CROSSOVER.get(backend)
    if crossover is None or mb * nb == 0:
        return False
    if capacity < crossover * mb * nb:
        return False
    tm, tn = tile_shape(mb, nb, encoding)
    tile_bytes = blocks * tm * tn * TILE_BYTES_PER_CELL[encoding]
    return bytes_limit is None or tile_bytes <= TILE_MEMORY_SHARE * bytes_limit


def encode(vals, lo, step) -> tuple[np.ndarray, np.ndarray]:
    """``vals``' 1-byte codes on the grid ``lo + (code − 1)·step``, and
    for each whether it decodes to itself bit for bit in float32.  The
    product ``(code − 1)·step`` must be exact too, so that a decode gives
    the same bits whether or not the device fuses the multiply and the
    add.  A value that fails gets code 1; callers keep no such code."""

    v = np.asarray(vals, np.float32)
    lo, step = np.float32(lo), np.float32(step)
    with np.errstate(invalid="ignore", over="ignore"):
        k = np.rint((v.astype(np.float64) - lo) / step)
        ok = (k >= 0) & (k < CODE_LEVELS)
        k = np.where(ok, k, 0).astype(np.float32)
        prod = k * step
        ok &= prod.astype(np.float64) == k.astype(np.float64) * np.float64(
            step)
        ok &= (lo + prod).view(np.uint32) == v.view(np.uint32)
    return (k + 1).astype(np.uint8), ok


def decode(codes, grid):
    """float32 values and bool mask of a tile's 1-byte ``codes`` on
    ``grid`` (its last axis ``(lo, step)``): ``lo + (code − 1)·step`` and
    ``code ≠ 0``, inverse of :func:`encode` bit for bit.  Traced inside
    the residual that reads them (``sparse/objective.py``)."""

    lo, step = grid[..., 0, None, None], grid[..., 1, None, None]
    return lo + (codes.astype(jnp.float32) - 1.0) * step, codes != 0


def code_grid(vals) -> tuple[np.float32, np.float32] | None:
    """The grid ``(lo, step)`` on which every one of ``vals`` has an exact
    1-byte code (:func:`encode`), or ``None``.

    ``lo`` is the least value and ``step`` the least gap between distinct
    values, so the grid holds where the values span at most 255 of its
    levels and each decodes to itself.  A rating scale minus a float32
    mean does: half stars lie 0.5 apart and ``r − μ`` is exact while
    |r − μ| < 4.  Off-grid values are never rounded onto it: a store whose
    values miss it keeps the float32 tile."""

    v = np.unique(np.asarray(vals, np.float32))
    if len(v) < 2:
        grid = (np.float32(v[0] if len(v) else 0.0), np.float32(1.0))
    else:
        step = np.float32(np.diff(v.astype(np.float64)).min())
        if not step >= np.finfo(np.float32).tiny:    # a normal float only
            return None
        grid = (v[0], step)
    return grid if encode(v, *grid)[1].all() else None


def _dense_tiles(blk, rr, cc, vv, p: int, q: int, mb: int, nb: int):
    """(p, q, *tile_shape(mb, nb, "f32")) values and mask, on the host,
    from block-routed COO entries (``blk`` the flat block index)."""

    tm, tn = tile_shape(mb, nb, "f32")
    vals = np.zeros((p * q, tm, tn), np.float32)
    mask = np.zeros((p * q, tm, tn), bool)
    vals[blk, rr, cc] = vv
    mask[blk, rr, cc] = True
    return vals.reshape(p, q, tm, tn), mask.reshape(p, q, tm, tn)


def _code_tiles(blk, rr, cc, codes, grid, p: int, q: int, mb: int, nb: int):
    """(p, q, *tile_shape(mb, nb, "codes")) codes and the (p, q, 2) grid,
    on the host, from block-routed COO entries and their codes."""

    tm, tn = tile_shape(mb, nb, "codes")
    tile = np.zeros((p * q, tm, tn), np.uint8)
    tile[blk, rr, cc] = codes
    return (tile.reshape(p, q, tm, tn),
            np.tile(np.asarray(grid, np.float32), (p, q, 1)))


def _tile_admit(capacity: int, mb: int, nb: int, blocks: int):
    """``admit(encoding)`` for :func:`_host_tiles`: :func:`tile_rule` on
    this backend and this device's memory."""

    backend, limit = jax.default_backend(), device_bytes_limit()
    return lambda enc: tile_rule(capacity, mb, nb, blocks, backend, limit,
                                 enc)


def _host_tiles(blk, rr, cc, vv, p: int, q: int, mb: int, nb: int,
                admit=lambda encoding: True):
    """The four tile fields (``TILE_FIELDS`` order) on the host, from
    block-routed COO entries: codes where the values fit a grid
    (:func:`code_grid`), else float32 values and a mask.  ``admit(enc)``
    (:func:`tile_rule`) says whether a tile in that encoding may be built;
    all four are ``None`` where it may not."""

    none = (None,) * 4
    if not admit("codes"):        # the float32 tile takes more at any size
        return none
    grid = code_grid(vv)
    if grid is not None:
        codes, _ = encode(vv, *grid)
        return (None, None) + _code_tiles(blk, rr, cc, codes, grid, p, q,
                                          mb, nb)
    if not admit("f32"):
        return none
    return _dense_tiles(blk, rr, cc, vv, p, q, mb, nb) + (None, None)


def _stored(rows, cols, vals, nnz):
    """(blk, rows, cols, vals) of the real entries of (p·q, E) per-block
    entry arrays holding ``nnz`` entries each."""

    real = np.arange(rows.shape[1])[None, :] < np.asarray(nnz)[:, None]
    return np.nonzero(real)[0], rows[real], cols[real], vals[real]


def _with_tiles(entries: BlockEntries, tiles) -> BlockEntries:
    """``entries`` with the four host tile fields uploaded."""

    return entries._replace(**{f: None if t is None else jnp.asarray(t)
                               for f, t in zip(TILE_FIELDS, tiles)})


def set_padding_share(nnz_total: int, blocks: int, capacity: int) -> None:
    """The ``ingest_padding_share`` gauge: the share of the store's slots
    that hold no rating, 1 − Σ nnz ÷ (blocks × capacity).  The segment
    engine pays for every slot; ``bench/workcount.py`` counts ratings."""

    obs.gauge("ingest_padding_share").set(
        1.0 - nnz_total / (blocks * capacity) if blocks * capacity else 0.0)


def with_tile(sp: SparseProblem, encoding: str | None = None
              ) -> SparseProblem:
    """``sp`` with every block carrying its dense tile, whatever
    :func:`tile_rule` would say: what tests and benchmarks use to compare
    the arithmetics on one store.  The tile is encoded as ingest encodes
    it (codes where the values fit a grid), or as ``encoding`` names
    (``"f32"`` always can; ``"codes"`` raises off a grid)."""

    if sp.has_tile and encoding in (None, sp.entries.tile_encoding):
        return sp
    p, q, _ = sp.rows.shape
    blk, rows, cols, vals = _stored(
        *(np.asarray(a).reshape(p * q, -1) for a in (sp.rows, sp.cols,
                                                     sp.vals)),
        np.asarray(sp.nnz).reshape(-1))
    if encoding == "f32":
        tiles = _dense_tiles(blk, rows, cols, vals, p, q, sp.mb, sp.nb) \
            + (None, None)
    else:
        tiles = _host_tiles(blk, rows, cols, vals, p, q, sp.mb, sp.nb)
        if encoding == "codes" and tiles[2] is None:
            raise ValueError("the store's values fit no 1-byte code grid")
    return SparseProblem(_with_tiles(sp.entries, tiles), sp.nnz)


def drop_tile(sp: SparseProblem) -> SparseProblem:
    """The store without its dense tile: every f-term takes the segment
    path (mesh placement, and tests that pin the segment engine)."""

    return SparseProblem(sp.entries.without_tile(), sp.nnz)


def _pack_sorted(blk, rr, cc, vv, p, q, mb, nb, bucket,
                 headroom: int = 0,
                 capacity: int | None = None,
                 tile: bool = True) -> SparseProblem:
    """Shared packing tail: (block, row, col)-lexicographically sorted entry
    streams -> the padded, segment-sorted store.  ``blk`` must be
    non-decreasing with (rr, cc) lexicographic within each block.
    ``capacity`` forces the per-block capacity E — the sharded ingest path
    (``sparse/sharded.py``) packs each device's blocks independently but
    must agree on one global E, and passes ``tile=False``: its stores keep
    the segment path.  Otherwise :func:`tile_rule` decides whether the
    blocks carry their dense tile."""

    with obs.span("ingest.sort", annotate=True):
        host = _sorted_host(blk, rr, cc, vv, p, q, mb, nb, bucket, headroom,
                            capacity, tile)
    with obs.span("ingest.upload", annotate=True):
        entries = BlockEntries(*(None if a is None else jnp.asarray(a)
                                 for a in host[:-1]))
        sp = SparseProblem(entries, jnp.asarray(host[-1]))
    total, E = len(blk), entries.capacity
    nnz = host[-1]
    obs.counter("ingest_entries_total").inc(total)
    obs.gauge("ingest_tile_blocks").set(p * q if entries.has_tile else 0)
    enc = entries.tile_encoding
    obs.gauge("ingest_tile_bytes_per_cell").set(
        TILE_BYTES_PER_CELL[enc] if enc else 0)
    # min over blocks: the append slack of the block that would raise first
    obs.gauge("ingest_free_slots").set(int(E - (nnz.max() if total else 0)))
    set_padding_share(total, p * q, E)
    return sp


def _sorted_host(blk, rr, cc, vv, p, q, mb, nb, bucket, headroom, capacity,
                 tile):
    """The host arrays of :func:`_pack_sorted`'s store, in
    ``BlockEntries`` field order, then the (p, q) nnz counts."""

    total = len(blk)
    nnz = np.bincount(blk, minlength=p * q).astype(np.int64)
    E = (capacity if capacity is not None
         else bucketed_capacity(int(nnz.max()) if total else 0, bucket,
                                headroom))
    if int(nnz.max() if total else 0) > E:
        raise ValueError(
            f"forced capacity {E} below the largest block nnz "
            f"{int(nnz.max())}"
        )
    starts = np.zeros(p * q + 1, np.int64)
    np.cumsum(nnz, out=starts[1:])
    within = np.arange(total, dtype=np.int64) - starts[blk]
    dest = blk * E + within

    # padding rows sit at mb-1 so each block's row stream is non-decreasing
    # over the full capacity — the segment engine's sorted-gather contract
    rows = np.full(p * q * E, mb - 1, np.int32)
    cols = np.zeros(p * q * E, np.int32)
    vals = np.zeros(p * q * E, np.float32)
    valid = np.zeros(p * q * E, np.float32)
    rows[dest] = rr
    cols[dest] = cc
    vals[dest] = vv
    valid[dest] = 1.0

    # CSR offsets: per-(block, row) counts, cumulated along the row axis.
    rcnt = np.bincount(blk * mb + rr, minlength=p * q * mb).reshape(p * q, mb)
    row_ptr = np.zeros((p * q, mb + 1), np.int32)
    row_ptr[:, 1:] = np.cumsum(rcnt, axis=1)

    # CSC dual view: stable (block, col, row) order.  lexsort keeps the
    # block grouping (blk is already sorted and is the primary key), so the
    # i-th col-sorted entry of block b sits at global position starts[b]+i.
    order = np.lexsort((rr, cc, blk))
    col_perm = np.tile(np.arange(E, dtype=np.int32), p * q)  # padding -> itself
    col_perm[blk * E + within] = within[order].astype(np.int32)
    ccnt = np.bincount(blk * nb + cc, minlength=p * q * nb).reshape(p * q, nb)
    col_ptr = np.zeros((p * q, nb + 1), np.int32)
    col_ptr[:, 1:] = np.cumsum(ccnt, axis=1)

    tiles = (None,) * 4
    if tile:
        tiles = _host_tiles(blk, rr, cc, vv, p, q, mb, nb,
                            _tile_admit(E, mb, nb, p * q))
    return (rows.reshape(p, q, E), cols.reshape(p, q, E),
            vals.reshape(p, q, E), valid.reshape(p, q, E),
            col_perm.reshape(p, q, E), row_ptr.reshape(p, q, mb + 1),
            col_ptr.reshape(p, q, nb + 1), *tiles,
            nnz.reshape(p, q).astype(np.int32))


def from_blocks(
    xb: np.ndarray, maskb: np.ndarray, bucket: int = DEFAULT_BUCKET,
    headroom: int = 0,
) -> SparseProblem:
    """Convert blockified dense (p,q,mb,nb) tensors to the sorted store.

    Fully vectorized: one ``np.nonzero`` over the block tensor plus bincount
    packing — no per-entry (or per-block) Python loops, so MovieLens-scale
    ingest stays in numpy kernels.  ``np.nonzero``'s C order already yields
    (block, row, col) lexicographic entries, i.e. the row-sorted (CSR) view;
    the column-sorted (CSC) dual view is one ``np.lexsort`` away.
    ``headroom`` pre-allocates per-block append slack for
    :func:`append_entries` (streaming ingestion).
    """

    xb = np.asarray(xb)
    maskb = np.asarray(maskb)
    p, q, mb, nb = xb.shape
    bi, bj, rr, cc = np.nonzero(maskb)            # C order: row-sorted per block
    blk = bi * q + bj                             # non-decreasing
    return _pack_sorted(blk, rr, cc, xb[bi, bj, rr, cc], p, q, mb, nb,
                        bucket, headroom)


def from_entries(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    m: int,
    n: int,
    p: int,
    q: int,
    bucket: int = DEFAULT_BUCKET,
    headroom: int = 0,
) -> tuple[SparseProblem, tuple[int, int]]:
    """Build the sorted store straight from a global COO triplet list —
    no dense (m, n) materialization anywhere, the streaming-ingestion entry
    point.  The grid is padded implicitly (mb = ceil(m/p) etc.); returns
    the store plus the padded (m, n) so callers can build a ``GridSpec``.
    ``headroom`` pre-allocates per-block append slack so later
    :func:`append_entries` calls splice in place instead of overflowing.
    Duplicate (row, col) pairs are the caller's responsibility."""

    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, np.float32)
    if not (rows.shape == cols.shape == vals.shape) or rows.ndim != 1:
        raise ValueError(
            f"rows/cols/vals must be equal-length 1-D arrays, got "
            f"{rows.shape}/{cols.shape}/{vals.shape}"
        )
    if len(rows) and (rows.min() < 0 or rows.max() >= m
                      or cols.min() < 0 or cols.max() >= n):
        raise ValueError(
            f"entry indices out of range for a {m}x{n} matrix: rows in "
            f"[{rows.min()}, {rows.max()}], cols in [{cols.min()}, {cols.max()}]"
        )
    mb = -(-m // p)
    nb = -(-n // q)
    bi, rr = rows // mb, rows % mb
    bj, cc = cols // nb, cols % nb
    blk = bi * q + bj
    with obs.span("ingest.sort", annotate=True):
        order = np.lexsort((cc, rr, blk))          # (block, row, col) lexicographic
    sp = _pack_sorted(blk[order], rr[order].astype(np.int64),
                      cc[order].astype(np.int64), vals[order],
                      p, q, mb, nb, bucket, headroom)
    return sp, (mb * p, nb * q)


def from_dataset(
    ds: MCDataset, p: int, q: int, r: int, bucket: int = DEFAULT_BUCKET,
    headroom: int = 0,
) -> tuple[SparseProblem, G.GridSpec]:
    """Pad to the grid, blockify, and build the store.  Returns the padded
    GridSpec alongside (the spec's m/n include grid padding).  ``headroom``
    pre-allocates per-block append slack (streaming ingestion)."""

    x, mask, m, n = G.pad_to_grid(ds.x, ds.train_mask, p, q)
    spec = G.GridSpec(m, n, p, q, r)
    xb, maskb = G.blockify(x * mask, mask, spec)
    return from_blocks(xb, maskb, bucket, headroom), spec


def to_dense(sp: SparseProblem, mb: int | None = None,
             nb: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Back to dense (xb, maskb) block tensors — tests and interop.  Block
    dims default to the store's own CSR/CSC offsets."""

    mb = sp.mb if mb is None else mb
    nb = sp.nb if nb is None else nb
    rows = np.asarray(sp.rows)
    cols = np.asarray(sp.cols)
    vals = np.asarray(sp.vals)
    nnz = np.asarray(sp.nnz)
    p, q, _ = rows.shape
    xb = np.zeros((p, q, mb, nb), np.float32)
    maskb = np.zeros((p, q, mb, nb), np.float32)
    for i in range(p):
        for j in range(q):
            k = int(nnz[i, j])
            xb[i, j, rows[i, j, :k], cols[i, j, :k]] = vals[i, j, :k]
            maskb[i, j, rows[i, j, :k], cols[i, j, :k]] = 1.0
    return xb, maskb


def dedupe_last_write(rows, cols, vals, stride: int):
    """Resolve duplicate (row, col) pairs in a COO batch to the **last**
    occurrence (an edited rating wins over the one it edits).  ``stride``
    is the column count of the indexing frame; the single definition of
    append dedup semantics for both layouts (``append_entries`` and
    ``CompletionProblem.append``)."""

    lin = rows * stride + cols
    order = np.argsort(lin, kind="stable")
    last = np.ones(len(order), bool)
    last[:-1] = lin[order][1:] != lin[order][:-1]
    order = order[last]
    return rows[order], cols[order], vals[order]


def _splice_block(ent, rptr, cptr, nnz, b, nrr, ncc, nvv, mb, nb, E,
                  label: str):
    """Splice one block's new entries into its sorted prefix, in place.

    ``ent`` maps field name -> (nblocks, E) arrays; ``rptr``/``cptr``/
    ``nnz`` are the matching flattened offset/count arrays; ``b`` is the
    flat block index *within those arrays*.  ``label`` names the block in
    overflow errors (global (i, j) coords — the sharded append passes the
    global label even though its arrays are device-local).  This is the
    single definition of the sorted-splice merge, shared by the global
    :func:`append_entries` and the owner-routed ``ShardedEntries.append``.
    """

    k = int(nnz[b])
    # new entries in the block's (row, col) lexicographic key order
    nkey = nrr * nb + ncc
    ks = np.argsort(nkey)
    nkey = nkey[ks]
    nrr, ncc = nrr[ks], ncc[ks]
    nvv = nvv[ks]
    ekey = ent["rows"][b, :k].astype(np.int64) * nb + ent["cols"][b, :k]
    idx = np.searchsorted(ekey, nkey)
    if k:
        dup = (idx < k) & (ekey[np.minimum(idx, k - 1)] == nkey)
    else:
        dup = np.zeros(len(nkey), bool)
    if dup.any():                        # edited ratings: value-only patch
        ent["vals"][b, idx[dup]] = nvv[dup]
    ins = ~dup
    n_ins = int(ins.sum())
    if n_ins == 0:
        return
    k2 = k + n_ins
    if k2 > E:
        raise ValueError(
            f"append overflows block {label}: {k} stored + {n_ins} new "
            f"entries > capacity {E}; re-ingest with headroom>={k2 - E} "
            f"more than before (from_entries/from_dataset headroom=) or "
            f"a larger bucket to pre-allocate append slack"
        )
    irr, icc, ivv = nrr[ins], ncc[ins], nvv[ins]
    # the classic merge, by insertion index: old entry i shifts by the
    # number of inserts landing at or before it, insert j lands at its
    # searchsorted position plus the inserts already placed before it
    pos = np.searchsorted(ekey, nkey[ins])
    old_dest = np.arange(k) + np.searchsorted(pos, np.arange(k), "right")
    ins_dest = pos + np.arange(n_ins)
    # CSC keys of the old prefix, in CSC order — before the splice below
    old_perm = ent["col_perm"][b, :k]
    ckey_sorted = (ent["cols"][b, :k].astype(np.int64) * mb
                   + ent["rows"][b, :k])[old_perm]
    for f, new in (("rows", irr), ("cols", icc), ("vals", ivv)):
        merged = np.empty(k2, ent[f].dtype)
        merged[old_dest] = ent[f][b, :k]
        merged[ins_dest] = new
        ent[f][b, :k2] = merged
    ent["valid"][b, :k2] = 1.0
    # patch the segment offsets with cumulated per-row/col insert counts
    rptr[b, 1:] += np.cumsum(np.bincount(irr, minlength=mb)).astype(
        rptr.dtype)
    cptr[b, 1:] += np.cumsum(np.bincount(icc, minlength=nb)).astype(
        cptr.dtype)
    # same merge in the (col, row) dual order re-threads col_perm: old
    # CSC slots shift by the inserts sorting before them and map to the
    # spliced CSR positions of the entries they pointed at
    corder = np.argsort(icc * mb + irr)
    cpos = np.searchsorted(ckey_sorted, (icc * mb + irr)[corder])
    perm2 = np.empty(k2, np.int32)
    t = np.arange(k)
    perm2[t + np.searchsorted(cpos, t, "right")] = old_dest[old_perm]
    perm2[cpos + np.arange(n_ins)] = ins_dest[corder]
    ent["col_perm"][b, :k2] = perm2
    ent["col_perm"][b, k2:] = np.arange(k2, E)   # padding -> itself
    nnz[b] = k2


def append_entries(
    sp: SparseProblem,
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
) -> SparseProblem:
    """Splice new observed entries into the sorted padded-COO store —
    streaming ingestion without a re-sort or a shape change.

    ``rows``/``cols`` are global indices in the store's padded frame
    (p·mb × q·nb).  Each entry is routed to its block and merged into the
    existing (row, col) lexicographic order at its ``searchsorted``
    position; the CSR/CSC aux views are patched incrementally —
    ``row_ptr``/``col_ptr`` gain the cumulated per-row/col insert counts
    and ``col_perm`` is re-threaded by the same merge in the (col, row)
    dual order — so the segment-reduce fast path stays valid without ever
    re-sorting the stored prefix (DESIGN.md §11).  A dense tile, where
    the store carries one, gets the new cells: values and mask, or the
    codes of values on the tile's grid.  A value off that grid is never
    rounded onto it: the tile is then built afresh from the store's
    values by ingest's rule (a new grid where they fit one, else float32
    values and a mask where :func:`tile_rule` admits those, else no tile
    and the segment engine), and jitted consumers compile again for it.
    Capacity is untouched: otherwise jitted consumers keep their compiled
    executables, which is the point of pre-allocating ``headroom=`` at
    ingest.

    A (row, col) pair already present updates its value in place (an
    edited rating) and costs no slot; duplicate pairs within one append
    batch resolve to the last occurrence.  An empty append returns ``sp``
    unchanged.  Raises ``ValueError`` when a block's remaining
    ``free_slots`` cannot hold the new entries, with the headroom needed
    to have absorbed the append.
    """

    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, np.float32)
    if not (rows.shape == cols.shape == vals.shape) or rows.ndim != 1:
        raise ValueError(
            f"rows/cols/vals must be equal-length 1-D arrays, got "
            f"{rows.shape}/{cols.shape}/{vals.shape}"
        )
    if len(rows) == 0:
        return sp
    t0 = time.perf_counter()
    p, q = sp.nnz.shape
    mb, nb = sp.mb, sp.nb
    m, n = p * mb, q * nb
    if (rows.min() < 0 or rows.max() >= m
            or cols.min() < 0 or cols.max() >= n):
        raise ValueError(
            f"append indices out of range for the {m}x{n} padded grid: rows "
            f"in [{rows.min()}, {rows.max()}], cols in "
            f"[{cols.min()}, {cols.max()}]"
        )

    rows, cols, vals = dedupe_last_write(rows, cols, vals, n)

    bi, rr = rows // mb, rows % mb
    bj, cc = cols // nb, cols % nb
    blk = bi * q + bj

    E = sp.capacity
    ent = {f: np.asarray(getattr(sp.entries, f)).reshape(p * q, -1).copy()
           for f in ("rows", "cols", "vals", "valid", "col_perm")}
    rptr = np.asarray(sp.row_ptr).reshape(p * q, mb + 1).copy()
    cptr = np.asarray(sp.col_ptr).reshape(p * q, nb + 1).copy()
    nnz = np.asarray(sp.nnz).reshape(p * q).copy()

    for b in np.unique(blk):
        sel = blk == b
        i, j = divmod(int(b), q)
        _splice_block(ent, rptr, cptr, nnz, int(b), rr[sel], cc[sel],
                      vals[sel], mb, nb, E, label=f"({i},{j})")

    # the dense tile follows the splice: set the cells
    tiles = tuple(getattr(sp.entries, f) for f in TILE_FIELDS)
    enc = sp.entries.tile_encoding
    if enc == "f32":
        tv, tm = (np.asarray(t).copy() for t in tiles[:2])
        tv[bi, bj, rr, cc] = vals
        tm[bi, bj, rr, cc] = True
        tiles = (tv, tm, None, None)
    elif enc == "codes":
        codes, ok = encode(vals, *np.asarray(tiles[3])[0, 0])
        if ok.all():
            tc = np.asarray(tiles[2]).copy()
            tc[bi, bj, rr, cc] = codes
            tiles = (None, None, tc, tiles[3])
        else:             # off the grid: encode the whole tile afresh
            tiles = _host_tiles(*_stored(ent["rows"], ent["cols"],
                                         ent["vals"], nnz), p, q, mb, nb,
                                _tile_admit(E, mb, nb, p * q))

    entries = _with_tiles(BlockEntries(
        jnp.asarray(ent["rows"].reshape(p, q, E)),
        jnp.asarray(ent["cols"].reshape(p, q, E)),
        jnp.asarray(ent["vals"].reshape(p, q, E)),
        jnp.asarray(ent["valid"].reshape(p, q, E)),
        jnp.asarray(ent["col_perm"].reshape(p, q, E)),
        jnp.asarray(rptr.reshape(p, q, mb + 1)),
        jnp.asarray(cptr.reshape(p, q, nb + 1)),
    ), tiles)
    out = SparseProblem(entries,
                        jnp.asarray(nnz.reshape(p, q).astype(np.int32)))
    # the ingest plane's scoreboard: calls, entries, splice latency, and
    # how close the buckets are to overflowing (min over blocks — the
    # block that will raise first)
    obs.counter("ingest_appends_total").inc()
    obs.counter("ingest_appended_entries_total").inc(len(rows))
    obs.histogram("ingest_append_seconds").observe(time.perf_counter() - t0)
    obs.gauge("ingest_free_slots").set(int((E - nnz).min()))
    return out


def density(sp: SparseProblem, spec: G.GridSpec | int | None = None,
            nb: int | None = None) -> float:
    """Fraction of observed entries.

    Block shape comes from a ``GridSpec`` (``density(sp, spec)``), from the
    store's own CSR/CSC offsets (``density(sp)``), or from explicit
    ``density(sp, mb, nb)`` ints for backwards compatibility.

    The denominator is the (padded) matrix area p·q·mb·nb, **not** the
    store's slot count: padding and pre-allocated headroom slots are
    excluded, so density reports how sparse the data is, never how full
    the buckets are (that is ``sp.free_slots``).
    """

    if isinstance(spec, G.GridSpec):
        mb_, nb_ = spec.mb, spec.nb
    elif spec is None:
        mb_, nb_ = sp.mb, sp.nb
    else:
        if nb is None:
            raise TypeError("density(sp, mb, nb) needs both block dims")
        mb_, nb_ = spec, nb
    return float(jnp.sum(sp.nnz)) / (sp.nnz.shape[0] * sp.nnz.shape[1] * mb_ * nb_)


def ensure_layout(problem, layout: str | None, bucket: int = DEFAULT_BUCKET):
    """Coerce a problem to the requested layout.

    ``None`` (the default) infers the layout from the problem type —
    passing a ``SparseProblem`` is enough to get the sparse path.
    ``"sparse"`` converts a dense ``Problem`` via :func:`from_blocks` (a
    SparseProblem passes through).  ``"dense"`` only validates — going back
    to dense tensors is an explicit :func:`to_dense` call, not a layout
    coercion.
    """

    from repro.core.state import Problem  # local import: state is layout-agnostic

    if layout is None:
        return problem
    if layout == "sparse":
        if isinstance(problem, SparseProblem):
            return problem
        return from_blocks(problem.xb, problem.maskb, bucket)
    if layout == "dense":
        if isinstance(problem, SparseProblem):
            raise ValueError(
                "layout='dense' but got a SparseProblem; convert with "
                "sparse.to_dense(sp) first"
            )
        return problem
    raise ValueError(f"unknown layout {layout!r}; expected 'dense' or 'sparse'")


# ---------------------------------------------------------------------------
# Streaming minibatch sampling over observed entries
# ---------------------------------------------------------------------------


def _sample_block(k, rows, cols, vals, nnz, *, batch: int, mb: int, nb: int):
    """One block's uniform with-replacement minibatch (the shared inner
    sampler of :func:`sample_minibatch` and the mesh-aware per-shard path
    in ``sparse/sharded.py``).  Sampled *positions* are sorted before
    gathering so the batch inherits the store's row-sorted order and
    carries fresh CSR/CSC offsets."""

    idx = jax.random.randint(k, (batch,), 0, jnp.maximum(nnz, 1))
    idx = jnp.sort(idx)                     # sorted positions -> sorted rows
    ok = (nnz > 0).astype(jnp.float32)
    r_ = jnp.take(rows, idx, indices_are_sorted=True, mode="clip")
    c_ = jnp.take(cols, idx, indices_are_sorted=True, mode="clip")
    v_ = jnp.take(vals, idx, indices_are_sorted=True, mode="clip")
    rptr = jnp.searchsorted(r_, jnp.arange(mb + 1)).astype(jnp.int32)
    perm = jnp.argsort(c_, stable=True).astype(jnp.int32)
    cptr = jnp.searchsorted(
        jnp.take(c_, perm, mode="clip"), jnp.arange(nb + 1)
    ).astype(jnp.int32)
    return r_, c_, v_, ok * jnp.ones((batch,), jnp.float32), perm, rptr, cptr


def _assemble_batch(parts, p: int, q: int, batch: int, mb: int, nb: int,
                    nnz) -> SparseProblem:
    """Pack the vmapped per-block sampler outputs into a SparseProblem."""

    rows, cols, vals, valid, perm, rptr, cptr = parts
    shape = (p, q, batch)
    entries = BlockEntries(
        rows.reshape(shape), cols.reshape(shape), vals.reshape(shape),
        valid.reshape(shape), perm.reshape(shape),
        rptr.reshape(p, q, mb + 1), cptr.reshape(p, q, nb + 1),
    )
    return SparseProblem(
        entries, jnp.where(nnz > 0, batch, 0).astype(jnp.int32)
    )


def sample_minibatch(key: jax.Array, sp: SparseProblem, batch: int) -> SparseProblem:
    """Uniform with-replacement sample of ``batch`` observed entries per block.

    Returns a SparseProblem with capacity ``batch``.  Sampled *positions*
    are sorted before gathering, so the batch inherits the store's
    row-sorted order (rows non-decreasing) and carries fresh
    ``row_ptr``/``col_ptr``/``col_perm`` offsets — stochastic gossip rounds
    stay on the segment-reduce fast path.  Empty blocks sample all-invalid
    slots.  The per-block stochastic gradient built from a minibatch
    estimates the full-block gradient scaled by batch/nnz; use
    :func:`minibatch_grad_scale` to correct when unbiasedness matters.
    """

    p, q, _ = sp.rows.shape
    mb, nb = sp.mb, sp.nb
    one = functools.partial(_sample_block, batch=batch, mb=mb, nb=nb)
    keys = jax.random.split(key, p * q)
    parts = jax.vmap(one)(
        keys,
        sp.rows.reshape(p * q, -1),
        sp.cols.reshape(p * q, -1),
        sp.vals.reshape(p * q, -1),
        sp.nnz.reshape(p * q),
    )
    return _assemble_batch(parts, p, q, batch, mb, nb, sp.nnz)


def minibatch_grad_scale(sp: SparseProblem, batch: int) -> jax.Array:
    """(p, q) factor making minibatch f-gradients unbiased: nnz/batch."""

    return sp.nnz.astype(jnp.float32) / float(batch)


class MinibatchStream:
    """Stateless (step -> minibatch) sampler, mirroring LMTokenPipeline's
    restart-exact contract: ``batch_at(step)`` is a pure function of
    (seed, step), so checkpoint resume replays the identical entry stream.
    ``seed`` takes an int or a ready PRNG key (the ``Gossip`` schedule
    derives the stream base from its fit key so resumed stochastic fits
    replay the identical minibatches).

    Mesh-aware mode: pass a ``repro.mesh.MeshPlan`` and the store is
    placed onto its owners once, after which every ``batch_at`` samples
    **per shard** under ``shard_map`` — each device draws only its own
    blocks' entries from its local shard, with per-block keys derived by
    ``fold_in(fold_in(seed_key, step), global_block_id)``.  Because the
    key of block (i, j) depends only on (seed, step, i, j), the sampled
    stream is identical for every mesh shape (host-count invariant) and
    stays restart-exact; no host ever materializes another host's
    entries.  ``plan=None`` keeps the original single-host sampler
    bit-for-bit (split-based keys).

    All per-block setup — flattened entry views, the gid table, the
    compiled sampler — is memoized at construction: ``batch_at`` inside a
    fit loop is one fold_in plus one cached jitted call, no repeated
    host-side derivation (the fit-loop hot path)."""

    def __init__(self, sp: SparseProblem, batch: int, seed=0, plan=None):
        self.sp = sp
        self.batch = batch
        self.seed = seed
        self.plan = plan
        self._base = (seed if isinstance(seed, jax.Array)
                      else jax.random.PRNGKey(seed))
        self._sharded = None
        p, q, _ = sp.rows.shape
        if plan is not None:
            from repro.sparse.sharded import (  # avoid cycle
                ShardedEntries, _gid_table, _make_shard_sampler,
            )

            self._sharded = ShardedEntries.from_problem(sp, plan)
            self._gids = _gid_table(plan.p, plan.q)
            self._fn = _make_shard_sampler(plan, batch, sp.capacity,
                                           sp.mb, sp.nb)
        else:
            mb, nb = sp.mb, sp.nb
            # pre-flattened block views + one compiled sampler: the exact
            # ops of sample_minibatch, with the per-call reshapes and
            # partial re-derivation hoisted out of the fit loop
            self._flat = (
                sp.rows.reshape(p * q, -1), sp.cols.reshape(p * q, -1),
                sp.vals.reshape(p * q, -1), sp.nnz.reshape(p * q),
            )
            one = functools.partial(_sample_block, batch=batch, mb=mb, nb=nb)

            def sample(key, rows2, cols2, vals2, nnz1, nnz2):
                keys = jax.random.split(key, p * q)
                parts = jax.vmap(one)(keys, rows2, cols2, vals2, nnz1)
                return _assemble_batch(parts, p, q, batch, mb, nb, nnz2)

            self._fn = jax.jit(sample)

    def batch_at(self, step: int) -> SparseProblem:
        key = jax.random.fold_in(self._base, step)
        if self._sharded is not None:
            return self._fn(self._sharded.sp, self._gids, key)
        return self._fn(key, *self._flat, self.sp.nnz)
