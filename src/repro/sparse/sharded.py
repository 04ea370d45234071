"""``ShardedEntries`` — the device-owned view of the sparse block store.

The global :class:`~repro.sparse.store.SparseProblem` is a logically
(p, q)-stacked pytree; a ``MeshPlan`` says which device owns each block.
This module makes that ownership *physical* without ever materializing a
global COO on any single host:

* :meth:`ShardedEntries.from_coo` routes each raw (row, col, val) triplet
  to its owning device and packs **each device's blocks independently**
  (per-shard lexsort + ``_pack_sorted`` with one agreed global capacity);
  the global ``jax.Array`` is assembled shard-by-shard via
  ``make_array_from_callback`` — no host holds the full sorted store.
* :meth:`ShardedEntries.append` routes streaming appends the same way:
  only the owners of touched blocks splice (the same ``_splice_block``
  merge the single-host :func:`~repro.sparse.store.append_entries` uses),
  and untouched device shards are reused verbatim — no global gather, no
  re-sort, no shape change.
* :func:`sample_minibatch_sharded` draws each block's minibatch **on its
  owner** under ``shard_map``, with per-block keys
  ``fold_in(fold_in(step_key, step), block_id)`` — deterministic per
  host, identical for every mesh shape, restart-exact.
* :func:`f_grads_sharded` evaluates the nnz-proportional f-gradients
  shard-locally (block-local math, so sharded == global exactly; the
  cross-shard consensus terms are the gossip halo protocol's job).

Single-device plans degrade to the plain global path bit-for-bit — the
callback assembly collapses to one shard and ``shard_map`` to a no-op
partitioning (parity-pinned by ``tests/test_mesh_plan.py``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.mesh.plan import MeshPlan
from repro.sparse import store as store_mod
from repro.sparse.entries import SORTED_FIELDS
from repro.sparse.store import (
    DEFAULT_BUCKET,
    SparseProblem,
    bucketed_capacity,
    dedupe_last_write,
)


def _slice_start(s) -> int:
    return 0 if s.start is None else int(s.start)


@dataclasses.dataclass(frozen=True)
class ShardedEntries:
    """A ``SparseProblem`` whose leaves live on their owning devices.

    ``sp`` is still the global logical store (same shapes, same
    consumers); the invariant this class adds is *placement*: every leaf
    is sharded with ``plan.entries_spec()``, so device (di, dj) holds
    exactly the blocks ``plan.local_blocks(di, dj)``.  All jitted
    consumers (gossip steps, sharded sampling, sharded gradients) then
    run without any input resharding."""

    sp: SparseProblem
    plan: MeshPlan

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #

    @classmethod
    def from_problem(cls, sp: SparseProblem, plan: MeshPlan) -> "ShardedEntries":
        """Place an existing (host-built) store onto its owners."""

        p, q = sp.nnz.shape
        if (p, q) != (plan.p, plan.q):
            raise ValueError(
                f"store grid {p}x{q} does not match plan grid "
                f"{plan.p}x{plan.q}"
            )
        return cls(plan.place_entries(sp), plan)

    @classmethod
    def from_coo(
        cls,
        rows: np.ndarray,
        cols: np.ndarray,
        vals: np.ndarray,
        m: int,
        n: int,
        plan: MeshPlan,
        bucket: int = DEFAULT_BUCKET,
        headroom: int = 0,
    ) -> tuple["ShardedEntries", tuple[int, int]]:
        """Owner-routed ingest from a global COO triplet list.

        Each entry is routed to its owning device shard and every shard's
        blocks are packed independently (shard-local lexsort — the global
        (block, row, col) sort never happens anywhere).  The only global
        coordination is a per-block nnz count to agree on the shared
        capacity E (a (p, q) int reduction, not entry data).  Returns the
        sharded store plus the padded (M, N), mirroring
        :func:`~repro.sparse.store.from_entries`."""

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
                f"[{rows.min()}, {rows.max()}], cols in "
                f"[{cols.min()}, {cols.max()}]"
            )
        p, q = plan.p, plan.q
        mb = -(-m // p)
        nb = -(-n // q)
        bi, rr = rows // mb, rows % mb
        bj, cc = cols // nb, cols % nb
        # the one global reduction: per-block counts -> shared capacity E
        nnz = np.bincount(bi * q + bj, minlength=p * q)
        E = bucketed_capacity(int(nnz.max()) if len(rows) else 0, bucket,
                              headroom)

        bpr, bpc = plan.blocks_per_row_shard, plan.blocks_per_col_shard
        di, dj = bi // bpr, bj // bpc
        shard_of = di * plan.col_size + dj
        shards: dict[tuple[int, int], SparseProblem] = {}
        for sdi in range(plan.row_size):
            for sdj in range(plan.col_size):
                sel = shard_of == sdi * plan.col_size + sdj
                # per-owner routing counts: a skewed ingest (hot shard)
                # shows up here before it shows up as a straggler
                obs.counter("ingest_routed_entries_total",
                            shard=f"{sdi},{sdj}").inc(int(sel.sum()))
                lbi = bi[sel] - sdi * bpr          # shard-local block coords
                lbj = bj[sel] - sdj * bpc
                lrr, lcc, lvv = rr[sel], cc[sel], vals[sel]
                blk = lbi * bpc + lbj
                order = np.lexsort((lcc, lrr, blk))  # shard-local sort only
                shards[sdi, sdj] = store_mod._pack_sorted(
                    blk[order], lrr[order], lcc[order], lvv[order],
                    bpr, bpc, mb, nb, bucket, headroom, capacity=E,
                    tile=False,
                )
        sp = cls._assemble(plan, shards, E, mb, nb)
        # each shard's pack set the gauge to its own share: the whole grid's
        store_mod.set_padding_share(len(rows), p * q, E)
        return cls(sp, plan), (mb * p, nb * q)

    @classmethod
    def _assemble(cls, plan: MeshPlan, shards, E: int, mb: int,
                  nb: int) -> SparseProblem:
        """Glue per-device local stores into global sharded jax.Arrays."""

        bpr, bpc = plan.blocks_per_row_shard, plan.blocks_per_col_shard
        p, q = plan.p, plan.q
        espec = plan.entries_spec()

        def leaf(get, shape, spec):
            local = {k: np.asarray(get(v)) for k, v in shards.items()}

            def cb(idx):
                key = (_slice_start(idx[0]) // bpr,
                       _slice_start(idx[1]) // bpc)
                return local[key]

            return jax.make_array_from_callback(shape, plan.sharding(spec),
                                                cb)

        fields = {
            "rows": ((p, q, E), lambda s: s.rows),
            "cols": ((p, q, E), lambda s: s.cols),
            "vals": ((p, q, E), lambda s: s.vals),
            "valid": ((p, q, E), lambda s: s.valid),
            "col_perm": ((p, q, E), lambda s: s.col_perm),
            "row_ptr": ((p, q, mb + 1), lambda s: s.row_ptr),
            "col_ptr": ((p, q, nb + 1), lambda s: s.col_ptr),
        }
        entries = type(espec.entries)(*[
            leaf(get, shape, getattr(espec.entries, f))
            for f, (shape, get) in fields.items()
        ])
        nnz = leaf(lambda s: s.nnz, (p, q), espec.nnz)
        return SparseProblem(entries, nnz)

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #

    @property
    def capacity(self) -> int:
        return self.sp.capacity

    @property
    def nnz(self):
        return self.sp.nnz

    def local(self, di: int, dj: int) -> SparseProblem:
        """Device (di, dj)'s shard as a host-side ``SparseProblem`` over
        its local (bpr, bpc) block grid — what that device physically
        holds.  Test/debug surface; the hot paths never call this."""

        local = {f: np.asarray(self._shard_map(getattr(self.sp, f))[di, dj].data)
                 for f in ("rows", "cols", "vals", "valid", "col_perm",
                           "row_ptr", "col_ptr", "nnz")}
        entries = type(self.sp.entries)(
            local["rows"], local["cols"], local["vals"], local["valid"],
            local["col_perm"], local["row_ptr"], local["col_ptr"],
        )
        return SparseProblem(jax.tree.map(jnp.asarray, entries),
                             jnp.asarray(local["nnz"]))

    def _shard_map(self, arr) -> dict:
        """Map device-grid coords -> that device's Shard handle.  Data is
        only pulled to host (``np.asarray(shard.data)``) at the point of
        use, so reading one shard never copies the others."""

        bpr = self.plan.blocks_per_row_shard
        bpc = self.plan.blocks_per_col_shard
        return {(_slice_start(s.index[0]) // bpr,
                 _slice_start(s.index[1]) // bpc): s
                for s in arr.addressable_shards}

    # ------------------------------------------------------------------ #
    # streaming append — owner-routed, no global gather
    # ------------------------------------------------------------------ #

    def append(self, rows, cols, vals) -> "ShardedEntries":
        """Splice new entries into their owning devices' shards.

        Same semantics as the single-host
        :func:`~repro.sparse.store.append_entries` (sorted splice,
        in-place value updates for duplicates, last-write-wins within the
        batch, overflow raises with the needed headroom) — but each entry
        is routed to its owner and **only touched shards are rebuilt**;
        every other device's data is reused verbatim.  No host ever sees
        another host's entries."""

        sp, plan = self.sp, self.plan
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals, np.float32)
        if not (rows.shape == cols.shape == vals.shape) or rows.ndim != 1:
            raise ValueError(
                f"rows/cols/vals must be equal-length 1-D arrays, got "
                f"{rows.shape}/{cols.shape}/{vals.shape}"
            )
        if len(rows) == 0:
            return self
        p, q = plan.p, plan.q
        mb, nb = sp.mb, sp.nb
        m, n = p * mb, q * nb
        if (rows.min() < 0 or rows.max() >= m
                or cols.min() < 0 or cols.max() >= n):
            raise ValueError(
                f"append indices out of range for the {m}x{n} padded grid: "
                f"rows in [{rows.min()}, {rows.max()}], cols in "
                f"[{cols.min()}, {cols.max()}]"
            )
        rows, cols, vals = dedupe_last_write(rows, cols, vals, n)

        bi, rr = rows // mb, rows % mb
        bj, cc = cols // nb, cols % nb
        bpr, bpc = plan.blocks_per_row_shard, plan.blocks_per_col_shard
        sdi, sdj = bi // bpr, bj // bpc
        E = sp.capacity

        # split the batch by owner; splice each owner's blocks locally
        shard_maps = {f: self._shard_map(getattr(sp, f))
                      for f in ("rows", "cols", "vals", "valid", "col_perm",
                                "row_ptr", "col_ptr", "nnz")}
        patched: dict[tuple[int, int], dict[str, np.ndarray]] = {}
        for key in sorted(set(zip(sdi.tolist(), sdj.tolist()))):
            osel = (sdi == key[0]) & (sdj == key[1])
            obs.counter("ingest_routed_entries_total",
                        shard=f"{key[0]},{key[1]}").inc(int(osel.sum()))
            loc = {f: np.asarray(shard_maps[f][key].data)
                   for f in shard_maps}
            ent = {f: loc[f].reshape(bpr * bpc, -1).copy()
                   for f in ("rows", "cols", "vals", "valid", "col_perm")}
            rptr = loc["row_ptr"].reshape(bpr * bpc, mb + 1).copy()
            cptr = loc["col_ptr"].reshape(bpr * bpc, nb + 1).copy()
            nnz = loc["nnz"].reshape(bpr * bpc).copy()
            lbi = bi[osel] - key[0] * bpr
            lbj = bj[osel] - key[1] * bpc
            blk = lbi * bpc + lbj
            for b in np.unique(blk):
                bsel = blk == b
                gi = key[0] * bpr + int(b) // bpc
                gj = key[1] * bpc + int(b) % bpc
                store_mod._splice_block(
                    ent, rptr, cptr, nnz, int(b), rr[osel][bsel],
                    cc[osel][bsel], vals[osel][bsel], mb, nb, E,
                    label=f"({gi},{gj})",
                )
            patched[key] = {
                "rows": ent["rows"].reshape(bpr, bpc, E),
                "cols": ent["cols"].reshape(bpr, bpc, E),
                "vals": ent["vals"].reshape(bpr, bpc, E),
                "valid": ent["valid"].reshape(bpr, bpc, E),
                "col_perm": ent["col_perm"].reshape(bpr, bpc, E),
                "row_ptr": rptr.reshape(bpr, bpc, mb + 1),
                "col_ptr": cptr.reshape(bpr, bpc, nb + 1),
                "nnz": nnz.reshape(bpr, bpc).astype(np.int32),
            }

        espec = plan.entries_spec()

        def rebuild(field, arr, spec):
            # patched shards are device_put onto their owner; every other
            # shard's existing device buffer is reused verbatim — an
            # append costs O(touched shards) transfer, never O(store)
            parts = [
                jax.device_put(patched[key][field], s.device)
                if key in patched else s.data
                for key, s in shard_maps[field].items()
            ]
            return jax.make_array_from_single_device_arrays(
                arr.shape, plan.sharding(spec), parts
            )

        entries = type(sp.entries)(*[
            rebuild(f, getattr(sp.entries, f), getattr(espec.entries, f))
            for f in SORTED_FIELDS
        ])
        nnz = rebuild("nnz", sp.nnz, espec.nnz)
        return ShardedEntries(SparseProblem(entries, nnz), plan)


# ---------------------------------------------------------------------- #
# per-shard minibatch sampling (mesh-aware MinibatchStream backend)
# ---------------------------------------------------------------------- #


@functools.lru_cache(maxsize=None)
def _gid_table(p: int, q: int):
    """Memoized (p, q) global-block-id table — the per-block fold_in keys'
    second operand; built once per grid shape, not per sample call."""

    return jnp.arange(p * q, dtype=jnp.uint32).reshape(p, q)


@functools.lru_cache(maxsize=None)
def _make_shard_sampler(plan: MeshPlan, batch: int, E: int, mb: int, nb: int):
    """Compiled shard-local sampler: each device draws its own blocks'
    minibatches with fold_in(step_key, global_block_id) keys."""

    p, q = plan.p, plan.q
    bpr, bpc = plan.blocks_per_row_shard, plan.blocks_per_col_shard
    espec = plan.entries_spec()

    def body(spl: SparseProblem, gids, key):
        one = functools.partial(store_mod._sample_block, batch=batch,
                                mb=mb, nb=nb)
        keys = jax.vmap(lambda g: jax.random.fold_in(key, g))(
            gids.reshape(-1)
        )
        parts = jax.vmap(one)(
            keys,
            spl.rows.reshape(bpr * bpc, -1),
            spl.cols.reshape(bpr * bpc, -1),
            spl.vals.reshape(bpr * bpc, -1),
            spl.nnz.reshape(bpr * bpc),
        )
        return store_mod._assemble_batch(parts, bpr, bpc, batch, mb, nb,
                                         spl.nnz)

    return jax.jit(jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(espec, plan.grid_spec, P()),
        out_specs=espec,
        check_vma=False,
    ))


def sample_minibatch_sharded(key: jax.Array, sharded: ShardedEntries,
                             batch: int) -> SparseProblem:
    """Per-shard uniform minibatch over a device-owned store.

    Block (i, j)'s sample depends only on (``key``, its global block id,
    its own entries) — never on the mesh shape — so a 1×1 plan, a 2×2
    plan and a plain host-side run of the same fold-in scheme all yield
    identical batches (mesh-shape invariance, pinned by
    ``tests/test_mesh_plan.py``), and ``MinibatchStream.batch_at`` stays
    a pure function of (seed, step): restart-exact across hosts."""

    sp, plan = sharded.sp, sharded.plan
    gids = _gid_table(plan.p, plan.q)
    fn = _make_shard_sampler(plan, batch, sp.capacity, sp.mb, sp.nb)
    return fn(sp, gids, key)


# ---------------------------------------------------------------------- #
# shard-local f-gradients (block-local math => sharded == global exactly)
# ---------------------------------------------------------------------- #


@functools.lru_cache(maxsize=None)
def _make_shard_grads(plan: MeshPlan, use_kernel: bool, method: str,
                      chunk):
    bpr, bpc = plan.blocks_per_row_shard, plan.blocks_per_col_shard
    espec = plan.entries_spec()
    g = plan.grid_spec

    def body(spl: SparseProblem, U, W):
        from repro.sparse.objective import f_grads_sparse

        _, gu, gw = jax.vmap(jax.vmap(
            lambda entries, u, w: f_grads_sparse(
                entries, u, w, use_kernel=use_kernel, method=method,
                chunk=chunk,
            )
        ))(spl.entries, U, W)
        return gu, gw

    return jax.jit(jax.shard_map(
        body, mesh=plan.mesh, in_specs=(espec, g, g), out_specs=(g, g),
        check_vma=False,
    ))


def f_grads_sharded(sharded: ShardedEntries, U, W, *,
                    use_kernel: bool = False, method: str = "segment",
                    chunk: int | None = None):
    """(gU_f, gW_f) of the data-fit term, computed where the data lives.

    The f-gradients are block-local, so the sharded result equals the
    global ``vmap`` bit-for-bit; the consensus/regularization terms (which
    couple neighbouring blocks) stay with the gossip halo protocol
    (``core/gossip``)."""

    fn = _make_shard_grads(sharded.plan, use_kernel, method, chunk)
    return fn(sharded.sp, U, W)
