"""``MeshPlan`` — the single source of truth for data-plane placement.

Before this layer existed, three different modules each hand-rolled a
piece of the same decision — *which device owns block (i, j), its
entries, and its slice of the item axis*:

* ``SparseProblem.pspec`` spelled out the shard specs of the entry store,
* ``core/gossip.py`` rebuilt factor/halo specs from raw axis names,
* ``launch/mesh.py`` constructed meshes, and ``serve/recommend.py`` had
  no notion of placement at all (the whole catalog lived on one device).

``MeshPlan`` collapses all of it into one immutable object:

    plan = MeshPlan.build(p=4, q=4, mesh=build_mesh((2, 2), ("data", "model")))
    plan.owner(1, 3)          # -> the Device owning block (1, 3)
    plan.entries_spec()       # -> SparseProblem pytree of PartitionSpecs
    plan.factor_spec          # -> P(row_axes, col_axes) for U/W stacks
    plan.item_spec            # -> item-axis spec for the serving index
    plan.place_entries(sp)    # -> store device_put onto its owners

The block grid is tiled contiguously: with ``p`` block rows over a mesh
row dimension of size ``R`` (the product of ``row_axes`` sizes), device
row ``d`` owns block rows ``[d·p/R, (d+1)·p/R)`` — exactly the slices
``shard_map`` hands each device when the leading (p, q) axes carry
``P(row_axes, col_axes)``.  A ``MeshPlan.build(p, q)`` with no mesh is
the 1×1 single-device plan: every spec degenerates to the one device and
every consumer's compiled program is bit-identical to the unplanned path
(parity-pinned by ``tests/test_mesh_plan.py``).

This module deliberately has no dependency on ``sparse``/``core``/``serve``
(pytree structures are imported locally), so every layer can import the
plan without cycles.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def build_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """Construct a device mesh with Auto axis types (the one
    mesh-construction call in the repo; ``launch/mesh.py`` delegates
    here)."""

    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def _as_axes(axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Mesh + block→device ownership + derived placement specs.

    Fields
    ------
    mesh      : the jax device mesh (all axes)
    p, q      : block-grid shape being placed
    row_axes  : mesh axes carrying block *rows* (composite allowed:
                multi-pod runs pass ``("pod", "data")``)
    col_axes  : mesh axes carrying block *cols*
    """

    mesh: Any
    p: int
    q: int
    row_axes: Tuple[str, ...] = ("data",)
    col_axes: Tuple[str, ...] = ("model",)

    def __post_init__(self) -> None:
        ax = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        for a in self.row_axes + self.col_axes:
            if a not in ax:
                raise ValueError(
                    f"axis {a!r} not in mesh axes {tuple(ax)}; MeshPlan "
                    f"row/col axes must name mesh axes"
                )
        if tuple(self.row_axes) + tuple(self.col_axes) != tuple(
            self.mesh.axis_names
        ):
            raise ValueError(
                f"row_axes + col_axes must cover the mesh axes in order: "
                f"got {self.row_axes} + {self.col_axes} over mesh "
                f"{tuple(self.mesh.axis_names)}"
            )
        if self.p % self.row_size or self.q % self.col_size:
            raise ValueError(
                f"block grid {self.p}x{self.q} does not tile the "
                f"{self.row_size}x{self.col_size} device grid: p must be a "
                f"multiple of {self.row_size} and q of {self.col_size} "
                f"(shard_map hands each device whole blocks)"
            )

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #

    @classmethod
    def build(
        cls,
        p: int,
        q: int,
        mesh=None,
        row_axes="data",
        col_axes="model",
    ) -> "MeshPlan":
        """The one constructor every layer uses.  ``mesh=None`` builds the
        1×1 single-device plan (axes named like production so the same
        specs compile); a ``MeshPlan`` passes through unchanged when its
        grid matches."""

        if isinstance(mesh, MeshPlan):
            if (mesh.p, mesh.q) != (p, q):
                raise ValueError(
                    f"plan is for a {mesh.p}x{mesh.q} grid, problem has "
                    f"{p}x{q}; build a matching MeshPlan"
                )
            return mesh
        row_axes = _as_axes(row_axes)
        col_axes = _as_axes(col_axes)
        if mesh is None:
            mesh = build_mesh(
                (1,) * (len(row_axes) + len(col_axes)), row_axes + col_axes
            )
        return cls(mesh=mesh, p=p, q=q, row_axes=row_axes, col_axes=col_axes)

    @classmethod
    def for_devices(cls, devices=None) -> "MeshPlan":
        """1×D plan over the given devices (default: all available), in
        the given order — for consumers that only care about the
        flattened device list (the serving index shards its item axis
        over ``all_axes``), not the 2-D block tiling."""

        from jax.sharding import Mesh

        devices = jax.devices() if devices is None else list(devices)
        n = len(devices)
        mesh = Mesh(np.asarray(devices).reshape(1, n), ("data", "model"))
        return cls.build(1, n, mesh=mesh)

    @classmethod
    def for_spec(cls, spec, mesh=None, row_axes="data",
                 col_axes="model") -> "MeshPlan":
        """Plan for a ``GridSpec``-shaped object (anything with p/q)."""

        return cls.build(spec.p, spec.q, mesh=mesh, row_axes=row_axes,
                         col_axes=col_axes)

    @classmethod
    def from_mesh_config(cls, cfg, p: int | None = None,
                         q: int | None = None) -> "MeshPlan":
        """Plan from a ``MeshConfig`` (absorbs ``launch/mesh.py``'s
        construction): multi-pod puts the pod axis on the rows.  The block
        grid defaults to one block per device."""

        if cfg.multi_pod:
            shape = (cfg.pod, cfg.data, cfg.model)
            axes = ("pod", "data", "model")
            row_axes: Tuple[str, ...] = ("pod", "data")
        else:
            shape = (cfg.data, cfg.model)
            axes = ("data", "model")
            row_axes = ("data",)
        mesh = build_mesh(shape, axes)
        rs = int(np.prod([dict(zip(axes, shape))[a] for a in row_axes]))
        return cls.build(p if p is not None else rs,
                         q if q is not None else cfg.model,
                         mesh=mesh, row_axes=row_axes, col_axes=("model",))

    # ------------------------------------------------------------------ #
    # geometry
    # ------------------------------------------------------------------ #

    def _axes_size(self, axes: Tuple[str, ...]) -> int:
        ax = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return int(np.prod([ax[a] for a in axes])) if axes else 1

    @property
    def row_size(self) -> int:
        """Device count along the block-row dimension."""

        return self._axes_size(self.row_axes)

    @property
    def col_size(self) -> int:
        """Device count along the block-col dimension."""

        return self._axes_size(self.col_axes)

    @property
    def num_devices(self) -> int:
        return self.mesh.size

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return self.row_axes + self.col_axes

    @property
    def is_single_device(self) -> bool:
        return self.mesh.size == 1

    @property
    def blocks_per_row_shard(self) -> int:
        """Block rows owned by each device row (contiguous tiling)."""

        return self.p // self.row_size

    @property
    def blocks_per_col_shard(self) -> int:
        return self.q // self.col_size

    # -- halo-edge geometry (the gossip wire graph, receiver-side view) -- #

    @property
    def num_u_edges(self) -> int:
        """Directed U-halo messages per refresh round: each of the
        ``row_size`` device rows has ``col_size - 1`` interior pairs, each
        exchanging in both directions.  Matches ``halo_bytes_per_round``'s
        byte geometry and is the denominator of ``FaultPlan`` drop
        accounting."""

        return 2 * self.row_size * (self.col_size - 1)

    @property
    def num_w_edges(self) -> int:
        """Directed W-halo messages per refresh round (dual of
        :attr:`num_u_edges`)."""

        return 2 * self.col_size * (self.row_size - 1)

    @property
    def num_halo_edges(self) -> int:
        """All directed halo messages one refresh round carries."""

        return self.num_u_edges + self.num_w_edges

    # ------------------------------------------------------------------ #
    # ownership
    # ------------------------------------------------------------------ #

    @property
    def device_grid(self) -> np.ndarray:
        """(row_size, col_size) array of Devices — who owns what."""

        return self.mesh.devices.reshape(self.row_size, self.col_size)

    def owner_coords(self, i: int, j: int) -> tuple[int, int]:
        """Device-grid coordinates owning block (i, j)."""

        if not (0 <= i < self.p and 0 <= j < self.q):
            raise IndexError(
                f"block ({i},{j}) outside the {self.p}x{self.q} grid"
            )
        return i // self.blocks_per_row_shard, j // self.blocks_per_col_shard

    def owner(self, i: int, j: int):
        """The Device owning block (i, j) — its entries, its U_ij/W_ij."""

        di, dj = self.owner_coords(i, j)
        return self.device_grid[di, dj]

    def block_owners(self) -> np.ndarray:
        """(p, q) int array: flat device-grid index owning each block."""

        di = np.arange(self.p) // self.blocks_per_row_shard
        dj = np.arange(self.q) // self.blocks_per_col_shard
        return (di[:, None] * self.col_size + dj[None, :]).astype(np.int32)

    def local_blocks(self, di: int, dj: int) -> list[tuple[int, int]]:
        """Blocks owned by device-grid cell (di, dj), row-major."""

        bpr, bpc = self.blocks_per_row_shard, self.blocks_per_col_shard
        return [(i, j)
                for i in range(di * bpr, (di + 1) * bpr)
                for j in range(dj * bpc, (dj + 1) * bpc)]

    def describe(self) -> str:
        """ASCII ownership map (docs / log lines)."""

        own = self.block_owners()
        head = (f"MeshPlan {self.p}x{self.q} blocks over "
                f"{self.row_size}x{self.col_size} devices "
                f"(row_axes={self.row_axes}, col_axes={self.col_axes})")
        width = max(2, len(str(own.max())))
        rows = ["  " + " ".join(f"d{own[i, j]:<{width}}"
                                for j in range(self.q))
                for i in range(self.p)]
        return "\n".join([head] + rows)

    # ------------------------------------------------------------------ #
    # derived specs — every placement decision downstream reads these
    # ------------------------------------------------------------------ #

    @property
    def row_spec_axes(self):
        """The P() entry for a dim sharded over block rows."""

        return self.row_axes if len(self.row_axes) > 1 else self.row_axes[0]

    @property
    def col_spec_axes(self):
        return self.col_axes if len(self.col_axes) > 1 else self.col_axes[0]

    @property
    def grid_spec(self) -> P:
        """P(row, col): the leading (p, q) dims of every grid-stacked
        tensor — entry stores, factor stacks, nnz counts."""

        return P(self.row_spec_axes, self.col_spec_axes)

    # factor stacks U (p, q, mb, r) / W (p, q, nb, r) shard exactly like
    # the grid; kept as a named alias so call sites say what they mean.
    factor_spec = grid_spec

    @property
    def replicated(self) -> P:
        return P()

    @property
    def row_edge_spec(self) -> P:
        """Specs of per-block-row edge stacks (gossip U halos: (p, mb, r))."""

        return P(self.row_spec_axes)

    @property
    def col_edge_spec(self) -> P:
        """Specs of per-block-col edge stacks (gossip W halos: (q, nb, r))."""

        return P(self.col_spec_axes)

    @property
    def item_spec(self) -> P:
        """Serving-index item axis: sharded over *all* mesh devices (the
        catalog is 1-D at serve time; every device holds n/num_devices
        items and answers with a per-shard top-k — see
        ``serve.recommend``)."""

        axes = self.all_axes
        return P(axes if len(axes) > 1 else axes[0])

    @property
    def num_item_shards(self) -> int:
        """Shard count of the serving item axis (= device count)."""

        return self.num_devices

    def spec_like(self, tree, spec: P | None = None):
        """Pytree of PartitionSpecs matching ``tree``: every leaf gets
        ``spec`` (default :attr:`grid_spec`) — the generalization that
        ``SparseProblem.pspec`` delegates to."""

        spec = self.grid_spec if spec is None else spec
        return jax.tree.map(lambda _: spec, tree)

    def entries_spec(self):
        """``SparseProblem`` pytree of specs: every leaf of the store —
        entry tensors, sorted-view offsets, nnz counts — shards on its
        leading (p, q) axes.  The one place that knows the store's
        placement (``SparseProblem.pspec`` is a thin delegate)."""

        return entries_spec_like(self.grid_spec)

    def state_spec(self):
        """``State`` spec: factor stacks on the grid, the scalar clock
        replicated."""

        from repro.core.state import State

        return State(self.factor_spec, self.factor_spec, P())

    # ------------------------------------------------------------------ #
    # placement
    # ------------------------------------------------------------------ #

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def place(self, tree, specs=None):
        """device_put every leaf with its spec (default: grid spec)."""

        if specs is None:
            specs = self.spec_like(tree)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, self.sharding(s)), tree, specs
        )

    def place_entries(self, sp):
        """Put a ``SparseProblem`` onto its owners: each device receives
        exactly the blocks :meth:`local_blocks` assigns it.  The dense
        tile stays behind: placed stores keep the segment path."""

        from repro.sparse.store import drop_tile

        return self.place(drop_tile(sp), self.entries_spec())

    def place_state(self, state):
        return self.place(state, self.state_spec())


def entries_spec_like(spec: P):
    """``SparseProblem``-shaped pytree with ``spec`` at every leaf — the
    one definition of the store's spec structure (``MeshPlan.entries_spec``
    and the back-compat ``SparseProblem.pspec`` both call this)."""

    from repro.sparse.entries import SORTED_FIELDS, BlockEntries
    from repro.sparse.store import SparseProblem

    # a placed store carries no dense tile (its fields stay None)
    return SparseProblem(
        BlockEntries(*([spec] * len(SORTED_FIELDS))), spec
    )


# ---------------------------------------------------------------------- #
# axis utilities shared with the LM sharding rules (train/sharding.py
# delegates here — MeshPlan is the home of "shard only when divisible")
# ---------------------------------------------------------------------- #


def divides(dim: int, by: int) -> bool:
    """True when a dim can legally shard ``by`` ways (the degrade-to-
    replication rule every placement decision uses)."""

    return by > 0 and dim % by == 0


def axis_if_divisible(dim: int, axis, size: int):
    """``axis`` when ``dim`` splits evenly over it, else ``None``
    (replicate) — the single definition of spec degradation."""

    return axis if divides(dim, size) else None


def dp_axes(mesh_cfg) -> tuple[str, ...]:
    """Data-parallel axes of an LM ``MeshConfig`` (pod folds into data)."""

    return ("pod", "data") if mesh_cfg.multi_pod else ("data",)
