"""``CompletionProblem`` — the one noun that owns matrix-completion data.

Before this facade existed, every call site juggled four things by hand:
the blockified data (``Problem`` or ``SparseProblem``), the ``GridSpec``,
a ``layout=`` switch threaded through every fit entry point, and the
engine knobs (Pallas on/off, gradient method, segment chunk, bucket size)
scattered across keyword arguments.  ``CompletionProblem`` bundles all of
it: construct once, hand to ``Trainer.fit`` with any schedule.

    problem = CompletionProblem.from_dense(x, mask, p=4, q=4, rank=8,
                                           layout="sparse")
    problem = CompletionProblem.from_entries(rows, cols, vals, shape=(m, n),
                                             p=4, q=4, rank=8)
    problem = CompletionProblem.from_dataset(ds, p=4, q=4, rank=8)

``EngineOptions`` is the kernel/engine configuration (``with_engine``
derives a tweaked copy) — including the segment-reduce ``chunk`` size that
used to be hardcoded in ``kernels/sddmm/segment.py`` and is swept by
``benchmarks/sparse_vs_dense.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import numpy as np

import functools

from repro.core import grid as G
from repro.core import objective as core_obj
from repro.core import waves as core_waves
from repro.core.state import Problem, State, make_problem
from repro.data.synthetic import MCDataset
from repro.mesh.plan import MeshPlan
from repro import sparse as sparse_mod
from repro.sparse import objective as sparse_obj
from repro.sparse.store import SparseProblem


@functools.partial(jax.jit, static_argnames=("lam",))
def _total_cost(data, U, W, lam: float):
    return core_obj.total_cost(data, U, W, lam)


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """How gradients are computed — orthogonal to what is computed.

    use_kernel : run the Pallas kernels (auto-interpret off-TPU)
    method     : "segment" (sorted CSR/CSC streaming, default) | "scatter"
    chunk      : segment-reduce chunk size; None auto-picks per backend
                 from the committed ``--chunks`` sweep results
                 (``kernels/sddmm/autotune.resolve_chunk``, fed by
                 ``benchmarks/sparse_vs_dense.py --chunks``), with a sane
                 hardcoded fallback.  An explicit chunk always wins.
    bucket     : padded-COO capacity quantum for sparse ingest
    headroom   : per-block append slack pre-allocated at sparse ingest, so
                 ``CompletionProblem.append`` splices streaming entries in
                 place instead of overflowing (DESIGN.md §11)
    """

    use_kernel: bool = False
    method: str = "segment"
    chunk: Optional[int] = None
    bucket: int = sparse_mod.DEFAULT_BUCKET
    headroom: int = 0

    def __post_init__(self) -> None:
        if self.method not in ("segment", "scatter"):
            raise ValueError(
                f"unknown method {self.method!r}; 'segment' or 'scatter'"
            )
        if self.chunk is not None and self.chunk <= 0:
            raise ValueError(f"chunk must be positive, got {self.chunk}")
        if self.bucket <= 0:
            raise ValueError(f"bucket must be positive, got {self.bucket}")
        if self.headroom < 0:
            raise ValueError(
                f"headroom must be non-negative, got {self.headroom}"
            )


def _place(data, p: int, q: int, mesh):
    """Resolve a ``mesh=`` knob (Mesh | MeshPlan | None) into
    (plan, device-placed data) — the single ingest-side placement hook."""

    if mesh is None:
        return None, data
    plan = MeshPlan.build(p, q, mesh=mesh)
    if isinstance(data, SparseProblem):
        return plan, plan.place_entries(data)
    g = plan.grid_spec
    return plan, plan.place(data, Problem(g, g))


@dataclasses.dataclass(frozen=True)
class CompletionProblem:
    """Immutable bundle of blockified data + grid spec + engine options.

    ``num_users``/``num_items`` are the true (pre-grid-padding) shape;
    ``seen_coo`` holds the observed (user, item) pairs for serve-time
    exclusion; ``mu`` is the observed-mean offset subtracted when
    ``mean_center=True`` (add it back when reporting predictions);
    ``dataset`` (optional) carries held-out test entries for eval-RMSE.
    """

    data: Union[Problem, SparseProblem]
    spec: G.GridSpec
    engine: EngineOptions = EngineOptions()
    num_users: int = 0
    num_items: int = 0
    seen_coo: Optional[Tuple[np.ndarray, np.ndarray]] = None
    mu: float = 0.0
    dataset: Optional[MCDataset] = None
    plan: Optional[MeshPlan] = None

    # ------------------------------------------------------------------ #
    # constructors
    # ------------------------------------------------------------------ #

    @classmethod
    def from_dense(
        cls,
        x: np.ndarray,
        mask: np.ndarray,
        p: int,
        q: int,
        rank: int,
        *,
        layout: str = "dense",
        engine: EngineOptions | None = None,
        mean_center: bool = False,
        dataset: MCDataset | None = None,
        headroom: int | None = None,
        mesh=None,
    ) -> "CompletionProblem":
        """From a dense (m, n) matrix + 0/1 observation mask.  Pads to the
        grid, blockifies, and converts to the sparse store when
        ``layout="sparse"``.  ``headroom`` pre-allocates per-block append
        slack in the sparse store for :meth:`append` (streaming
        ingestion); it overrides ``engine.headroom``.  ``mesh`` (a jax
        Mesh or a ``repro.mesh.MeshPlan``) places the data onto its
        owning devices at construction — the ``Gossip`` schedule,
        streaming appends, and sharded serving then consume the
        device-resident shards directly."""

        if layout not in ("dense", "sparse"):
            raise ValueError(
                f"unknown layout {layout!r}; expected 'dense' or 'sparse'"
            )
        engine = engine or EngineOptions()
        if headroom is not None:
            engine = dataclasses.replace(engine, headroom=headroom)
        x = np.asarray(x, np.float32)
        mask = np.asarray(mask, np.float32)
        if x.shape != mask.shape or x.ndim != 2:
            raise ValueError(
                f"x and mask must be equal-shape 2-D arrays, got "
                f"{x.shape} vs {mask.shape}"
            )
        m0, n0 = x.shape
        xp, mp, m, n = G.pad_to_grid(x, mask, p, q)
        spec = G.GridSpec(m, n, p, q, rank)
        mu = 0.0
        if mean_center:
            mu = float((xp * mp).sum() / max(mp.sum(), 1.0))
            xp = xp - mu                       # make_problem re-masks (x*mask)
        dense = make_problem(xp, mp, spec)
        data: Union[Problem, SparseProblem] = dense
        if layout == "sparse":
            data = sparse_mod.from_blocks(dense.xb, dense.maskb,
                                          engine.bucket, engine.headroom)
        plan, data = _place(data, p, q, mesh)
        rows, cols = np.nonzero(mask)
        return cls(data=data, spec=spec, engine=engine, num_users=m0,
                   num_items=n0, seen_coo=(rows.astype(np.int64),
                                           cols.astype(np.int64)),
                   mu=mu, dataset=dataset, plan=plan)

    @classmethod
    def from_entries(
        cls,
        rows: np.ndarray,
        cols: np.ndarray,
        vals: np.ndarray,
        shape: Tuple[int, int],
        p: int,
        q: int,
        rank: int,
        *,
        layout: str = "sparse",
        engine: EngineOptions | None = None,
        mean_center: bool = False,
        dataset: MCDataset | None = None,
        headroom: int | None = None,
        mesh=None,
    ) -> "CompletionProblem":
        """From a global COO triplet list — the streaming-ingestion path.
        ``layout="sparse"`` (default) never materializes the dense matrix;
        ``layout="dense"`` scatters into dense tensors first.  ``headroom``
        pre-allocates per-block append slack so :meth:`append` can splice
        future ratings in place (overrides ``engine.headroom``).  With a
        ``mesh`` (Mesh or ``MeshPlan``) the sparse ingest is
        **owner-routed**: each triplet goes straight to the device owning
        its block and every device packs its own buckets — no globally
        sorted COO is ever materialized (``sparse.ShardedEntries``)."""

        engine = engine or EngineOptions()
        if headroom is not None:
            engine = dataclasses.replace(engine, headroom=headroom)
        m0, n0 = shape
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals, np.float32)
        mu = float(vals.mean()) if (mean_center and len(vals)) else 0.0
        if layout == "dense":
            x = np.zeros((m0, n0), np.float32)
            mask = np.zeros((m0, n0), np.float32)
            x[rows, cols] = vals
            mask[rows, cols] = 1.0
            return cls.from_dense(x, mask, p, q, rank, layout="dense",
                                  engine=engine, mean_center=mean_center,
                                  dataset=dataset, mesh=mesh)
        if layout != "sparse":
            raise ValueError(
                f"unknown layout {layout!r}; expected 'dense' or 'sparse'"
            )
        cvals = vals - mu if mu else vals
        plan = MeshPlan.build(p, q, mesh=mesh) if mesh is not None else None
        if plan is not None:
            from repro.sparse.sharded import ShardedEntries

            sharded, (m, n) = ShardedEntries.from_coo(
                rows, cols, cvals, m0, n0, plan,
                engine.bucket, engine.headroom,
            )
            sp = sharded.sp
        else:
            sp, (m, n) = sparse_mod.from_entries(
                rows, cols, cvals, m0, n0, p, q,
                engine.bucket, engine.headroom,
            )
        spec = G.GridSpec(m, n, p, q, rank)
        order = np.argsort(rows, kind="stable")   # seen table wants user-sorted
        return cls(data=sp, spec=spec, engine=engine, num_users=m0,
                   num_items=n0, seen_coo=(rows[order], cols[order]),
                   mu=mu, dataset=dataset, plan=plan)

    @classmethod
    def from_dataset(
        cls,
        ds: MCDataset,
        p: int,
        q: int,
        rank: int,
        *,
        layout: str = "dense",
        engine: EngineOptions | None = None,
        mean_center: bool = False,
        headroom: int | None = None,
        mesh=None,
    ) -> "CompletionProblem":
        """From an ``MCDataset`` (synthetic low-rank, MovieLens proxy, or a
        loaded ratings file); keeps the held-out test split attached for
        eval-RMSE callbacks and ``FitResult.rmse()``.  ``headroom``
        pre-allocates append slack for streaming :meth:`append`;
        ``mesh`` places the blocks onto their owners (see
        :meth:`from_dense`)."""

        return cls.from_dense(ds.x, ds.train_mask, p, q, rank, layout=layout,
                              engine=engine, mean_center=mean_center,
                              dataset=ds, headroom=headroom, mesh=mesh)

    # ------------------------------------------------------------------ #
    # derived views
    # ------------------------------------------------------------------ #

    @property
    def layout(self) -> str:
        return "sparse" if isinstance(self.data, SparseProblem) else "dense"

    @property
    def grad_path(self) -> str:
        """The arithmetic of the f-term on this problem's data: ``"dense"``
        (the dense layout), ``"scatter"`` or ``"segment"`` (the sparse
        engines), or ``"tile"`` (the store's dense masked tile, which the
        default engine takes wherever the store built one)."""

        if not isinstance(self.data, SparseProblem):
            return "dense"
        if self.engine.method == "scatter":
            return "scatter"
        if sparse_obj.takes_tile(self.data.entries, self.engine.method,
                                 self.engine.use_kernel):
            return "tile"
        return "segment"

    @property
    def density(self) -> float:
        if isinstance(self.data, SparseProblem):
            return sparse_mod.density(self.data, self.spec)
        return float(np.asarray(self.data.maskb).mean())

    def with_engine(self, **overrides) -> "CompletionProblem":
        """Copy with tweaked EngineOptions (data/spec shared, zero-copy).
        Note ``bucket`` only affects future ingest, not the built store."""

        return dataclasses.replace(
            self, engine=dataclasses.replace(self.engine, **overrides)
        )

    def with_mesh(self, mesh) -> "CompletionProblem":
        """Copy placed onto a mesh: builds the ``MeshPlan`` for this grid
        and device_puts the data onto its owners.  ``mesh=None`` drops the
        plan (data stays wherever it is)."""

        if mesh is None:
            return dataclasses.replace(self, plan=None)
        plan, data = _place(self.data, self.spec.p, self.spec.q, mesh)
        return dataclasses.replace(self, data=data, plan=plan)

    def with_layout(self, layout: str) -> "CompletionProblem":
        """Copy converted to the requested layout (no-op when it matches)."""

        if layout == self.layout:
            return self
        if layout == "sparse":
            data = sparse_mod.from_blocks(
                self.data.xb, self.data.maskb, self.engine.bucket,
                self.engine.headroom,
            )
        elif layout == "dense":
            xb, maskb = sparse_mod.to_dense(self.data, self.spec.mb,
                                            self.spec.nb)
            data = Problem(jax.numpy.asarray(xb), jax.numpy.asarray(maskb))
        else:
            raise ValueError(
                f"unknown layout {layout!r}; expected 'dense' or 'sparse'"
            )
        if self.plan is not None:      # keep the converted data on its owners
            _, data = _place(data, self.spec.p, self.spec.q, self.plan)
        return dataclasses.replace(self, data=data)

    # ------------------------------------------------------------------ #
    # streaming ingestion
    # ------------------------------------------------------------------ #

    def append(self, rows, cols, vals) -> "CompletionProblem":
        """New ratings spliced into the problem's store — the streaming
        ingestion path (DESIGN.md §11).

        ``rows``/``cols`` are true (pre-padding) user/item indices; values
        are mean-centered by the problem's μ automatically.  On the sparse
        layout the entries are merged into the sorted padded-COO store in
        place capacity-wise (pre-allocate slack with ``headroom=`` at
        ingest; a full bucket raises with the headroom that would have
        absorbed the append).  On the dense layout they scatter into the
        block tensors.  A (user, item) pair already rated updates its value
        (an edited rating); duplicate pairs within the batch resolve to the
        last occurrence; an empty append returns ``self``.

        Returns a new problem sharing the spec/engine/dataset; the
        seen-item table grows so serving built from a refit excludes the
        new ratings.  Appends never grow the matrix — new users or items
        need a fresh :meth:`from_entries` ingest (and a cold fit, since
        factor shapes change)."""

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
        if (rows.min() < 0 or rows.max() >= self.num_users
                or cols.min() < 0 or cols.max() >= self.num_items):
            raise ValueError(
                f"append indices out of range for the "
                f"{self.num_users}x{self.num_items} matrix: rows in "
                f"[{rows.min()}, {rows.max()}], cols in "
                f"[{cols.min()}, {cols.max()}] — appends cover existing "
                f"users/items; a grown matrix needs a fresh from_entries "
                f"ingest (factor shapes change)"
            )
        rows, cols, vals = sparse_mod.store.dedupe_last_write(
            rows, cols, vals, self.num_items
        )
        cvals = vals - self.mu if self.mu else vals
        if isinstance(self.data, SparseProblem):
            if self.plan is not None:
                # owner-routed: each entry goes to the device holding its
                # block; untouched shards are reused, nothing is gathered
                from repro.sparse.sharded import ShardedEntries

                data: Union[Problem, SparseProblem] = ShardedEntries(
                    self.data, self.plan
                ).append(rows, cols, cvals).sp
            else:
                data = sparse_mod.append_entries(
                    self.data, rows, cols, cvals
                )
        else:
            mb, nb = self.spec.mb, self.spec.nb
            bi, rr = rows // mb, rows % mb
            bj, cc = cols // nb, cols % nb
            data = Problem(
                self.data.xb.at[bi, bj, rr, cc].set(jax.numpy.asarray(cvals)),
                self.data.maskb.at[bi, bj, rr, cc].set(1.0),
            )
            if self.plan is not None:
                _, data = _place(data, self.spec.p, self.spec.q, self.plan)
        if self.seen_coo is not None:
            ar = np.concatenate([np.asarray(self.seen_coo[0], np.int64), rows])
            ac = np.concatenate([np.asarray(self.seen_coo[1], np.int64), cols])
        else:
            ar, ac = rows, cols
        ni = max(self.num_items, 1)
        uniq = np.unique(ar * ni + ac)               # user-sorted + deduped
        return dataclasses.replace(self, data=data,
                                   seen_coo=(uniq // ni, uniq % ni))

    # ------------------------------------------------------------------ #
    # engine-option-respecting evaluation (what benchmarks time)
    # ------------------------------------------------------------------ #

    def total_cost(self, state: State, lam: float) -> float:
        """Paper Table-2 cost at ``state`` (layout-dispatching, jitted)."""

        return float(self.total_cost_device(state, lam))

    def total_cost_device(self, state: State, lam: float) -> jax.Array:
        """Same cost as a device scalar (no host sync) — what benchmarks
        time so the transfer does not serialize dispatch."""

        return _total_cost(self.data, state.U, state.W, lam)

    def full_gradients(self, state: State, *, rho: float, lam: float):
        """∇L of the collapsed objective with this problem's engine options."""

        return core_waves.full_gradients(
            self.data, state.U, state.W, rho=rho, lam=lam,
            use_kernel=self.engine.use_kernel, method=self.engine.method,
            chunk=self.engine.chunk,
        )
