"""Top-k recommendation serving over completed gossip factors.

After training, ``assemble`` collapses the (p, q) block factors into global
U (m×r) and W (n×r).  This module turns those into a serving index and
answers "top-k unseen items for these users" in fixed-shape jitted batches:

    scores   = U[user_batch] @ Wᵀ                   (B×n, one MXU matmul)
    masked   = scores with each user's seen items at −inf (scatter, 'drop')
    items    = lax.top_k(masked, k)

The seen-item table is a padded (m, S) int32 ragged list; padding slots
hold ``n`` (one past the last item id) and are dropped by the scatter's
out-of-bounds mode, so no per-user bucketing logic exists at serve time.
``RecommendService`` adds fixed-batch chunking (pad the tail batch, keep one
jit cache entry) — the shape discipline that a production front-end needs —
and ``refresh(fit_result)`` hot-swaps the index after a streaming
``Trainer.refit`` without touching the serving loop (DESIGN.md §11).

**Catalogs bigger than one device** (`shard_index` + a ``MeshPlan``): the
item axis of W is sharded over every mesh device and top-k runs in two
stages — each shard k-selects over its own n/S items (seen-exclusion
applied shard-locally on the global ids that fall in its range), then the
S·k candidates are all-gathered and merged by one final k-selection.  The
merge is exact (the global top-k is always a subset of the per-shard
top-k's), pinned against the numpy oracle in ``tests/test_mesh_plan.py``.

**int8 serving** (DESIGN.md §16): every query in this module also takes a
``QuantizedRecommendIndex`` (serve/quant.py — int8 codes + per-row f32
scales); scoring then routes through the fused dequantize-score kernel
switch (``kernels/quant``, ``method="fused"|"dequant"``, ``None`` =
per-backend autotune).  Per-row scales make per-shard quantization exact,
so ``shard_index`` shards the int8 catalog the same way and the two-stage
query serves int8 unchanged.  Accuracy is gated in
``tests/test_quant_serving.py`` (overlap@k ≥ 0.99 vs f32).

Throughput bench: ``benchmarks/serve_recommend.py`` (``--sharded``);
``benchmarks/serving_traffic.py --quant`` for the int8 engine arm.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.assemble import assemble
from repro.core.grid import GridSpec
from repro.kernels.quant import dequant_score
from repro.serve.quant import QuantizedRecommendIndex, quantize_index

_SEEN_PAD_QUANTUM = 16
# f32 scores are computed at full f32 precision: the TPU's default matmul
# rounds f32 operands to bf16, which reorders a top-100 against the f32
# reference (on the CPU the two settings give identical results)
_F32_SCORES = jax.lax.Precision.HIGHEST


class RecommendIndex(NamedTuple):
    """Immutable serving state (device-resident)."""

    u: jax.Array      # (m, r) float32 — user factors
    w: jax.Array      # (n, r) float32 — item factors
    seen: jax.Array   # (m, S) int32 — items to exclude; pad value == n

    @property
    def num_users(self) -> int:
        return self.u.shape[0]

    @property
    def num_items(self) -> int:
        return self.w.shape[0]

    @property
    def rank(self) -> int:
        return self.u.shape[1]

    def refresh(self, fit_result) -> "RecommendIndex":
        """Rebuild from a (re)fit without a serving restart — the read
        side of the streaming loop (DESIGN.md §11): new factors plus the
        updated seen-item table, so just-appended ratings stop being
        recommended back.  The index is immutable; swap the returned value
        in (``RecommendService.refresh`` does exactly that).  The catalog
        and user counts must match — appends never grow the matrix, so a
        reshaped problem means this index is serving the wrong universe."""

        new = fit_result.to_recommend_index()
        if new.u.shape != self.u.shape or new.w.shape != self.w.shape:
            raise ValueError(
                f"refresh changes the factor shapes: expected "
                f"u{tuple(self.u.shape)} x w{tuple(self.w.shape)}, got "
                f"u{tuple(new.u.shape)} x w{tuple(new.w.shape)}; a "
                f"re-shaped problem needs a new build_index, not a refresh"
            )
        return new


def build_seen_table_coo(rows: np.ndarray, cols: np.ndarray,
                         num_users: int, num_items: int) -> np.ndarray:
    """Padded per-user seen-item lists straight from COO (user, item) pairs
    — the streaming-ingestion path; never materializes an (m, n) mask.
    Pairs must be sorted by user (np.nonzero order qualifies).  Pad value is
    ``num_items`` (out of range → dropped by the serve-time scatter)."""

    rows = np.asarray(rows)
    cols = np.asarray(cols)
    if len(rows) and np.any(np.diff(rows) < 0):
        raise ValueError(
            "build_seen_table_coo needs user-sorted pairs; sort with "
            "order = np.argsort(rows, kind='stable') first"
        )
    keep = cols < num_items                       # drop grid-padding columns
    rows, cols = rows[keep], cols[keep]
    counts = np.bincount(rows, minlength=num_users)
    S = int(counts.max()) if len(rows) else 0
    S = max(_SEEN_PAD_QUANTUM,
            (S + _SEEN_PAD_QUANTUM - 1) // _SEEN_PAD_QUANTUM * _SEEN_PAD_QUANTUM)
    seen = np.full((num_users, S), num_items, np.int32)
    # user-sorted pairs: entries of user u occupy the contiguous range
    # [starts[u], starts[u]+counts[u])
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    seen[rows, np.arange(len(rows)) - starts[rows]] = cols
    return seen


def build_seen_table(train_mask: np.ndarray, num_items: int) -> np.ndarray:
    """Padded per-user seen-item lists from a 0/1 mask.  Pad value is
    ``num_items`` (out of range → dropped by the serve-time scatter)."""

    mask = np.asarray(train_mask)
    rows, cols = np.nonzero(mask[:, :num_items])  # row-major == user-sorted
    return build_seen_table_coo(rows, cols, mask.shape[0], num_items)


def build_index(
    U: jax.Array,
    W: jax.Array,
    spec: GridSpec,
    train_mask: np.ndarray | None = None,
    num_users: int | None = None,
    num_items: int | None = None,
    seen_coo: tuple[np.ndarray, np.ndarray] | None = None,
) -> RecommendIndex:
    """Assemble block factors and attach the seen-item exclusion table.

    ``num_users``/``num_items`` trim grid padding (pad_to_grid rows/cols)
    back to the true matrix shape.  The exclusion table comes from a 0/1
    ``train_mask`` or — mask-free, for COO-ingested problems — from
    user-sorted ``seen_coo = (user_ids, item_ids)`` pairs.
    """

    u, w = assemble(U, W, spec)
    m = num_users if num_users is not None else spec.m
    n = num_items if num_items is not None else spec.n
    u = jnp.asarray(u[:m], jnp.float32)
    w = jnp.asarray(w[:n], jnp.float32)
    if train_mask is not None:
        seen = build_seen_table(np.asarray(train_mask)[:m], n)
    elif seen_coo is not None:
        seen = build_seen_table_coo(seen_coo[0], seen_coo[1], m, n)
    else:
        seen = np.full((m, _SEEN_PAD_QUANTUM), n, np.int32)
    return RecommendIndex(u, w, jnp.asarray(seen))


def _batch_scores(index, user_ids, method):
    """(B, n) scores for either index layout — the one scoring switch."""

    if isinstance(index, QuantizedRecommendIndex):
        return dequant_score(
            index.u_q[user_ids], index.u_scale[user_ids],
            index.w_q, index.w_scale, method=method,
        )
    return jnp.matmul(index.u[user_ids], index.w.T, precision=_F32_SCORES)


@partial(jax.jit, static_argnames=("k", "exclude_seen", "method"))
def recommend_topk(
    index, user_ids: jax.Array, *,
    k: int, exclude_seen: bool = True, method: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(items, scores) of shape (B, k) for a batch of user ids.

    ``index`` is a ``RecommendIndex`` or its int8 twin
    (``QuantizedRecommendIndex``); ``method`` picks the quantized
    scoring path (``"fused"``/``"dequant"``, ``None`` = per-backend
    autotune — ``kernels/quant``) and is ignored for f32 indices."""

    n_items = index.num_items
    if k > n_items:
        raise ValueError(
            f"k={k} exceeds catalog size n={n_items}"
        )
    scores = _batch_scores(index, user_ids, method)         # (B, n)
    if exclude_seen:
        b = user_ids.shape[0]
        seen = index.seen[user_ids]                         # (B, S)
        scores = scores.at[jnp.arange(b)[:, None], seen].set(
            -jnp.inf, mode="drop"
        )
    scores, items = jax.lax.top_k(scores, k)
    return items, scores


@jax.jit
def score_pairs(index, user_ids, item_ids):
    """Pointwise predicted ratings for explicit (user, item) pairs."""

    if isinstance(index, QuantizedRecommendIndex):
        dots = jnp.sum(
            index.u_q[user_ids].astype(jnp.int32)
            * index.w_q[item_ids].astype(jnp.int32), axis=-1,
        ).astype(jnp.float32)
        return dots * index.u_scale[user_ids] * index.w_scale[item_ids]
    return jnp.sum(index.u[user_ids] * index.w[item_ids], axis=-1)


# ---------------------------------------------------------------------- #
# item-axis-sharded serving: per-shard k-select + exact merge
# ---------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class ShardedRecommendIndex:
    """A ``RecommendIndex`` whose item axis lives across the mesh.

    The item factors are padded to a multiple of the plan's device count
    and device_put with ``plan.item_spec`` — every device holds exactly
    ``shard_items`` item factors, so catalogs scale past one device's
    memory.  ``u``/``seen`` stay replicated (user batches are small;
    queries gather by user id).  ``num_items`` is the true catalog size;
    padding rows are masked inside the sharded query.

    ``index`` may be the int8 twin (``QuantizedRecommendIndex``): the
    codes shard like W, the per-item scale vector shards alongside them
    (per-row scales make the per-shard quantization exactly the global
    one), and the two-stage query scores through ``kernels/quant``."""

    index: object                # RecommendIndex | QuantizedRecommendIndex
    plan: object                 # repro.mesh.MeshPlan
    num_items: int

    @property
    def quantized(self) -> bool:
        return isinstance(self.index, QuantizedRecommendIndex)

    @property
    def num_item_shards(self) -> int:
        return self.plan.num_item_shards

    @property
    def shard_items(self) -> int:
        """Items held per device (padded width / shard count)."""

        return self.index.num_items // self.plan.num_item_shards

    def refresh(self, fit_result) -> "ShardedRecommendIndex":
        """Hot-swap after a (re)fit, keeping the shard layout (and the
        quantized layout: an int8 sharded index re-quantizes the fresh
        factors per shard on the swap).

        Guards the sharded contract on top of the factor-shape guard: the
        refreshed fit must produce the same item-shard geometry this index
        was built with — a fit carrying a ``MeshPlan`` with a different
        device count would re-partition the catalog mid-serve, which the
        compiled two-stage query cannot absorb."""

        fit_plan = getattr(getattr(fit_result, "problem", None), "plan", None)
        if fit_plan is not None and \
                fit_plan.num_item_shards != self.num_item_shards:
            raise ValueError(
                f"refresh changes the item-shard count: this index serves "
                f"{self.num_items} items over {self.num_item_shards} shards "
                f"({self.shard_items} items/shard), the refit's MeshPlan has "
                f"{fit_plan.num_item_shards} shards; rebuild the serving "
                f"side with shard_index(new_index, new_plan) / "
                f"RecommendService(index, plan=new_plan) instead of refresh"
            )
        new = fit_result.to_recommend_index()
        old = _unpad_index(self)
        expected = (_u_shape(old), _w_shape(old))
        got = (tuple(new.u.shape), tuple(new.w.shape))
        if expected != got:
            raise ValueError(
                f"refresh changes the factor shapes: expected "
                f"u{expected[0]} x w{expected[1]}"
                f"{' (int8 layout)' if self.quantized else ''}, got "
                f"u{got[0]} x w{got[1]}; a re-shaped problem needs a new "
                f"shard_index, not a refresh"
            )
        if self.quantized:
            new = quantize_index(new)
        return shard_index(new, self.plan)


def _u_shape(index) -> tuple:
    return tuple((index.u_q if isinstance(index, QuantizedRecommendIndex)
                  else index.u).shape)


def _w_shape(index) -> tuple:
    return tuple((index.w_q if isinstance(index, QuantizedRecommendIndex)
                  else index.w).shape)


def _unpad_index(sidx: ShardedRecommendIndex):
    idx = sidx.index
    if isinstance(idx, QuantizedRecommendIndex):
        return idx._replace(w_q=idx.w_q[: sidx.num_items],
                            w_scale=idx.w_scale[: sidx.num_items])
    return RecommendIndex(idx.u, idx.w[: sidx.num_items], idx.seen)


def _pad_items(a, n_pad: int):
    """Zero-pad an item-axis array (codes, factors or scales) to the
    shard multiple; padded rows are masked at query time."""

    pad = n_pad - a.shape[0]
    if not pad:
        return a
    widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    return jnp.pad(a, widths)


def shard_index(index, plan) -> ShardedRecommendIndex:
    """Partition an index's item axis over every device of ``plan``.

    The item factors are zero-padded to a shard multiple (padding masked
    at query time) and placed with ``plan.item_spec``; u and the seen
    table replicate.  A quantized index shards exactly the same way —
    codes and the per-item scale row both live on the item axis.  A
    1-device plan degrades to the unsharded layout (and the two-stage
    query to a plain ``recommend_topk`` — parity-tested)."""

    S = plan.num_item_shards
    n = index.num_items
    n_pad = -(-n // S) * S
    item_sh = plan.sharding(plan.item_spec)
    rep = plan.sharding(P())
    if isinstance(index, QuantizedRecommendIndex):
        placed = QuantizedRecommendIndex(
            jax.device_put(index.u_q, rep),
            jax.device_put(index.u_scale, rep),
            jax.device_put(_pad_items(index.w_q, n_pad), item_sh),
            jax.device_put(_pad_items(index.w_scale, n_pad), item_sh),
            jax.device_put(index.seen, rep),
        )
    else:
        placed = RecommendIndex(
            jax.device_put(index.u, rep),
            jax.device_put(_pad_items(index.w, n_pad), item_sh),
            jax.device_put(index.seen, rep),
        )
    return ShardedRecommendIndex(placed, plan, n)


@functools.lru_cache(maxsize=None)
def _make_sharded_topk(plan, k: int, exclude_seen: bool, num_items: int,
                       shard_items: int, quant: bool = False,
                       method: str | None = None):
    """Compiled two-stage query for one (plan, k, layout) shape.

    ``quant=True`` compiles the int8 body: per-shard codes + per-item
    scales score through the ``kernels/quant`` switch (``method`` is the
    resolved trace-time scoring method); the mask/top-k/merge stages are
    identical to the f32 body."""

    axes = plan.all_axes
    ax = axes if len(axes) > 1 else axes[0]

    def select_merge(scores, start, seen, user_ids):
        local_ids = start + jnp.arange(shard_items)
        scores = jnp.where(local_ids[None, :] < num_items, scores, -jnp.inf)
        if exclude_seen:
            b = user_ids.shape[0]
            seen_l = seen[user_ids] - start                  # (B, S_seen)
            seen_l = jnp.where(
                (seen_l >= 0) & (seen_l < shard_items), seen_l, shard_items
            )
            scores = scores.at[jnp.arange(b)[:, None], seen_l].set(
                -jnp.inf, mode="drop"
            )
        sc, idx = jax.lax.top_k(scores, k)                   # stage 1: local
        ids = start + idx
        all_sc = jax.lax.all_gather(sc, ax, axis=1, tiled=True)   # (B, S·k)
        all_ids = jax.lax.all_gather(ids, ax, axis=1, tiled=True)
        msc, mix = jax.lax.top_k(all_sc, k)                  # stage 2: merge
        mids = jnp.take_along_axis(all_ids, mix, axis=1)
        return mids, msc

    if quant:
        def body(u_q, u_s, wq_local, ws_local, seen, user_ids):
            start = jax.lax.axis_index(ax) * shard_items
            scores = dequant_score(                          # (B, ln)
                u_q[user_ids], u_s[user_ids], wq_local, ws_local,
                method=method,
            )
            return select_merge(scores, start, seen, user_ids)

        in_specs = (P(), P(), plan.item_spec, plan.item_spec, P(), P())
    else:
        def body(u, w_local, seen, user_ids):
            start = jax.lax.axis_index(ax) * shard_items
            scores = jnp.matmul(u[user_ids], w_local.T,      # (B, ln)
                                precision=_F32_SCORES)
            return select_merge(scores, start, seen, user_ids)

        in_specs = (P(), plan.item_spec, P(), P())

    return jax.jit(jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=in_specs,
        out_specs=(P(), P()),
        check_vma=False,
    ))


def recommend_topk_sharded(
    sidx: ShardedRecommendIndex, user_ids: jax.Array, *,
    k: int, exclude_seen: bool = True, method: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(items, scores) of shape (B, k) from the sharded index.

    Stage 1 runs on every item shard in parallel (local matmul — or the
    fused dequantize-score switch for an int8-sharded index — local
    seen-mask, local top-k over n/S items); stage 2 all-gathers the S·k
    candidates and k-selects once.  Exact: any global top-k item is by
    definition in its own shard's top-k.  ``method`` picks the quantized
    scoring path and is ignored for f32 indices."""

    if k > sidx.shard_items:
        raise ValueError(
            f"k={k} exceeds the per-shard catalog slice "
            f"{sidx.shard_items} (= {_w_shape(sidx.index)[0]} padded items "
            f"/ {sidx.num_item_shards} shards); shrink k or use fewer shards"
        )
    if sidx.quantized:
        # resolve here so the lru key (and the compiled body) is the
        # concrete method, never two entries for None-vs-resolved
        from repro.kernels.quant import resolve_method

        fn = _make_sharded_topk(sidx.plan, k, exclude_seen, sidx.num_items,
                                sidx.shard_items, quant=True,
                                method=resolve_method(method))
        i = sidx.index
        return fn(i.u_q, i.u_scale, i.w_q, i.w_scale, i.seen, user_ids)
    fn = _make_sharded_topk(sidx.plan, k, exclude_seen, sidx.num_items,
                            sidx.shard_items)
    return fn(sidx.index.u, sidx.index.w, sidx.index.seen, user_ids)


class RecommendService:
    """Fixed-batch front end: chunk arbitrary user lists into ``batch``-sized
    jitted calls (tail padded), so serving hits exactly one compiled shape.

    Pass ``plan=`` (a ``repro.mesh.MeshPlan``) and the catalog's item axis
    is sharded over every device of the plan with the two-stage top-k
    query — the front-end contract (``recommend``, ``refresh``) is
    unchanged.  A sharded service holds the catalog **only** as its
    per-device shards (``self.index`` is ``None``): retaining the
    unsharded copy would pin the full n×r factor matrix on one device,
    which is exactly what ``plan=`` exists to avoid.

    Pass ``quant="int8"`` and the index is quantized to the int8 serving
    layout (serve/quant.py) before placement — composes with ``plan=``
    (per-shard int8), and ``refresh`` re-quantizes on every hot swap.
    ``quant_method`` picks the scoring path (``"fused"``/``"dequant"``,
    ``None`` = per-backend autotune).

    Every ``recommend`` call streams into the ``repro.obs`` registry:
    ``serve_batch_seconds`` (queue-to-answer latency per jitted batch —
    the host-side ``np.asarray`` copy already syncs the device, so the
    stamp is device-true), ``queue_wait_seconds`` (how long each chunk
    sat behind earlier chunks of the same call — host wait, kept strictly
    out of the device-time histogram), ``serve_requests_total`` /
    ``serve_users_total`` / ``serve_batches_total`` counters.  The very
    first executed batch pays the jit compile, so it lands in
    ``serve_warmup_seconds`` + ``serve_warmup_batches_total`` instead of
    ``serve_batch_seconds`` — steady-state percentiles never mix with
    compile time.  ``metrics()`` summarizes all of it into p50/p99
    latency and QPS (DESIGN.md §12)."""

    def __init__(self, index, batch: int = 256, k: int = 10,
                 exclude_seen: bool = True, plan=None,
                 quant: str | None = None, quant_method: str | None = None):
        if quant not in (None, "int8"):
            raise ValueError(
                f"unknown quant mode {quant!r}; expected None or 'int8'"
            )
        if isinstance(index, QuantizedRecommendIndex):
            quant = "int8"        # already-quantized input implies the mode
        elif quant == "int8":
            index = quantize_index(index)
        self.batch = batch
        self.k = k
        self.exclude_seen = exclude_seen
        self.plan = plan
        self.quant = quant
        self.quant_method = quant_method
        if plan is not None:
            self._sharded = shard_index(index, plan)
            self.index = None     # catalog lives only as device shards
        else:
            self._sharded = None
            self.index = index
        # first/last answer stamps bound the QPS window; per-instance so
        # two services sharing the process registry don't mix their rates
        self._t_first: float | None = None
        self._t_last: float | None = None
        self._served_users = 0
        self._served_requests = 0
        # the first batch pays the jit compile: route it to the warmup
        # histogram so steady-state percentiles stay compile-free.  Sticky
        # across reset_metrics (the jit cache survives a metrics reset).
        self._warm = False

    @property
    def num_users(self) -> int:
        if self._sharded is not None:
            return self._sharded.index.num_users
        return self.index.num_users

    @property
    def num_items(self) -> int:
        if self._sharded is not None:
            return self._sharded.num_items
        return self.index.num_items

    @property
    def num_item_shards(self) -> int:
        """Devices the catalog is partitioned over (1 when unsharded)."""

        return self._sharded.num_item_shards if self._sharded else 1

    def refresh(self, fit_result) -> "RecommendService":
        """Hot-swap the index from a (re)fit: same batch/k/jit cache, new
        factors + seen table.  In-flight ``recommend`` calls are unaffected
        (the old index is immutable); the next call serves the refresh.
        On a sharded service the refit must keep the item-shard geometry
        (``ShardedRecommendIndex.refresh`` validates and raises with the
        expected-vs-got shard counts otherwise).  Returns ``self`` for
        chaining."""

        if self._sharded is not None:
            # one index rebuild: ShardedRecommendIndex.refresh guards the
            # shard geometry and the factor shapes before swapping
            self._sharded = self._sharded.refresh(fit_result)
        else:
            self.index = self.index.refresh(fit_result)
        return self

    def recommend(self, user_ids) -> tuple[np.ndarray, np.ndarray]:
        """(items, scores) arrays of shape (len(user_ids), k)."""

        user_ids = np.asarray(user_ids, np.int32)
        n = len(user_ids)
        out_items = np.empty((n, self.k), np.int32)
        out_scores = np.empty((n, self.k), np.float32)
        # snapshot whichever backend is live: a concurrent refresh never
        # mixes universes within one call
        index = self.index
        sharded = self._sharded
        lat_h = obs.histogram("serve_batch_seconds")
        t_enter = time.perf_counter()
        if self._t_first is None:
            self._t_first = t_enter
        for s in range(0, n, self.batch):           # universes within a call
            t0 = time.perf_counter()
            # host-side wait behind this call's earlier chunks — reported
            # separately so device time and queueing never mix
            obs.histogram("queue_wait_seconds").observe(t0 - t_enter)
            chunk = user_ids[s : s + self.batch]
            pad = self.batch - len(chunk)
            if pad:
                chunk = np.pad(chunk, (0, pad))
            if sharded is not None:
                items, scores = recommend_topk_sharded(
                    sharded, jnp.asarray(chunk),
                    k=self.k, exclude_seen=self.exclude_seen,
                    method=self.quant_method,
                )
            else:
                items, scores = recommend_topk(
                    index, jnp.asarray(chunk),
                    k=self.k, exclude_seen=self.exclude_seen,
                    method=self.quant_method,
                )
            take = min(self.batch, n - s)
            # the host copies force the device sync, so the stamp below
            # is the true queue-to-answer latency of this batch
            out_items[s : s + take] = np.asarray(items)[:take]
            out_scores[s : s + take] = np.asarray(scores)[:take]
            dt = time.perf_counter() - t0
            if self._warm:
                lat_h.observe(dt)
            else:                       # first batch == jit compile
                obs.histogram("serve_warmup_seconds").observe(dt)
                obs.counter("serve_warmup_batches_total").inc()
                self._warm = True
            obs.counter("serve_batches_total").inc()
        self._t_last = time.perf_counter()
        self._served_users += n
        self._served_requests += 1
        obs.counter("serve_requests_total").inc()
        obs.counter("serve_users_total").inc(n)
        return out_items, out_scores

    def reset_metrics(self) -> None:
        """Zero this service's request/QPS window — benches call it after
        the warmup/compile request so ``metrics()`` reports steady state.
        (The shared ``serve_*`` registry metrics are separate; reset those
        with ``obs.reset()``.)"""

        self._t_first = self._t_last = None
        self._served_users = self._served_requests = 0

    def metrics(self) -> dict:
        """Latency/throughput summary of everything served so far.

        ``latency`` holds the ``serve_batch_seconds`` histogram summary
        (count/mean/p50/p90/p99, seconds per jitted batch, **warmup
        excluded** — the compile-paying first batch reports under
        ``warmup`` instead); ``queue_wait`` is the host-side chunk wait,
        separate from device time; ``qps`` and ``users_per_s`` divide the
        served totals by the first-to-last answer window.  All zeros
        before the first ``recommend`` call or when the registry is
        disabled."""

        summ = obs.histogram("serve_batch_seconds").summary()
        window = 0.0
        if self._t_first is not None and self._t_last is not None:
            window = self._t_last - self._t_first
        rate = (1.0 / window) if window > 0 else 0.0
        return {
            "latency": summ,
            "queue_wait": obs.histogram("queue_wait_seconds").summary(),
            "warmup": {
                "batches": obs.counter("serve_warmup_batches_total").value,
                "seconds": obs.histogram("serve_warmup_seconds").summary(),
            },
            "requests": self._served_requests,
            "users": self._served_users,
            "qps": self._served_requests * rate,
            "users_per_s": self._served_users * rate,
            "window_seconds": window,
        }
