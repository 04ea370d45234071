"""``ServingEngine`` — the always-hot request path over a trained index.

Owns the full pipeline (DESIGN.md §14): a :class:`BucketLadder` routes
every request onto a fixed set of batch shapes, ``compile_buckets`` AOT-
compiles one executable per bucket **at startup**, and a
:class:`~repro.serving.queue.ServeWorker` drains submitted requests into
bucketed executions behind futures.  The contract the tests pin:

* **no serve-time compiles** — ``serve_compiles_total`` equals the bucket
  count after ``__init__`` and never moves again;
* **bit-identity** — the unsharded executables are the compiled form of
  ``recommend_topk`` itself, so engine answers equal the jit path's
  exactly (and the sharded path equals ``recommend_topk_sharded``);
* **hot refresh** — ``refresh(result)`` swaps the factor buffers (same
  shapes, seen table re-padded to the fixed ``seen_capacity``) without
  invalidating a single executable, and a request always runs against
  exactly one factor version (atomic snapshot per request);
* **clean shutdown** — ``drain()`` resolves the backlog, ``shutdown()``
  then rejects new work.

:class:`RefreshPolicy` adds the auto-refit loop: ``note_append(n)``
bookkeeping trips a ``Trainer.refit`` + hot swap once enough appends (or
enough wall time) accumulate — the serving side of the streaming story
in DESIGN.md §11, now policy-driven instead of hand-rolled.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Iterable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.quant import resolve_method
from repro.serve.quant import (QuantizedRecommendIndex, index_nbytes,
                               quantize_index)
from repro.serve.recommend import (RecommendIndex, _u_shape, _w_shape,
                                   shard_index)
from repro.serving.buckets import DEFAULT_BUCKETS, BucketLadder
from repro.serving.compiler import compile_buckets
from repro.serving.queue import Request, ServeWorker


@dataclasses.dataclass(frozen=True)
class RefreshPolicy:
    """When should the engine refit and hot-swap its factors?

    ``max_appends``: refit once this many appended ratings accumulate
    (``note_append`` counts them).  ``max_age_seconds``: refit once the
    serving factors are this stale, checked at ``note_append`` time (the
    engine never spawns its own timer thread).  Either may be ``None``;
    at least one must be set."""

    max_appends: Optional[int] = None
    max_age_seconds: Optional[float] = None

    def __post_init__(self) -> None:
        if self.max_appends is None and self.max_age_seconds is None:
            raise ValueError(
                "RefreshPolicy needs max_appends and/or max_age_seconds"
            )
        if self.max_appends is not None and self.max_appends <= 0:
            raise ValueError(f"max_appends must be positive, "
                             f"got {self.max_appends}")
        if self.max_age_seconds is not None and self.max_age_seconds <= 0:
            raise ValueError(f"max_age_seconds must be positive, "
                             f"got {self.max_age_seconds}")

    def due(self, appends: int, age_seconds: float) -> bool:
        if self.max_appends is not None and appends >= self.max_appends:
            return True
        if (self.max_age_seconds is not None
                and age_seconds >= self.max_age_seconds):
            return True
        return False


def _pad_seen(seen, capacity: int, num_items: int):
    """Widen a seen table to the engine's fixed capacity (pad = n, the
    out-of-range id the serve-time scatter drops)."""

    width = seen.shape[1]
    if width > capacity:
        raise ValueError(
            f"seen table width {width} exceeds the engine's fixed capacity "
            f"{capacity}; rebuild the engine with a larger seen_headroom "
            f"(executable shapes are frozen at startup, so the seen axis "
            f"cannot grow under a refresh)"
        )
    if width == capacity:
        return jnp.asarray(seen)
    pad = jnp.full((seen.shape[0], capacity - width), num_items, jnp.int32)
    return jnp.concatenate([jnp.asarray(seen), pad], axis=1)


class ServingEngine:
    """AOT bucket-batched serving front end (see module docstring).

    ``plan=`` (a ``repro.mesh.MeshPlan``) shards the catalog's item axis
    over the plan's devices exactly like ``RecommendService(plan=...)``;
    the unsharded index is not retained.  ``seen_headroom`` reserves extra
    seen-table columns so post-append refreshes (whose tables are wider)
    still fit the frozen executable shapes.

    ``quant="int8"`` serves the int8 factor cache (DESIGN.md §16): the
    index is quantized (symmetric per-row, serve/quant.py) before the
    bucket executables lower, so every AOT program scores through the
    fused dequantize-score switch — composes with ``plan=`` (per-shard
    int8) and with ``refresh`` (re-quantize on every hot swap).
    ``quant_method`` picks the scoring path (``"fused"``/``"dequant"``;
    ``None`` resolves per backend once, at startup, so all buckets and
    every later refresh serve one concrete method)."""

    def __init__(
        self,
        index,
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        k: int = 10,
        exclude_seen: bool = True,
        plan=None,
        seen_headroom: int = 64,
        refresh_policy: Optional[RefreshPolicy] = None,
        quant: Optional[str] = None,
        quant_method: Optional[str] = None,
    ):
        self.ladder = (buckets if isinstance(buckets, BucketLadder)
                       else BucketLadder(tuple(buckets)))
        self.k = k
        self.exclude_seen = exclude_seen
        self.plan = plan
        self.refresh_policy = refresh_policy
        if quant not in (None, "int8"):
            raise ValueError(
                f"unknown quant mode {quant!r}; expected None or 'int8'"
            )
        if isinstance(index, QuantizedRecommendIndex):
            quant = "int8"        # already-quantized input implies the mode
        elif quant == "int8":
            index = quantize_index(index)
        self.quant = quant
        # resolve once: all bucket executables (and the jit path a parity
        # test compares against) share one concrete scoring method
        self.quant_method = resolve_method(quant_method) if quant else None
        self.num_users = int(index.num_users)
        self.num_items = int(index.num_items)
        if seen_headroom < 0:
            raise ValueError(f"seen_headroom must be >= 0, "
                             f"got {seen_headroom}")
        self.seen_capacity = int(index.seen.shape[1]) + int(seen_headroom)
        index = index._replace(
            seen=_pad_seen(index.seen, self.seen_capacity, self.num_items)
        )
        obs.gauge("serve_index_bytes",
                  dtype="int8" if quant else "f32").set(index_nbytes(index))
        if plan is not None:
            self._bufs = shard_index(index, plan)
            sharded = self._bufs
        else:
            self._bufs = index
            sharded = None
        self._execs = compile_buckets(
            index, self.ladder, k, exclude_seen,
            plan=plan, sharded_index=sharded, method=self.quant_method,
        )
        # auto-refit state (RefreshPolicy / note_append)
        self._trainer = None
        self._fit_result = None
        self._appends_since_refresh = 0
        self._t_last_refresh = time.perf_counter()
        self._refresh_lock = threading.Lock()
        # QPS window, same discipline as RecommendService
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._served_users = 0
        self._served_requests = 0
        self._worker = ServeWorker(self._execute)

    # ------------------------------------------------------------------ #
    # request path
    # ------------------------------------------------------------------ #

    def submit(self, user_ids) -> Future:
        """Enqueue one request; the future resolves to (items, scores)
        numpy arrays of shape (len(user_ids), k)."""

        user_ids = np.asarray(user_ids, np.int32).ravel()
        if user_ids.size == 0:
            raise ValueError("empty request")
        return self._worker.submit(user_ids)

    def recommend(self, user_ids) -> Tuple[np.ndarray, np.ndarray]:
        """Synchronous convenience: submit + wait."""

        return self.submit(user_ids).result()

    def recommend_many(
        self, requests: Iterable
    ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Submit a batch of requests, wait for all, return results in
        submission order."""

        futures = [self.submit(r) for r in requests]
        return [f.result() for f in futures]

    def _execute(self, req: Request) -> Tuple[np.ndarray, np.ndarray]:
        """Worker-thread body: route one request through the ladder.

        The factor snapshot is taken ONCE per request — a concurrent
        ``refresh`` swap lands between requests, never inside one, so
        every answer reflects exactly one factor version."""

        bufs = self._bufs
        user_ids = req.user_ids
        n = len(user_ids)
        out_items = np.empty((n, self.k), np.int32)
        out_scores = np.empty((n, self.k), np.float32)
        if self._t_first is None:
            self._t_first = time.perf_counter()
        for start, length, bucket in self.ladder.plan(n):
            # host side of the chunk: pad + the executable call (async)
            with obs.span("serve.dispatch", annotate=True, rid=req.rid,
                          bucket=bucket) as dispatch:
                chunk = user_ids[start : start + length]
                if length < bucket:
                    chunk = np.pad(chunk, (0, bucket - length))
                items, scores = self._execs[bucket](bufs, chunk)
            # host copies force the device sync → device-true batch stamp
            with obs.span("serve.fetch", annotate=True, rid=req.rid,
                          bucket=bucket) as fetch:
                rows = slice(start, start + length)
                out_items[rows] = np.asarray(items)[:length]
                out_scores[rows] = np.asarray(scores)[:length]
            obs.histogram("serve_batch_seconds", bucket=str(bucket)).observe(
                dispatch.seconds + fetch.seconds
            )
            obs.counter("engine_batches_total").inc()
        obs.histogram("serve_request_seconds").observe(
            time.perf_counter() - req.t_submit
        )
        obs.counter("engine_requests_total").inc()
        obs.counter("engine_users_total").inc(n)
        self._t_last = time.perf_counter()
        self._served_users += n
        self._served_requests += 1
        return out_items, out_scores

    # ------------------------------------------------------------------ #
    # refresh
    # ------------------------------------------------------------------ #

    def refresh(self, result) -> "ServingEngine":
        """Hot-swap the factor buffers from a refit (or a bare index).

        Accepts a ``FitResult`` (anything with ``to_recommend_index``) or
        a bare index.  The new factors must keep the engine's
        (m, r) × (n, r) shapes and the new seen table must fit the fixed
        ``seen_capacity`` — then the swap is one atomic attribute store
        and every compiled executable keeps running untouched.

        On an int8 engine a fresh f32 fit **re-quantizes on the swap**
        (the documented hot path: new factors in, new codes + scales out,
        executables untouched).  The reverse never flies: the layouts may
        not mix, and handing a quantized index to an f32 engine (or vice
        versa an f32-only engine a quantized one) raises instead of
        silently serving through executables compiled for the other
        layout."""

        if hasattr(result, "to_recommend_index"):
            new = result.to_recommend_index()
        else:
            new = result
        if self.quant is None and isinstance(new, QuantizedRecommendIndex):
            raise ValueError(
                "refresh would mix factor layouts: this engine's bucket "
                "executables are compiled against the f32 layout, but the "
                "swap-in is a QuantizedRecommendIndex (int8); serve int8 "
                "through ServingEngine(quant='int8') — a refresh cannot "
                "change the compiled layout"
            )
        if self.quant == "int8":
            # f32 fit → fresh codes + scales; already-int8 → unchanged
            new = quantize_index(new)
        with self._refresh_lock:
            old_u, old_w = self._factor_shapes()
            got_u, got_w = _u_shape(new), _w_shape(new)
            if got_u != old_u or got_w != old_w:
                raise ValueError(
                    f"refresh changes the factor shapes: expected "
                    f"u{old_u} x w{old_w}"
                    f"{' (int8 layout)' if self.quant else ''}, got "
                    f"u{got_u} x w{got_w}; a re-shaped problem needs a "
                    f"new ServingEngine, not a refresh"
                )
            new = new._replace(
                seen=_pad_seen(new.seen, self.seen_capacity, self.num_items)
            )
            obs.gauge("serve_index_bytes",
                      dtype="int8" if self.quant else "f32").set(
                          index_nbytes(new))
            if self.plan is not None:
                self._bufs = shard_index(new, self.plan)
            else:
                self._bufs = new
            if hasattr(result, "to_recommend_index"):
                self._fit_result = result
            self._appends_since_refresh = 0
            self._t_last_refresh = time.perf_counter()
        obs.counter("engine_refreshes_total").inc()
        obs.gauge("engine_last_refresh_age_seconds").set(0.0)
        return self

    def _factor_shapes(self):
        idx = self._bufs.index if self.plan is not None else self._bufs
        u_shape, w_shape = _u_shape(idx), _w_shape(idx)
        # sharded buffers carry shard padding on the item axis; the
        # refresh contract is against the true catalog size
        return u_shape, (self.num_items, w_shape[1])

    def bind(self, trainer, result) -> "ServingEngine":
        """Attach the training side for policy-driven auto-refit:
        ``trainer.refit(result, problem)`` is what ``note_append`` runs
        when the :class:`RefreshPolicy` trips."""

        self._trainer = trainer
        self._fit_result = result
        return self

    def note_append(self, n: int, problem=None) -> bool:
        """Record ``n`` just-appended ratings (and optionally the grown
        problem); refit + hot-swap when the policy is due.

        Returns True iff a refresh happened.  Without a bound trainer (or
        without a policy) this is pure bookkeeping."""

        if n < 0:
            raise ValueError(f"note_append takes a non-negative count, "
                             f"got {n}")
        self._appends_since_refresh += n
        if problem is not None:
            self._latest_problem = problem
        age = time.perf_counter() - self._t_last_refresh
        obs.gauge("engine_last_refresh_age_seconds").set(age)
        policy = self.refresh_policy
        if policy is None or self._trainer is None \
                or self._fit_result is None:
            return False
        if not policy.due(self._appends_since_refresh, age):
            return False
        problem = getattr(self, "_latest_problem", None)
        refit = self._trainer.refit(self._fit_result, problem)
        self.refresh(refit)
        return True

    @property
    def appends_since_refresh(self) -> int:
        return self._appends_since_refresh

    # ------------------------------------------------------------------ #
    # observability + lifecycle
    # ------------------------------------------------------------------ #

    def metrics(self) -> dict:
        """Engine health in one dict, riding the ``repro.obs`` registry:
        queue depth, per-bucket on-device batch latency, end-to-end
        request latency, queue wait (kept separate from device time),
        compile/refresh counters, and the QPS window."""

        age = time.perf_counter() - self._t_last_refresh
        obs.gauge("engine_last_refresh_age_seconds").set(age)
        window = 0.0
        if self._t_first is not None and self._t_last is not None:
            window = self._t_last - self._t_first
        rate = (1.0 / window) if window > 0 else 0.0
        return {
            "queue_depth": self._worker.depth,
            "latency": obs.histogram("serve_request_seconds").summary(),
            "queue_wait": obs.histogram("queue_wait_seconds").summary(),
            "buckets": {
                b: obs.histogram("serve_batch_seconds",
                                 bucket=str(b)).summary()
                for b in self.ladder.sizes
            },
            "compiles": obs.counter("serve_compiles_total").value,
            "refreshes": obs.counter("engine_refreshes_total").value,
            "appends_since_refresh": self._appends_since_refresh,
            "last_refresh_age_seconds": age,
            "requests": self._served_requests,
            "users": self._served_users,
            "qps": self._served_requests * rate,
            "users_per_s": self._served_users * rate,
            "window_seconds": window,
        }

    def reset_metrics(self) -> None:
        """Zero the engine's QPS window (benches: call after warmup).
        Shared registry metrics reset separately via ``obs.reset()``."""

        self._t_first = self._t_last = None
        self._served_users = self._served_requests = 0

    def drain(self) -> None:
        """Block until every already-submitted request has resolved."""

        self._worker.drain()

    def shutdown(self, drain: bool = True) -> None:
        """Reject new requests, finish (or cancel) the backlog, stop the
        worker thread.  Idempotent."""

        self._worker.shutdown(drain=drain)

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=True)
