"""Pluggable execution schedules for ``Trainer.fit``.

One scheduler-agnostic problem abstraction over interchangeable update
orders (the NOMAD / Riemannian-gossip presentation): every schedule
consumes the same ``CompletionProblem`` + ``GossipMCConfig`` + PRNG key and
produces the same ``(State, history)`` pair, so callers swap execution
strategies without touching data plumbing.

    Sequential  — Algorithm 1 verbatim: one random structure per iteration
    Wave        — ≤8 conflict-free parity waves per round, vectorized
    FullGD      — deterministic limit: all structures at once (GD on L)
    Gossip      — distributed shard_map rounds with ppermute halo exchange
    Incremental — short wave run sized for ``Trainer.refit`` warm starts

Each schedule wraps the corresponding internal loop in ``core/`` (the same
code the deprecated ``sequential.fit`` / ``waves.fit`` shims call), so
facade and legacy paths are bit-identical given the same key.

The ``run`` contract: ``run(problem, cfg, key, state=None, done=0,
eval_cb=None)`` where ``done`` (in the schedule's own units — iterations
or rounds) resumes a checkpointed run and ``eval_cb(unit, cost, state,
key)`` fires at every eval boundary (the restart-exact checkpoint hook).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import numpy as np

from repro.config import GossipMCConfig
from repro.core import gossip as core_gossip
from repro.core import sequential as core_sequential
from repro.core import waves as core_waves
from repro.core.state import State, init_state
from repro.mc.problem import CompletionProblem

EvalCb = Optional[Callable[[int, float, State, jax.Array], None]]


class Schedule:
    """Strategy interface: subclasses define ``name``, ``units`` and
    ``run``."""

    name = "abstract"
    units = "rounds"

    def run(self, problem: CompletionProblem, cfg: GossipMCConfig,
            key: jax.Array, *, state: State | None = None, done: int = 0,
            eval_cb: EvalCb = None) -> tuple[State, list[tuple[int, float]]]:
        raise NotImplementedError

    def grad_path(self, problem: CompletionProblem) -> str:
        """The f-term arithmetic this schedule's rounds take on
        ``problem`` (``CompletionProblem.grad_path``)."""

        return problem.grad_path


@dataclasses.dataclass(frozen=True)
class Sequential(Schedule):
    """Paper Algorithm 1: one uniformly sampled structure per iteration."""

    num_iters: int = 20_000
    eval_every: int = 0

    name = "sequential"
    units = "iterations"

    def run(self, problem, cfg, key, *, state=None, done=0, eval_cb=None):
        eng = problem.engine
        return core_sequential._fit(
            problem.data, problem.spec, cfg, key,
            num_iters=self.num_iters, eval_every=self.eval_every,
            state=state, use_kernel=eng.use_kernel, method=eng.method,
            chunk=eng.chunk, done=done, progress_cb=eval_cb,
        )


@dataclasses.dataclass(frozen=True)
class Wave(Schedule):
    """Parity-wave rounds: all non-overlapping structures of a wave updated
    in one vectorized conflict-free step, waves in random order."""

    num_rounds: int = 200
    eval_every: int = 0

    name = "wave"
    units = "rounds"
    _mode = "wave"

    def run(self, problem, cfg, key, *, state=None, done=0, eval_cb=None):
        eng = problem.engine
        return core_waves._fit(
            problem.data, problem.spec, cfg, key,
            num_rounds=self.num_rounds, eval_every=self.eval_every,
            mode=self._mode, state=state, use_kernel=eng.use_kernel,
            method=eng.method, chunk=eng.chunk, start_round=done,
            progress_cb=eval_cb,
        )


@dataclasses.dataclass(frozen=True)
class FullGD(Wave):
    """Deterministic limit: every structure at once = GD on the collapsed
    objective L (what each gossip device computes per tile)."""

    name = "full"
    _mode = "full"


@dataclasses.dataclass(frozen=True)
class Incremental(Wave):
    """Warm-start refresh rounds — the default for ``Trainer.refit``.

    Same wave updates as :class:`Wave`, sized for the streaming loop
    (DESIGN.md §11): after an append the factors are already near the new
    optimum, so a short run of cheap rounds recovers the cold-fit quality
    at a fraction of the iterations.  Only the default size differs —
    resuming from a trained ``State`` is what makes it incremental."""

    num_rounds: int = 40
    eval_every: int = 0

    name = "incremental"


@dataclasses.dataclass(frozen=True)
class Gossip(Schedule):
    """Distributed full-GD rounds over a device mesh: shard_map tiles the
    (p, q) block grid, factor edges travel by ``ppermute`` (one ICI hop),
    bounded staleness and optional int8/top-k message compression ride on
    the halo exchange.

    Placement comes from one ``MeshPlan`` (priority: ``plan=`` on the
    schedule, then ``mesh=`` + ``row_axes``/``col_axes``, then the
    problem's own ``CompletionProblem.plan``, else a 1×1 single-device
    plan — the degenerate case, numerically identical to ``FullGD``,
    parity-tested).  A problem built with ``mesh=`` is already placed on
    its owners, so the jitted step consumes the shards with no input
    resharding.

    Checkpoint resume restores factors only; with ``staleness == 1`` and no
    compression the halos are rebuilt on the first resumed round, so resume
    is exact.  Stale-halo / error-feedback state is intentionally not
    persisted (a restarted node re-gossips, matching the paper's fault
    model).

    ``faults=FaultPlan(...)`` turns on deterministic fault injection
    (DESIGN.md §13): dropped/straggling edges reuse the last received
    halo, ages past ``max_staleness`` degrade the seam to the local-only
    gradient, and per-chunk fault counts stream into the obs registry
    (``gossip_edges_dropped_total``, ``gossip_stale_rounds_total``,
    ``gossip_straggled_edges_total``, ``gossip_halo_age``).  With
    ``faults=None`` the legacy step runs verbatim — bit-identical.

    ``batch=<int>`` switches to stochastic rounds (DESIGN.md §15): every
    round samples a fresh per-block minibatch via a restart-exact
    ``MinibatchStream`` (stream base derived from the fit key, per-round
    key = fold_in(base, absolute round) — a killed-and-resumed fit replays
    the identical entry stream) and feeds it to the step with the
    ``minibatch_grad_scale`` unbiasedness correction, so a round costs
    O(batch) per device instead of O(nnz).  Requires the sparse layout.
    ``batch_seed=`` overrides the stream base with a fixed seed.

    ``async_rounds=True`` is the NOMAD-style non-blocking regime: halo
    exchange fires every ``exchange_every``-th round only; skipped rounds
    run on the last *received* halos with ``HaloState.age`` counting
    rounds-since-receive, bounded by ``max_staleness`` (past it the seam
    gates out).  Planned skips and ``faults=`` compose on the same
    age/gate machinery.  Wire-byte and stale/skip accounting is exact:
    ``train_gossip_halo_bytes_total`` counts only rounds that exchanged,
    and ``gossip_skipped_exchanges_total`` / ``gossip_stale_rounds_total``
    stream the skipped-exchange and stale-round counts per chunk.  With
    ``exchange_every=1, max_staleness=0, batch=None`` the async step is
    bit-identical to the synchronous one (pinned by test)."""

    num_rounds: int = 200
    eval_every: int = 0
    mesh: Any = None
    plan: Any = None
    row_axes: Any = "data"
    col_axes: Any = "model"
    staleness: int = 1
    compression: str = "none"
    topk_fraction: float = 0.25
    faults: Any = None
    max_staleness: int = 3
    batch: Optional[int] = None
    batch_seed: Optional[int] = None
    async_rounds: bool = False
    exchange_every: int = 1

    name = "gossip"
    units = "rounds"

    def grad_path(self, problem: CompletionProblem) -> str:
        # the mesh step drops the store's dense tile (core/gossip.py)
        path = problem.grad_path
        return "segment" if path == "tile" else path

    def _plan(self, problem):
        from repro.mesh.plan import MeshPlan

        p, q = problem.spec.p, problem.spec.q
        if self.plan is not None:
            return MeshPlan.build(p, q, mesh=self.plan)
        if self.mesh is not None:
            return MeshPlan.build(p, q, mesh=self.mesh,
                                  row_axes=self.row_axes,
                                  col_axes=self.col_axes)
        if getattr(problem, "plan", None) is not None:
            return problem.plan
        return MeshPlan.build(p, q, row_axes=self.row_axes,
                              col_axes=self.col_axes)

    def run(self, problem, cfg, key, *, state=None, done=0, eval_cb=None):
        from repro import obs

        eng = problem.engine
        plan = self._plan(problem)
        if self.batch is not None and problem.layout != "sparse":
            raise ValueError(
                "Gossip(batch=) needs layout='sparse': stochastic rounds "
                "sample the sparse store"
            )
        if state is None:
            key, ik = jax.random.split(key)
            state = init_state(ik, problem.spec)
        # round0=done keeps the FaultPlan clock aligned on resume: replay
        # continues at the round the checkpoint completed
        carry = core_gossip.init_carry(state, round0=done)
        eval_every = self.eval_every or self.num_rounds
        steps: dict[int, Any] = {}

        stream = scale = None
        if self.batch is not None:
            from repro.sparse.store import (MinibatchStream,
                                            minibatch_grad_scale)

            # the stream base is a pure function of the fit key (post
            # init-split — exactly what Checkpoint saves), so a resumed
            # fit replays the identical per-round minibatches; the plan
            # path keys blocks by global id => mesh-shape invariant
            base = (jax.random.PRNGKey(self.batch_seed)
                    if self.batch_seed is not None
                    else jax.random.fold_in(key, 0x0b_a7c4))
            stream = MinibatchStream(problem.data, self.batch, seed=base,
                                     plan=plan)
            scale = jax.device_put(
                minibatch_grad_scale(problem.data, self.batch),
                plan.sharding(plan.grid_spec),
            )

        # exact comm accounting from the plan's edge specs: what one
        # exchange moves over the wires (0 on a 1x1 plan — no wires, no
        # bytes); per chunk only the rounds that actually exchanged count
        spec = problem.spec
        exchange_bytes = core_gossip.halo_bytes_per_round(
            plan, spec.mb, spec.nb, spec.r, self.compression,
        )["total_bytes"]
        stride = self.exchange_every if self.async_rounds \
            else max(self.staleness, 1)
        rounds_c = obs.counter("train_gossip_rounds_total")
        bytes_c = obs.counter("train_gossip_halo_bytes_total")
        round_h = obs.histogram("train_gossip_round_seconds")
        track_stats = self.faults is not None or self.async_rounds
        if track_stats:
            dropped_c = obs.counter("gossip_edges_dropped_total")
            stale_c = obs.counter("gossip_stale_rounds_total")
            strag_c = obs.counter("gossip_straggled_edges_total")
            age_h = obs.histogram("gossip_halo_age")
            seen = (0, 0, 0)
        if self.async_rounds:
            skipped_c = obs.counter("gossip_skipped_exchanges_total")

        def step_for(n: int):
            if n not in steps:
                steps[n], _ = core_gossip.gossip_step(
                    (problem.spec.p, problem.spec.q), cfg, plan=plan,
                    staleness=self.staleness, compression=self.compression,
                    topk_fraction=self.topk_fraction,
                    use_kernel=eng.use_kernel, steps_per_call=n,
                    layout=problem.layout, method=eng.method, chunk=eng.chunk,
                    faults=self.faults, max_staleness=self.max_staleness,
                    async_rounds=self.async_rounds,
                    exchange_every=self.exchange_every, batch=self.batch,
                )
            return steps[n]

        history: list[tuple[int, float]] = []
        rd = done
        while rd < self.num_rounds:
            n = min(eval_every - rd % eval_every, self.num_rounds - rd)
            with obs.span("gossip.rounds") as sp:
                if stream is None:
                    carry = sp.outputs(step_for(n)(problem.data, carry))
                else:
                    # stochastic rounds: one sampled store per round, keyed
                    # on the absolute round (restart-exact replay).  Each
                    # round blocks before the next dispatch: the step
                    # carries collectives, and XLA-CPU's rendezvous can
                    # deadlock when several in-flight executions of a
                    # collective program interleave (the scan path never
                    # sees this — all its rounds share one execution)
                    step = step_for(1)
                    for t in range(rd, rd + n):
                        carry = step(stream.batch_at(t), scale, carry)
                        jax.block_until_ready(carry.state.t)
                    carry = sp.outputs(carry)
            rounds_c.inc(n)
            if self.async_rounds:
                # exchange fires on absolute rounds rnd % exchange_every
                # == 0 — count the chunk's exchange rounds exactly
                n_ex = core_gossip.exchange_rounds_in(rd, n,
                                                      self.exchange_every)
                skipped_c.inc(n - n_ex)
            else:
                # the sync staleness clock restarts per chunked call
                n_ex = core_gossip.exchange_rounds_in(0, n, stride)
            bytes_c.inc(n_ex * exchange_bytes)
            round_h.observe(sp.seconds / n)
            if track_stats:
                # carry stats are cumulative device-side; diff per chunk so
                # counters stream monotonically during the fit
                tot = tuple(int(np.asarray(x).sum()) for x in carry.stats)
                dropped_c.inc(tot[0] - seen[0])
                stale_c.inc(tot[1] - seen[1])
                strag_c.inc(tot[2] - seen[2])
                seen = tot
                self._observe_ages(age_h, plan, carry.halos.age)
            rd += n
            cost = float(core_gossip.distributed_cost(
                None, problem.data, carry.state, cfg.lam, plan=plan,
            ))
            history.append((int(carry.state.t), cost))
            if eval_cb:
                eval_cb(rd, cost, carry.state, key)
        return carry.state, history

    @staticmethod
    def _observe_ages(age_h, plan, age) -> None:
        """Sample each device's per-direction halo age into the histogram
        (one block per device — blocks of a shard share the age), skipping
        non-existent edges and the never-received sentinel."""

        from repro.faults.plan import AGE_NEVER

        ages = np.asarray(age)
        bpr, bpc = plan.blocks_per_row_shard, plan.blocks_per_col_shard
        for di in range(plan.row_size):
            for dj in range(plan.col_size):
                a = ages[di * bpr, dj * bpc]
                exists = (dj > 0, dj < plan.col_size - 1,
                          di > 0, di < plan.row_size - 1)
                for d in range(4):
                    if exists[d] and a[d] < AGE_NEVER:
                        age_h.observe(float(a[d]))


_BY_NAME = {
    "sequential": Sequential,
    "wave": Wave,
    "full": FullGD,
    "full_gd": FullGD,
    "gossip": Gossip,
    "incremental": Incremental,
}


def make_schedule(spec: Union[str, Schedule], **overrides) -> Schedule:
    """Resolve a schedule: pass a ``Schedule`` through, or build one from
    its name (``"sequential" | "wave" | "full" | "gossip"``) with default
    sizes overridable by keyword."""

    if isinstance(spec, Schedule):
        if overrides:
            return dataclasses.replace(spec, **overrides)
        return spec
    try:
        cls = _BY_NAME[spec]
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown schedule {spec!r}; expected one of "
            f"{sorted(_BY_NAME)} or a Schedule instance"
        ) from None
    return cls(**overrides)
