"""``Trainer`` + ``FitResult`` — the session layer over the schedules.

One ``fit`` for every execution strategy:

    from repro.mc import CompletionProblem, Trainer, Wave

    problem = CompletionProblem.from_dataset(ds, p=4, q=4, rank=8,
                                             layout="sparse")
    result = Trainer(cfg).fit(problem, schedule="wave", seed=0)
    result = Trainer(cfg).fit(problem, Wave(num_rounds=500, eval_every=50))

    svc = result.to_service(k=10)          # straight into serving
    items, scores = svc.recommend(user_ids)

    fresh = problem.append(new_rows, new_cols, new_vals)
    result = Trainer(cfg).refit(result, fresh)     # warm-start refresh
    svc.refresh(result)                            # hot-swap the index

``FitResult`` carries the final ``State``, the (t, cost) loss trace,
wall-clock stats, and the bridges into evaluation (``factors``, ``rmse``)
and serving (``to_recommend_index`` → ``serve.recommend``).

Key discipline: with the same seed, ``Trainer.fit`` is bit-identical to
the legacy ``sequential.fit`` / ``waves.fit`` entry points (the schedules
call the same internal loops) — pinned by the facade-vs-direct parity
tests.  Checkpoint resume (``resume_from=``) restores (state, key, unit)
saved by the ``Checkpoint`` callback and replays the identical stream.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Union

import jax
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.config import GossipMCConfig
from repro.core import assemble as asm
from repro.core.state import State
from repro.mc.callbacks import Callback, Checkpoint, restore_session
from repro.mc.problem import CompletionProblem
from repro.mc.schedules import Schedule, make_schedule
from repro.serve.recommend import RecommendIndex, RecommendService, build_index


@dataclasses.dataclass
class FitResult:
    """Everything a finished fit produced."""

    state: State
    history: list            # (t, cost) pairs at eval boundaries
    wall_time: float         # seconds inside the schedule loop
    schedule: str            # schedule name ("sequential" | ... | "gossip")
    problem: CompletionProblem
    # one entry per self-healing restart (Trainer.fit(recovery=...)):
    # {restart, unit, cost, reason, resumed_from, step_a}
    recovery_log: list = dataclasses.field(default_factory=list)

    @property
    def final_cost(self) -> float:
        return self.history[-1][1] if self.history else float("nan")

    @property
    def t(self) -> int:
        """Structure-update count (the paper's iteration clock)."""

        return int(self.state.t)

    def factors(self) -> tuple[jax.Array, jax.Array]:
        """Consensus-assembled global (m, r) / (n, r) factors."""

        return asm.assemble(self.state.U, self.state.W, self.problem.spec)

    def consensus_error(self) -> tuple[float, float]:
        return asm.consensus_error(self.state.U, self.state.W)

    def rmse(self, rows=None, cols=None, vals=None) -> float:
        """Held-out completion RMSE; defaults to the problem's attached
        dataset test split (``vals`` are compared in the problem's
        mean-centered frame automatically)."""

        if rows is None:
            ds = self.problem.dataset
            if ds is None:
                raise ValueError(
                    "no test triplets: attach a dataset "
                    "(CompletionProblem.from_dataset) or pass "
                    "rows/cols/vals explicitly"
                )
            rows, cols, vals = ds.test_rows, ds.test_cols, ds.test_vals
        u, w = self.factors()
        return asm.rmse(u, w, rows, cols,
                        np.asarray(vals, np.float32) - self.problem.mu)

    def to_recommend_index(self) -> RecommendIndex:
        """Bridge straight into ``serve.recommend``: assemble the factors,
        trim grid padding to the true (num_users, num_items) shape, and
        attach the seen-item exclusion table from the problem's observed
        entries."""

        p = self.problem
        return build_index(
            self.state.U, self.state.W, p.spec,
            num_users=p.num_users or None, num_items=p.num_items or None,
            seen_coo=p.seen_coo,
        )

    def to_service(self, batch: int = 256, k: int = 10,
                   exclude_seen: bool = True, plan=None,
                   quant=None, quant_method=None) -> RecommendService:
        """Fixed-batch top-k serving front end over the trained factors.

        ``plan`` (a ``repro.mesh.MeshPlan``; defaults to the problem's own
        plan when it spans multiple devices) shards the catalog's item
        axis over the plan's devices with the two-stage top-k query —
        serving for catalogs larger than one device.  ``quant="int8"``
        serves the int8 factor cache (DESIGN.md §16); ``quant_method``
        picks its scoring path."""

        if plan is None:
            pp = getattr(self.problem, "plan", None)
            if pp is not None and not pp.is_single_device:
                plan = pp
        return RecommendService(self.to_recommend_index(), batch=batch, k=k,
                                exclude_seen=exclude_seen, plan=plan,
                                quant=quant, quant_method=quant_method)

    def to_engine(self, buckets=None, k: int = 10, exclude_seen: bool = True,
                  plan=None, refresh_policy=None, trainer=None,
                  seen_headroom: int = 64, quant=None, quant_method=None):
        """AOT bucket-batched serving engine over the trained factors
        (``repro.serving.ServingEngine``, DESIGN.md §14) — every bucket
        compiled eagerly here, so the first request is already hot.

        ``plan`` defaults like :meth:`to_service`; pass ``trainer`` (plus
        a ``refresh_policy``) and the engine is bound for policy-driven
        auto-refit: ``engine.note_append(n, problem)`` runs
        ``trainer.refit`` and hot-swaps the factors once the policy trips.
        ``quant="int8"`` lowers every bucket executable against the int8
        factor cache (DESIGN.md §16)."""

        from repro.serving import DEFAULT_BUCKETS, ServingEngine

        if plan is None:
            pp = getattr(self.problem, "plan", None)
            if pp is not None and not pp.is_single_device:
                plan = pp
        engine = ServingEngine(
            self.to_recommend_index(),
            buckets=buckets if buckets is not None else DEFAULT_BUCKETS,
            k=k, exclude_seen=exclude_seen, plan=plan,
            seen_headroom=seen_headroom, refresh_policy=refresh_policy,
            quant=quant, quant_method=quant_method,
        )
        engine._fit_result = self
        if trainer is not None:
            engine.bind(trainer, self)
        return engine


class Trainer:
    """Runs any ``Schedule`` against any ``CompletionProblem``.

    ``cfg`` carries the paper's hyper-parameters (ρ, λ, step-size a/b);
    ``None`` uses the paper defaults sized to the problem's grid.
    ``callbacks`` fire at fit start, every eval boundary, and fit end.
    """

    def __init__(self, cfg: GossipMCConfig | None = None,
                 callbacks: Sequence[Callback] = ()):
        self.cfg = cfg
        self.callbacks = list(callbacks)

    def _config_for(self, problem: CompletionProblem) -> GossipMCConfig:
        if self.cfg is not None:
            return self.cfg
        spec = problem.spec
        return GossipMCConfig(m=spec.m, n=spec.n, p=spec.p, q=spec.q,
                              rank=spec.r)

    def fit(
        self,
        problem: CompletionProblem,
        schedule: Union[str, Schedule] = "wave",
        *,
        seed: int = 0,
        key: jax.Array | None = None,
        state: State | None = None,
        resume_from: Union[Checkpoint, CheckpointManager, str, None] = None,
        recovery=None,
        **schedule_overrides,
    ) -> FitResult:
        """Run the schedule to completion and return a :class:`FitResult`.

        ``schedule`` is a ``Schedule`` instance or a name ("sequential",
        "wave", "full", "gossip"); keyword overrides (e.g.
        ``num_rounds=500``) are applied either way.  ``resume_from``
        restarts from the latest session checkpoint written by the
        :class:`Checkpoint` callback (state + PRNG key + progress unit),
        replaying the exact stream of the uninterrupted run — including
        the per-round minibatch stream of a stochastic
        ``Gossip(batch=...)`` fit, whose ``MinibatchStream`` base is a
        pure function of the saved key and whose per-round sample is
        keyed on the absolute round (bit-identical resume, pinned by
        test).

        ``recovery=RecoveryPolicy(...)`` makes the fit self-healing
        (DESIGN.md §13): a ``DivergenceGuard`` watches every eval
        boundary (one is prepended if the callbacks don't carry one —
        guards always run *before* ``Checkpoint`` so a poisoned state is
        never persisted), and on divergence the fit restores the latest
        valid checkpoint, re-folds the PRNG key, decays the step size by
        ``policy.backoff`` per restart, clears one-shot injected faults,
        and resumes.  Restarts land in ``FitResult.recovery_log`` and the
        ``fit_recoveries_total`` counter; exhausting ``max_restarts``
        (or ``on_divergence="raise"``) re-raises the
        ``DivergenceError``."""

        if not isinstance(problem, CompletionProblem):
            raise TypeError(
                f"Trainer.fit expects a CompletionProblem, got "
                f"{type(problem).__name__}; build one with "
                "CompletionProblem.from_dense/from_entries/from_dataset"
            )
        sched = make_schedule(schedule, **schedule_overrides)
        cfg = self._config_for(problem)
        obs.counter("grad_engine_fits_total",
                    path=sched.grad_path(problem)).inc()
        if key is None:
            key = jax.random.PRNGKey(seed)

        mgr = resume_from
        if isinstance(mgr, Checkpoint):
            mgr = mgr.manager
        if isinstance(mgr, str):
            mgr = CheckpointManager(mgr)
        done = 0
        if mgr is not None:
            restored = restore_session(mgr, problem)
            if restored is not None:
                done, state, key = restored

        if recovery is None:
            return self._run_attempt(problem, sched, cfg, key, state, done,
                                     self.callbacks)
        return self._run_recovering(problem, sched, cfg, key, state, done,
                                    mgr, recovery)

    def _run_attempt(self, problem, sched, cfg, key, state, done,
                     callbacks, recovery_log=None) -> FitResult:
        """One uninterrupted schedule run (the body every fit shares)."""

        for cb in callbacks:
            cb.on_fit_start(problem, sched, cfg)

        def eval_cb(unit, cost, st, k):
            for cb in callbacks:
                cb.on_eval(unit, cost, st, k)

        # the span is the fit's outermost timer: device-true (syncs the
        # final factors before the clock stops) and TraceAnnotation-named,
        # so a Perfetto capture (obs.trace) shows one slice per fit
        t0 = time.perf_counter()
        with obs.span(f"fit.{sched.name}", annotate=True) as sp:
            state, history = sp.outputs(sched.run(
                problem, cfg, key, state=state, done=done,
                eval_cb=eval_cb if callbacks else None,
            ))
        result = FitResult(
            state=state, history=history,
            wall_time=time.perf_counter() - t0,
            schedule=sched.name, problem=problem,
            recovery_log=recovery_log if recovery_log is not None else [],
        )
        for cb in callbacks:
            cb.on_fit_end(result)
        return result

    def _run_recovering(self, problem, sched, cfg, key, state, done,
                        mgr, recovery) -> FitResult:
        """The self-healing loop around :meth:`_run_attempt`."""

        from repro.faults import DivergenceError, DivergenceGuard

        if mgr is None:
            for cb in self.callbacks:
                if isinstance(cb, Checkpoint):
                    mgr = cb.manager
                    break
        if mgr is None and recovery.on_divergence == "restore":
            raise ValueError(
                "recovery with on_divergence='restore' needs a checkpoint "
                "to restore from: add a Checkpoint callback to the Trainer "
                "or pass resume_from="
            )
        # guards before everything else — in particular before Checkpoint,
        # so a diverged state is never persisted as a restore point
        guards = [cb for cb in self.callbacks
                  if isinstance(cb, DivergenceGuard)]
        others = [cb for cb in self.callbacks
                  if not isinstance(cb, DivergenceGuard)]
        if not guards:
            guards = [DivergenceGuard()]
        callbacks = guards + others

        recovery_log: list = []
        restart = 0
        attempt_sched, attempt_cfg = sched, cfg
        while True:
            try:
                return self._run_attempt(problem, attempt_sched, attempt_cfg,
                                         key, state, done, callbacks,
                                         recovery_log=recovery_log)
            except DivergenceError as err:
                if recovery.on_divergence == "raise" \
                        or restart >= recovery.max_restarts:
                    raise
                restart += 1
                obs.counter("fit_recoveries_total").inc()
                restored = restore_session(mgr, problem) if mgr else None
                if restored is not None:
                    done, state, key = restored
                else:
                    # nothing valid on disk yet: restart the fit from
                    # scratch (still with decayed step size + folded key)
                    done, state = 0, None
                # a restarted node draws a fresh (deterministic) stream
                key = jax.random.fold_in(key, restart)
                a = cfg.a * recovery.backoff ** restart
                attempt_cfg = dataclasses.replace(cfg, a=a)
                faults = getattr(attempt_sched, "faults", None)
                if faults is not None:
                    attempt_sched = dataclasses.replace(
                        attempt_sched, faults=faults.refold(restart))
                recovery_log.append({
                    "restart": restart,
                    "unit": err.unit,
                    "cost": err.cost,
                    "reason": err.reason,
                    "resumed_from": done,
                    "step_a": a,
                })

    def refit(
        self,
        result: FitResult,
        problem: CompletionProblem | None = None,
        schedule: Union[str, Schedule, None] = None,
        *,
        seed: int = 0,
        reset_clock: bool = False,
        **schedule_overrides,
    ) -> FitResult:
        """Warm-start refresh from a finished fit — the incremental half of
        the streaming loop (DESIGN.md §11).

        Resumes from ``result``'s trained ``(U, W)`` factors against
        ``problem`` (typically ``result.problem.append(...)``'s output;
        defaults to ``result.problem``) and runs only the cheap incremental
        rounds — ``schedule`` defaults to :class:`~repro.mc.Incremental`,
        a short wave run.  The paper's iteration clock ``t`` carries over,
        so the γ_t = a/(1+bt) step size continues its decay (fine-tuning
        steps, not a restarted descent); ``reset_clock=True`` restarts the
        step-size schedule for appends that shift the data distribution
        hard.  The refreshed ``FitResult`` feeds straight into
        ``RecommendIndex.refresh`` / ``RecommendService.refresh``."""

        if problem is None:
            problem = result.problem
        if not isinstance(problem, CompletionProblem):
            raise TypeError(
                f"Trainer.refit expects a CompletionProblem, got "
                f"{type(problem).__name__}"
            )
        if problem.spec != result.problem.spec:
            raise ValueError(
                f"refit needs matching factor shapes: new problem grid "
                f"{problem.spec} != fitted grid {result.problem.spec}; a "
                f"reshaped problem needs a cold Trainer.fit"
            )
        state = result.state
        if reset_clock:
            state = state._replace(t=state.t * 0)
        if schedule is None:
            schedule = "incremental"
        return self.fit(problem, schedule, seed=seed, state=state,
                        **schedule_overrides)
