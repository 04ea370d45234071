"""Paper Table 2: synthetic convergence of Exp#1–#6.

Reproduces the cost-vs-iterations table (cost = Σ f_ij + λ‖U‖² + λ‖W‖²)
through the unified session API: one ``CompletionProblem`` per experiment,
one ``Trainer`` warm-started across the paper's iteration checkpoints with
the deterministic ``FullGD`` schedule (same objective, same γ_t decay per
structure update as the sequential algorithm).  The paper runs 240k–400k
sequential Algorithm-1 iterations; Exp#5/#6 (5000²/10000²) run reduced
horizons by default; ``--full`` matches the paper's.
"""

from __future__ import annotations

import time

import jax

from repro.configs.gossip_mc import EXPERIMENTS
from repro.core.state import init_state
from repro.data import lowrank_problem
from repro.mc import CompletionProblem, FullGD, Trainer

CHECKPOINTS = (80_000, 160_000, 240_000, 280_000, 400_000)


def run_experiment(name: str, full: bool = False):
    cfg = EXPERIMENTS[name]
    checkpoints = CHECKPOINTS
    if not full and cfg.m >= 5000:
        checkpoints = (10_000, 20_000)
    ds = lowrank_problem(cfg.m, cfg.n, cfg.rank, density=cfg.density, seed=1)
    problem = CompletionProblem.from_dataset(ds, cfg.p, cfg.q, cfg.rank)
    n_struct = problem.spec.num_structures

    trainer = Trainer(cfg)
    state = init_state(jax.random.PRNGKey(cfg.seed), problem.spec)
    rows = [(0, problem.total_cost(state, cfg.lam))]
    t0 = time.time()
    for target_t in checkpoints:
        rounds = max(1, (target_t - int(state.t)) // n_struct)
        res = trainer.fit(problem, FullGD(num_rounds=rounds,
                                          eval_every=rounds), state=state)
        state = res.state
        rows.append((res.t, res.final_cost))
    return rows, time.time() - t0


def main(full: bool = False, out=print):
    names = list(EXPERIMENTS)
    if not full:
        names = [n for n in names if EXPERIMENTS[n].m < 10000]
    for name in names:
        rows, wall = run_experiment(name, full)
        per_iter_us = wall * 1e6 / max(rows[-1][0], 1)
        traj = ";".join(f"t{t}={c:.3e}" for t, c in rows)
        out(f"table2_{name},{per_iter_us:.3f},{traj}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys

    main(full="--full" in sys.argv)
