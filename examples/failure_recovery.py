"""Fault-tolerance demo: kill a gossip-MC fit mid-run, restart, verify
exactness — all through the unified session API (repro.mc).

Phase 1 fits uninterrupted.  Phase 2 runs the same fit but "crashes"
mid-run (simulated by a callback raising after a checkpoint boundary —
all live state lost), then resumes from the latest checkpoint with
``Trainer.fit(resume_from=...)``.  The ``Checkpoint`` callback persists
(factors, t, PRNG key, progress unit), so the resumed run replays the
identical key stream and the two final states agree **bit-for-bit**
(asserted).

Phase 3 flips failure handling from manual to automatic: a fit with a
deliberately hot step size diverges to NaN, and
``Trainer.fit(recovery=RecoveryPolicy(...))`` self-heals — the
``DivergenceGuard`` fires at the eval boundary, the trainer restarts
with a decayed step size, and the restart is audited in
``FitResult.recovery_log`` (DESIGN.md §13, docs/robustness.md).

    PYTHONPATH=src python examples/failure_recovery.py
"""

import shutil
import tempfile

import numpy as np

from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mc import (Callback, Checkpoint, CompletionProblem,
                      RecoveryPolicy, Trainer, Wave)

ROUNDS, EVAL_EVERY, CRASH_AT = 12, 2, 7


class SimulatedCrash(RuntimeError):
    pass


class CrashAt(Callback):
    """Raises once the fit passes the given round — a node failure."""

    def __init__(self, unit: int):
        self.unit = unit

    def on_eval(self, unit, cost, state, key):
        if unit >= self.unit:
            print(f"  💥 simulated node failure after round {unit} "
                  "(all live state lost)")
            raise SimulatedCrash()


def main():
    cfg = GossipMCConfig(m=160, n=128, p=4, q=4, rank=4)
    ds = lowrank_problem(cfg.m, cfg.n, cfg.rank, density=0.3, seed=0)
    problem = CompletionProblem.from_dataset(ds, cfg.p, cfg.q, cfg.rank,
                                             layout="sparse")
    schedule = Wave(num_rounds=ROUNDS, eval_every=EVAL_EVERY)

    # phase 1: uninterrupted
    ref = Trainer(cfg).fit(problem, schedule, seed=0)
    print(f"uninterrupted final cost: {ref.final_cost:.6e}")

    # phase 2: crash + restart from the latest checkpoint
    ckpt_dir = tempfile.mkdtemp(prefix="repro_ft_")
    ck = Checkpoint(ckpt_dir)
    try:
        # crash callback fires before the checkpoint one: the failing round
        # is lost, recovery recomputes it from the previous boundary
        Trainer(cfg, callbacks=[CrashAt(CRASH_AT), ck]).fit(
            problem, schedule, seed=0)
        raise AssertionError("crash did not fire")
    except SimulatedCrash:
        pass
    unit, _, _ = ck.restore(problem)
    print(f"  ↻ restarted from checkpoint at round {unit}")
    rec = Trainer(cfg, callbacks=[ck]).fit(problem, schedule, seed=0,
                                           resume_from=ck)
    print(f"recovered final cost:     {rec.final_cost:.6e}")

    np.testing.assert_array_equal(np.asarray(rec.state.U),
                                  np.asarray(ref.state.U))
    np.testing.assert_array_equal(np.asarray(rec.state.W),
                                  np.asarray(ref.state.W))
    assert rec.t == ref.t
    print("✓ restart is exact (state matches the uninterrupted run "
          "bit-for-bit)")
    shutil.rmtree(ckpt_dir)

    # phase 3: divergence self-heals instead of killing the run
    hot = GossipMCConfig(m=24, n=20, rank=2, p=2, q=2, a=2e-3)
    small = lowrank_problem(hot.m, hot.n, hot.rank, density=0.6, seed=1)
    prob = CompletionProblem.from_dataset(small, hot.p, hot.q, hot.rank)
    heal_dir = tempfile.mkdtemp(prefix="repro_heal_")
    res = Trainer(hot, callbacks=[Checkpoint(heal_dir)]).fit(
        prob, "wave", num_rounds=20, eval_every=5,
        recovery=RecoveryPolicy(max_restarts=3, backoff=0.25))
    entry = res.recovery_log[0]
    print(f"  🩹 diverged at round {entry['unit']} ({entry['reason']}); "
          f"restarted with a={entry['step_a']:g}")
    assert np.isfinite(res.final_cost)
    print(f"✓ self-healed final cost:  {res.final_cost:.6e} "
          f"({len(res.recovery_log)} restart)")
    shutil.rmtree(heal_dir)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
