"""Quickstart: the paper's algorithm end-to-end in ~30s on CPU.

One problem, one trainer, pluggable schedules (the unified session API,
DESIGN.md §4): decompose a synthetic low-rank matrix into a gossip grid,
fit with any execution strategy, and report held-out completion RMSE.

    PYTHONPATH=src python examples/quickstart.py \
        [--mode sequential|wave|full|gossip] [--layout dense|sparse] \
        [--m 400] [--n 400] [--grid 4 4] [--rank 5] \
        [--rounds 2500] [--iters 40000]
"""

import argparse

from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mc import (CompletionProblem, EvalRMSE, Sequential, Trainer,
                      make_schedule)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="wave",
                    choices=["sequential", "wave", "full", "gossip"])
    ap.add_argument("--m", type=int, default=400)
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--grid", type=int, nargs=2, default=(4, 4))
    ap.add_argument("--rank", type=int, default=5)
    ap.add_argument("--layout", default="dense", choices=["dense", "sparse"],
                    help="sparse runs the f-terms on the padded-COO store "
                         "(nnz-proportional compute)")
    ap.add_argument("--rounds", type=int, default=2_500,
                    help="rounds for wave/full/gossip modes")
    ap.add_argument("--iters", type=int, default=40_000,
                    help="iterations for sequential mode")
    args = ap.parse_args()

    p, q = args.grid
    cfg = GossipMCConfig(m=args.m, n=args.n, p=p, q=q, rank=args.rank)
    ds = lowrank_problem(args.m, args.n, args.rank, density=0.3, seed=0)
    problem = CompletionProblem.from_dataset(ds, p, q, args.rank,
                                             layout=args.layout)
    print(f"matrix {args.m}x{args.n} rank {args.rank} -> grid {p}x{q} "
          f"({problem.spec.num_structures} gossip structures), "
          f"mode={args.mode}, layout={problem.layout}")

    if args.mode == "sequential":
        schedule = Sequential(num_iters=args.iters,
                              eval_every=max(args.iters // 5, 1))
    else:
        schedule = make_schedule(args.mode, num_rounds=args.rounds,
                                 eval_every=max(args.rounds // 5, 1))

    trainer = Trainer(cfg, callbacks=[EvalRMSE(log=print)])
    result = trainer.fit(problem, schedule, seed=0)

    du, dw = result.consensus_error()
    print(f"consensus error: U {du:.2e}  W {dw:.2e}  "
          f"({result.wall_time:.1f}s wall)")
    print(f"held-out completion RMSE: {result.rmse():.4f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
