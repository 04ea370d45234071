"""Streaming ingestion bench: append throughput + refit vs cold-fit wall.

Times the two halves of the online loop (DESIGN.md §11) on one
``CompletionProblem``:

* **append throughput** — ``CompletionProblem.append`` batches of
  streaming ratings spliced into the sorted padded-COO store (per-batch
  wall → entries/s), swept over batch sizes.  The store's capacity never
  changes, so the jitted gradient executables survive every append.
* **refit vs cold fit** — ``Trainer.refit`` warm-start (the cheap
  incremental rounds) against a same-seed cold ``Trainer.fit`` on the
  grown problem, reporting wall clock, the rounds ratio, and the held-out
  RMSE gap (the acceptance gate is ±1e-3 at < half the rounds).

    PYTHONPATH=src python benchmarks/streaming_ingest.py \
        [--m 400] [--n 400] [--grid 4 4] [--rank 5] [--density 0.3] \
        [--stream-frac 0.15] [--batches 100 1000 10000] \
        [--headroom 2048] [--rounds 600] [--refit-rounds 150] [--json PATH]
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mc import CompletionProblem, Trainer, Wave

try:                                   # package mode (python -m benchmarks.x)
    from benchmarks.run import emit_json
except ImportError:                    # script mode (python benchmarks/x.py)
    from run import emit_json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=400)
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--grid", type=int, nargs=2, default=(4, 4))
    ap.add_argument("--rank", type=int, default=5)
    ap.add_argument("--density", type=float, default=0.3)
    ap.add_argument("--stream-frac", type=float, default=0.15)
    ap.add_argument("--batches", type=int, nargs="+",
                    default=[100, 1000, 10000],
                    help="append batch sizes to sweep")
    ap.add_argument("--headroom", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=600)
    ap.add_argument("--refit-rounds", type=int, default=None,
                    help="default rounds//4")
    ap.add_argument("--json", type=str, default=None,
                    help="write results as JSON to this path")
    args = ap.parse_args()

    p, q = args.grid
    refit_rounds = args.refit_rounds or max(args.rounds // 4, 1)
    ds = lowrank_problem(args.m, args.n, args.rank, density=args.density,
                         seed=0)
    rr, cc = np.nonzero(ds.train_mask)
    vv = ds.x[rr, cc]
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(rr))
    cut = int((1.0 - args.stream_frac) * len(rr))
    base, stream = perm[:cut], perm[cut:]

    t0 = time.perf_counter()
    problem = CompletionProblem.from_entries(
        rr[base], cc[base], vv[base], (args.m, args.n), p, q, args.rank,
        headroom=args.headroom, dataset=ds,
    )
    t_ingest = time.perf_counter() - t0
    print(f"matrix {args.m}x{args.n} grid {p}x{q} rank {args.rank} "
          f"(backend={jax.default_backend()})")
    print(f"ingest: {len(base)} entries in {t_ingest * 1e3:.1f}ms, capacity "
          f"{problem.data.capacity}/block, headroom {args.headroom}")

    # -- append throughput sweep ---------------------------------------- #
    append_rows = []
    for batch in args.batches:
        take = stream[:batch] if batch <= len(stream) else stream
        # repeat the same batch against the same base store: timing only
        reps = max(3, 2000 // max(len(take), 1))
        t0 = time.perf_counter()
        for _ in range(reps):
            appended = problem.append(rr[take], cc[take], vv[take])
        dt = (time.perf_counter() - t0) / reps
        append_rows.append({
            "batch": int(len(take)),
            "append_ms": dt * 1e3,
            "entries_per_s": len(take) / max(dt, 1e-12),
        })

    print(f"\nappend throughput ({len(stream)} streamed entries held back):")
    print(f"{'batch':>8} {'ms':>9} {'entries/s':>12}")
    for row in append_rows:
        print(f"{row['batch']:8d} {row['append_ms']:9.2f} "
              f"{row['entries_per_s']:12,.0f}")

    # -- refit vs cold fit ---------------------------------------------- #
    cfg = GossipMCConfig(m=problem.spec.m, n=problem.spec.n, p=p, q=q,
                         rank=args.rank, a=1e-3, b=1e-5, rho=1e2)
    trainer = Trainer(cfg)
    t0 = time.perf_counter()
    result = trainer.fit(problem, Wave(num_rounds=args.rounds), seed=0)
    t_fit0 = time.perf_counter() - t0

    fresh = problem.append(rr[stream], cc[stream], vv[stream])
    t0 = time.perf_counter()
    refit = trainer.refit(result, fresh, num_rounds=refit_rounds)
    t_refit = time.perf_counter() - t0
    t0 = time.perf_counter()
    cold = trainer.fit(fresh, Wave(num_rounds=args.rounds), seed=0)
    t_cold = time.perf_counter() - t0
    rmse_refit, rmse_cold = refit.rmse(), cold.rmse()

    print(f"\nrefit vs cold fit after appending {len(stream)} entries:")
    print(f"{'':>12} {'rounds':>7} {'wall_s':>8} {'rmse':>9}")
    print(f"{'initial fit':>12} {args.rounds:7d} {t_fit0:8.1f} "
          f"{result.rmse():9.4f}")
    print(f"{'warm refit':>12} {refit_rounds:7d} {t_refit:8.1f} "
          f"{rmse_refit:9.4f}")
    print(f"{'cold fit':>12} {args.rounds:7d} {t_cold:8.1f} "
          f"{rmse_cold:9.4f}")
    print(f"refit speedup {t_cold / max(t_refit, 1e-9):.1f}x wall at "
          f"{refit_rounds}/{args.rounds} rounds, rmse gap "
          f"{rmse_refit - rmse_cold:+.2e}")

    if args.json:
        emit_json(args.json, "streaming_ingest",
                  {"m": args.m, "n": args.n, "p": p, "q": q,
                   "rank": args.rank, "density": args.density,
                   "stream_frac": args.stream_frac,
                   "headroom": args.headroom, "rounds": args.rounds,
                   "refit_rounds": refit_rounds},
                  ingest_ms=t_ingest * 1e3,
                  append=append_rows,
                  refit={
                      "initial_fit_s": t_fit0,
                      "refit_s": t_refit,
                      "cold_fit_s": t_cold,
                      "refit_wall_speedup": t_cold / max(t_refit, 1e-9),
                      "rmse_refit": float(rmse_refit),
                      "rmse_cold": float(rmse_cold),
                      "rmse_gap": float(rmse_refit - rmse_cold),
                  })


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
