"""Online recommending end-to-end: ingest → fit → serve → **stream new
ratings** → incremental refit → hot-swap the index → re-serve.

The streaming loop (DESIGN.md §11) on a quickstart-sized problem:

1. Ingest an initial ratings log with ``CompletionProblem.from_entries``
   and ``headroom=`` append slack pre-allocated per block.
2. Cold ``Trainer.fit`` + ``FitResult.to_service()`` — the serving path.
3. A batch of new ratings arrives: ``problem.append(rows, cols, vals)``
   splices them into the sorted store in place (no re-sort, no new
   compile).
4. ``Trainer.refit`` warm-starts from the trained factors and runs only
   the cheap incremental rounds; ``RecommendService.refresh`` hot-swaps
   the index (new factors + updated seen-item table).

Asserts the two acceptance properties: the appended ratings change the
served top-k (and are themselves excluded as seen), and the refit reaches
the cold-fit RMSE (±1e-3) in **less than half** the cold-fit rounds.

    PYTHONPATH=src python examples/online_serving.py \
        [--m 400] [--n 400] [--grid 4 4] [--rank 5] \
        [--rounds 600] [--refit-rounds 150] [--headroom 2048] [--k 10]
"""

import argparse
import time

import numpy as np

from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mc import CompletionProblem, Trainer, Wave


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=400)
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--grid", type=int, nargs=2, default=(4, 4))
    ap.add_argument("--rank", type=int, default=5)
    ap.add_argument("--density", type=float, default=0.3)
    ap.add_argument("--stream-frac", type=float, default=0.15,
                    help="fraction of the ratings log held back to arrive "
                         "as the streaming append")
    ap.add_argument("--rounds", type=int, default=600,
                    help="cold-fit wave rounds")
    ap.add_argument("--refit-rounds", type=int, default=None,
                    help="incremental refit rounds (default rounds//4)")
    ap.add_argument("--headroom", type=int, default=2048,
                    help="per-block append slack pre-allocated at ingest")
    ap.add_argument("--k", type=int, default=10)
    args = ap.parse_args()

    p, q = args.grid
    refit_rounds = args.refit_rounds or max(args.rounds // 4, 1)
    assert 2 * refit_rounds < args.rounds, "refit must cost < half the cold fit"

    # -- the ratings log: an initial batch + a held-back stream ---------- #
    ds = lowrank_problem(args.m, args.n, args.rank, density=args.density,
                         seed=0)
    rr, cc = np.nonzero(ds.train_mask)
    vv = ds.x[rr, cc]
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(rr))
    cut = int((1.0 - args.stream_frac) * len(rr))
    base, stream = perm[:cut], perm[cut:]
    print(f"ratings log: {len(base)} initial + {len(stream)} streaming "
          f"({args.m}x{args.n}, rank {args.rank}, grid {p}x{q})")

    problem = CompletionProblem.from_entries(
        rr[base], cc[base], vv[base], (args.m, args.n), p, q, args.rank,
        headroom=args.headroom, dataset=ds,
    )
    print(f"store: capacity {problem.data.capacity}/block, min free slots "
          f"{int(np.asarray(problem.data.free_slots).min())}")
    cfg = GossipMCConfig(m=problem.spec.m, n=problem.spec.n, p=p, q=q,
                         rank=args.rank, a=1e-3, b=1e-5, rho=1e2)
    trainer = Trainer(cfg)

    # -- cold fit + serve ------------------------------------------------ #
    t0 = time.perf_counter()
    result = trainer.fit(problem, Wave(num_rounds=args.rounds), seed=0)
    t_fit = time.perf_counter() - t0
    print(f"cold fit:  {args.rounds} rounds, rmse {result.rmse():.4f} "
          f"({t_fit:.1f}s)")
    svc = result.to_service(k=args.k)
    users = np.unique(rr[stream])[:64].astype(np.int32)
    before = svc.recommend(users)[0].copy()

    # -- stream arrives: append + incremental refit + hot swap ---------- #
    t0 = time.perf_counter()
    fresh = problem.append(rr[stream], cc[stream], vv[stream])
    t_append = time.perf_counter() - t0
    print(f"append:    {len(stream)} entries spliced in {t_append * 1e3:.1f}ms "
          f"({len(stream) / max(t_append, 1e-9):,.0f} entries/s), "
          f"min free slots {int(np.asarray(fresh.data.free_slots).min())}")
    t0 = time.perf_counter()
    refit = trainer.refit(result, fresh, num_rounds=refit_rounds)
    t_refit = time.perf_counter() - t0
    print(f"refit:     {refit_rounds} rounds warm-start, rmse "
          f"{refit.rmse():.4f} ({t_refit:.1f}s)")
    svc.refresh(refit)
    after = svc.recommend(users)[0]

    # -- the appended ratings changed what we serve ---------------------- #
    assert (before != after).any(), "append + refit left the top-k unchanged"
    served = {u: set(row.tolist()) for u, row in zip(users, after)}
    leaked = sum(int(c) in served[int(u)]
                 for u, c in zip(rr[stream], cc[stream]) if int(u) in served)
    assert leaked == 0, f"{leaked} just-appended items were recommended back"
    print(f"serve:     top-{args.k} changed for "
          f"{int((before != after).any(axis=1).sum())}/{len(users)} streamed "
          f"users; 0 appended items leaked back")

    # -- refit quality: cold-fit RMSE at < half the rounds --------------- #
    t0 = time.perf_counter()
    cold = trainer.fit(fresh, Wave(num_rounds=args.rounds), seed=0)
    t_cold = time.perf_counter() - t0
    gap = refit.rmse() - cold.rmse()
    print(f"cold refit baseline: {args.rounds} rounds, rmse "
          f"{cold.rmse():.4f} ({t_cold:.1f}s)")
    assert gap <= 1e-3, (
        f"refit rmse {refit.rmse():.5f} vs cold {cold.rmse():.5f}: "
        f"gap {gap:.2e} > 1e-3"
    )
    print(f"✓ refit matches cold-fit rmse (gap {gap:+.2e} ≤ 1e-3) in "
          f"{refit_rounds}/{args.rounds} rounds "
          f"({t_refit:.1f}s vs {t_cold:.1f}s wall)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
