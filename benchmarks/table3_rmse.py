"""Paper Table 3: test RMSE × decomposition grid × rank.

Offline container ⇒ a seeded MovieLens-scale proxy (long-tail popularity,
user/item biases, ratings in [1,5]; DESIGN.md §9).  Pass ``--data
path.csv`` to run on a real ratings file.  Default is a reduced
1800×1200/120k-ratings proxy; ``--full`` runs the ML-1M-scale proxy
(6040×3706, 1M ratings).

Each cell is one ``CompletionProblem`` (mean-centered, grid-padded) fitted
with the deterministic ``FullGD`` schedule through ``Trainer`` — the
facade's ``mean_center=True`` replaces the hand-rolled μ bookkeeping, and
``FitResult.rmse()`` evaluates the held-out split in the centered frame.
"""

from __future__ import annotations

import time

from repro.config import GossipMCConfig
from repro.data import movielens_proxy
from repro.data.synthetic import load_movielens_csv
from repro.mc import CompletionProblem, FullGD, Trainer

GRIDS = ((2, 2), (3, 3), (4, 4), (5, 5))
RANKS = (5, 10, 15)


def run_cell(ds, p, q, rank, rounds=800):
    problem = CompletionProblem.from_dataset(ds, p, q, rank,
                                             mean_center=True)
    spec = problem.spec
    cfg = GossipMCConfig(m=spec.m, n=spec.n, p=p, q=q, rank=rank,
                         rho=1e3, lam=1e-6, a=2.0e-4, b=5.0e-7)
    res = Trainer(cfg).fit(problem, FullGD(num_rounds=rounds,
                                           eval_every=rounds), seed=0)
    return res.rmse()


def main(full: bool = False, data: str | None = None, out=print):
    if data:
        ds = load_movielens_csv(data)
        tag = "real"
    elif full:
        ds = movielens_proxy()
        tag = "ml1m_proxy"
    else:
        ds = movielens_proxy(num_users=1800, num_items=1200,
                             num_ratings=120_000)
        tag = "proxy"
    grids = GRIDS if full else GRIDS[:3]
    ranks = RANKS if full else RANKS[:2]
    for (p, q) in grids:
        for r in ranks:
            t0 = time.time()
            rmse = run_cell(ds, p, q, r)
            us = (time.time() - t0) * 1e6
            out(f"table3_{tag}_grid{p}x{q}_r{r},{us:.0f},rmse={rmse:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys

    data = None
    if "--data" in sys.argv:
        data = sys.argv[sys.argv.index("--data") + 1]
    main(full="--full" in sys.argv, data=data)
