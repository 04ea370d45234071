"""Top-k recommendation serving throughput bench.

Measures batched masked top-k throughput (users/s, item-scores/s, per-batch
latency) through the production front end — ``RecommendService`` — on a
MovieLens-scale serving index, so the numbers include exactly what a
deployment pays (fixed-batch chunking, host round-trip) and the service's
own telemetry (``serve_batch_seconds`` p50/p99, QPS via
``service.metrics()``) lands in the ``--json`` output.  Index sources:

* default: random factors at the requested shape — serving cost does not
  depend on factor values, so this isolates pure serving throughput;
* ``--from-fit``: the full session-API path — train a MovieLens proxy with
  ``Trainer.fit`` and bridge into serving via
  ``FitResult.to_recommend_index()`` (shapes then come from the proxy);
* ``--sharded``: shard the item axis over every available device
  (``MeshPlan.for_devices`` + two-stage top-k) — run under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU to
  exercise the multi-device path (the CI multidevice-smoke job does).

    PYTHONPATH=src python benchmarks/serve_recommend.py \
        [--users 6040] [--items 3706] [--rank 16] [--batch 256] [--k 10] \
        [--iters 50] [--density 0.02] [--from-fit] [--rounds 30] \
        [--sharded] [--json PATH]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.mesh import MeshPlan
from repro.serve.recommend import (RecommendIndex, RecommendService,
                                   build_seen_table)

try:                                   # package mode (python -m benchmarks.x)
    from benchmarks.run import emit_json
except ImportError:                    # script mode (python benchmarks/x.py)
    from run import emit_json


def _random_index(args) -> RecommendIndex:
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(args.users, args.rank)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(args.items, args.rank)), jnp.float32)
    mask = (rng.random((args.users, args.items)) < args.density)
    seen = jnp.asarray(build_seen_table(mask.astype(np.float32), args.items))
    return RecommendIndex(u, w, seen)


def _fitted_index(args) -> RecommendIndex:
    from repro.config import GossipMCConfig
    from repro.data import movielens_proxy
    from repro.mc import CompletionProblem, Trainer, Wave

    nratings = int(args.users * args.items * args.density)
    ds = movielens_proxy(num_users=args.users, num_items=args.items,
                         num_ratings=nratings, seed=0)
    p = q = 4
    problem = CompletionProblem.from_dataset(ds, p, q, args.rank,
                                             layout="sparse",
                                             mean_center=True)
    spec = problem.spec
    cfg = GossipMCConfig(m=spec.m, n=spec.n, p=p, q=q, rank=args.rank,
                         rho=1e3, lam=1e-6, a=2.0e-4, b=5.0e-7)
    res = Trainer(cfg).fit(problem, Wave(num_rounds=args.rounds), seed=0)
    print(f"trained {args.rounds} wave rounds: cost={res.final_cost:.3e} "
          f"rmse={res.rmse():.4f} ({res.wall_time:.1f}s)")
    return res.to_recommend_index()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=6040)
    ap.add_argument("--items", type=int, default=3706)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--density", type=float, default=0.02,
                    help="seen-item density for the exclusion table")
    ap.add_argument("--from-fit", action="store_true",
                    help="build the index by training a MovieLens proxy "
                         "through Trainer.fit + to_recommend_index()")
    ap.add_argument("--rounds", type=int, default=30,
                    help="wave rounds for --from-fit")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the item axis over all devices "
                         "(MeshPlan.for_devices + two-stage top-k)")
    ap.add_argument("--json", type=str, default=None,
                    help="write results as JSON to this path")
    args = ap.parse_args()

    index = _fitted_index(args) if args.from_fit else _random_index(args)
    num_users, num_items = index.u.shape[0], index.w.shape[0]
    seen_width = int(index.seen.shape[1])

    plan = MeshPlan.for_devices() if args.sharded else None
    service = RecommendService(index, batch=args.batch, k=args.k, plan=plan)
    shards = service.num_item_shards

    rng = np.random.default_rng(1)
    user_batches = [
        rng.integers(0, num_users, args.batch).astype(np.int32)
        for _ in range(args.iters)
    ]
    # warmup/compile outside the measured window, then drop its telemetry
    # so the reported p50/p99 are steady-state batches only
    service.recommend(user_batches[0])
    obs.reset()
    service.reset_metrics()

    t0 = time.perf_counter()
    for ub in user_batches:
        items, scores = service.recommend(ub)
    dt = time.perf_counter() - t0       # recommend() already synced

    total_users = args.batch * args.iters
    per_batch_ms = dt / args.iters * 1e3
    serving = service.metrics()
    print(f"index: {num_users} users x {num_items} items, rank {args.rank}, "
          f"seen table width {seen_width}, {shards} item shard(s) "
          f"(backend={jax.default_backend()})")
    print(f"batch={args.batch} k={args.k}: {per_batch_ms:.2f} ms/batch, "
          f"{total_users / dt:,.0f} users/s, "
          f"{total_users * num_items / dt / 1e6:,.0f}M scores/s")
    lat = serving["latency"]
    if lat["count"]:
        print(f"service: p50={lat['p50'] * 1e3:.2f}ms "
              f"p99={lat['p99'] * 1e3:.2f}ms over {lat['count']} batches, "
              f"{serving['qps']:.1f} req/s")

    if args.json:
        emit_json(args.json, "serve_recommend",
                  {"users": num_users, "items": num_items,
                   "rank": args.rank, "batch": args.batch, "k": args.k,
                   "iters": args.iters, "density": args.density,
                   "from_fit": bool(args.from_fit),
                   "sharded": bool(args.sharded),
                   "item_shards": shards},
                  per_batch_ms=per_batch_ms,
                  users_per_s=total_users / dt,
                  scores_per_s=total_users * num_items / dt,
                  serving=serving)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
