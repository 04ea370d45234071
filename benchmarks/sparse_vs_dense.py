"""Sparse vs dense objective bench: nnz-proportional speedup at low density.

Times the Table-2 objective and the full ∇L evaluation on the same
``CompletionProblem``, sweeping density, in three engine configurations:
dense masked tensors, the segment-sorted sparse store (streaming CSR/CSC
reductions, the default), and the unsorted scatter-add reference — all
selected through ``EngineOptions`` (``problem.with_engine(...)`` /
``with_layout(...)``), never through divergent entry points.  The dense
path reads O(m·n) values+masks per evaluation regardless of sparsity; the
sparse paths read O(nnz).  On CPU the objective (pure gather + dot) wins by
~1/density; the *sorted* gradient replaces XLA's serialized scatter-add
with contiguous segment reductions, which moves the gradient crossover from
~2–3% density past 5% (DESIGN.md §3 has the measured table).

``--chunks`` additionally sweeps the segment-reduce chunk size (the
``EngineOptions.chunk`` knob, ROADMAP autotune follow-on) and the JSON
output records the per-chunk timings + the fastest choice per density.

    PYTHONPATH=src python benchmarks/sparse_vs_dense.py \
        [--m 2048] [--n 2048] [--grid 4 4] [--rank 8] \
        [--densities 0.01 0.02 0.05] [--iters 10] \
        [--chunks 16 32 64] [--json PATH]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.config import GossipMCConfig
from repro.core.state import init_state
from repro.data import lowrank_problem
from repro.mc import CompletionProblem

try:                                   # package mode (python -m benchmarks.x)
    from benchmarks.run import emit_json
except ImportError:                    # script mode (python benchmarks/x.py)
    from run import emit_json


def _sync(out):
    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def _time(fn, iters=10):
    _sync(fn())                                            # compile + warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    _sync(out)
    return (time.perf_counter() - t0) / iters * 1e3        # ms


def _maxdiff(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(a, b))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=2048)
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--grid", type=int, nargs=2, default=(4, 4))
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--densities", type=float, nargs="+",
                    default=[0.01, 0.02, 0.05])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--chunks", type=int, nargs="+", default=[16, 32, 64],
                    help="segment-reduce chunk sizes to sweep "
                         "(EngineOptions.chunk)")
    ap.add_argument("--json", type=str, default=None,
                    help="write results as JSON to this path")
    args = ap.parse_args()

    p, q = args.grid
    cfg = GossipMCConfig(m=args.m, n=args.n, p=p, q=q, rank=args.rank)
    rho, lam = cfg.rho, cfg.lam

    print(f"matrix {cfg.m}x{cfg.n} grid {p}x{q} rank {cfg.rank} "
          f"({args.iters} iters, backend={jax.default_backend()})")
    rows = []
    st = None
    for d in args.densities:
        ds = lowrank_problem(cfg.m, cfg.n, cfg.rank, density=d, seed=0)
        dense = CompletionProblem.from_dataset(ds, p, q, args.rank,
                                               layout="dense")
        sorted_ = dense.with_layout("sparse")              # segment method
        scatter = sorted_.with_engine(method="scatter")
        if st is None:
            st = init_state(jax.random.PRNGKey(0), dense.spec)
        nnz = int(jnp.sum(sorted_.data.nnz))

        grad = lambda pr: (lambda: pr.full_gradients(st, rho=rho, lam=lam))
        cost = lambda pr: (lambda: pr.total_cost_device(st, lam))
        tc_d = _time(cost(dense), iters=args.iters)
        tc_s = _time(cost(sorted_), iters=args.iters)
        tg_d = _time(grad(dense), iters=args.iters)
        tg_s = _time(grad(sorted_), iters=args.iters)
        tg_u = _time(grad(scatter), iters=args.iters)
        gd = dense.full_gradients(st, rho=rho, lam=lam)
        gs = sorted_.full_gradients(st, rho=rho, lam=lam)
        gu = scatter.full_gradients(st, rho=rho, lam=lam)

        sweep = {
            c: _time(grad(sorted_.with_engine(chunk=c)), iters=args.iters)
            for c in args.chunks
        }
        best_chunk = min(sweep, key=sweep.get)

        rows.append({
            "density": d,
            "nnz": nnz,
            "cost_dense_ms": tc_d,
            "cost_sparse_ms": tc_s,
            "grad_dense_ms": tg_d,
            "grad_sorted_ms": tg_s,
            "grad_scatter_ms": tg_u,
            "grad_sorted_speedup": tg_d / tg_s,
            "grad_scatter_speedup": tg_d / tg_u,
            "maxdiff_sorted_vs_dense": _maxdiff(gs, gd),
            "maxdiff_scatter_vs_dense": _maxdiff(gu, gd),
            "chunk_sweep_ms": {str(c): ms for c, ms in sweep.items()},
            "chunk_best": best_chunk,
        })

    print("\nobjective (Table-2 cost):")
    print(f"{'density':>8} {'nnz':>10} {'dense_ms':>9} {'sparse_ms':>10} {'speedup':>8}")
    for r in rows:
        print(f"{r['density']:8.3f} {r['nnz']:10d} {r['cost_dense_ms']:9.2f} "
              f"{r['cost_sparse_ms']:10.2f} "
              f"{r['cost_dense_ms'] / r['cost_sparse_ms']:7.1f}x")

    print("\nfull gradient (∇L): sorted segment-reduce vs unsorted scatter vs dense")
    print(f"{'density':>8} {'nnz':>10} {'dense_ms':>9} {'sorted_ms':>10} "
          f"{'scatter_ms':>11} {'sorted_x':>9} {'scatter_x':>10} {'maxdiff':>10}")
    for r in rows:
        print(f"{r['density']:8.3f} {r['nnz']:10d} {r['grad_dense_ms']:9.2f} "
              f"{r['grad_sorted_ms']:10.2f} {r['grad_scatter_ms']:11.2f} "
              f"{r['grad_sorted_speedup']:8.1f}x {r['grad_scatter_speedup']:9.1f}x "
              f"{r['maxdiff_sorted_vs_dense']:10.2e}")

    print("\nsegment-reduce chunk sweep (sorted ∇L, ms):")
    hdr = " ".join(f"c={c:<4d}" for c in args.chunks)
    print(f"{'density':>8}  {hdr}  best")
    for r in rows:
        cells = " ".join(f"{r['chunk_sweep_ms'][str(c)]:6.2f}"
                         for c in args.chunks)
        print(f"{r['density']:8.3f}  {cells}  c={r['chunk_best']}")

    if args.json:
        emit_json(args.json, "sparse_vs_dense",
                  {"m": cfg.m, "n": cfg.n, "p": p, "q": q,
                   "rank": cfg.rank, "iters": args.iters,
                   "chunks": args.chunks},
                  rows=rows)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
