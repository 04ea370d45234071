"""Chaos bench: gossip convergence under deterministic fault injection.

Sweeps drop probability × staleness bound on the forced-host device grid
(CI runs it under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)
and records, per cell, the held-out RMSE, final cost, and the fault
counters the fit streamed into ``repro.obs`` — plus two proof columns:

* ``p0_bit_identical``: the ``p_drop=0`` fault-path fit is bit-identical
  to the fault-free (``faults=None``) fit — the fault machinery costs
  nothing when nothing fails.
* ``rmse_vs_clean``: RMSE ratio against the fault-free fit at equal
  rounds — graceful degradation, not a cliff (the chaos suite asserts
  the 2× bound at ``p_drop=0.2``).

Observed drop counts are cross-checked against ``FaultPlan.replay`` (the
same pure function the jitted step evaluates) — injected == observed, by
construction, or the bench fails loudly.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python benchmarks/gossip_faults.py --json BENCH_faults.json
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import obs
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.faults import FaultPlan
from repro.mc import CompletionProblem, Gossip, Trainer
from repro.mesh import MeshPlan, build_mesh

try:                                   # package mode (python -m benchmarks.x)
    from benchmarks.run import emit_json
except ImportError:                    # script mode (python benchmarks/x.py)
    from run import emit_json

FAULT_COUNTERS = ("gossip_edges_dropped_total", "gossip_stale_rounds_total",
                  "gossip_straggled_edges_total")


def _grid_plan():
    """One block per device over every available device (2×2 under the
    4-device CI forcing; 1×1 on a bare host — no edges, drops no-op)."""

    ndev = len(jax.devices())
    dr = 2 if ndev % 2 == 0 and ndev > 1 else 1
    dc = ndev // dr
    mesh = build_mesh((dr, dc), ("data", "model"))
    return MeshPlan.build(dr, dc, mesh=mesh)


def _counter_snapshot():
    snap = obs.snapshot()["counters"]
    return {k: snap.get(k, 0.0) for k in FAULT_COUNTERS}


def run_sweep(rounds: int, drops: list[float], bounds: list[int],
              p_straggle: float, seed: int = 0):
    plan = _grid_plan()
    p, q = plan.p, plan.q
    m = n = 32 * max(p, q, 2)
    ds = lowrank_problem(m, n, r=4, density=0.3, seed=seed)
    problem = CompletionProblem.from_dataset(ds, p, q, rank=4,
                                             layout="sparse", mesh=plan)
    cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=4)

    def fit(faults, max_staleness=3):
        return Trainer(cfg).fit(
            problem, Gossip(num_rounds=rounds, plan=plan, faults=faults,
                            max_staleness=max_staleness), seed=seed)

    clean = fit(None)
    clean_rmse = clean.rmse()

    rows = []
    p0_bit_identical = None
    for pd in drops:
        for bound in bounds:
            fp = FaultPlan(key=seed, p_drop_edge=pd, p_straggle=p_straggle)
            before = _counter_snapshot()
            res = fit(fp, max_staleness=bound)
            after = _counter_snapshot()
            counters = {k: after[k] - before[k] for k in FAULT_COUNTERS}

            if pd == 0.0 and p_straggle == 0.0 and p0_bit_identical is None:
                p0_bit_identical = bool(
                    np.array_equal(np.asarray(clean.state.U),
                                   np.asarray(res.state.U))
                    and np.array_equal(np.asarray(clean.state.W),
                                       np.asarray(res.state.W)))

            # injected == observed, from the same pure fault function the
            # jitted step evaluated
            expected = _expected_drops(fp, plan, rounds)
            got = counters["gossip_edges_dropped_total"]
            if got != expected:
                raise AssertionError(
                    f"fault replay mismatch at p_drop={pd}: observed "
                    f"{got} dropped edges, FaultPlan.replay says {expected}"
                )

            rmse = res.rmse()
            # synchronous-round critical path: a round with >=1 straggling
            # edge runs at straggler_scale; modelled, never slept
            p_round = 1.0 - (1.0 - p_straggle) ** max(plan.num_halo_edges, 1)
            rows.append({
                "p_drop": pd, "max_staleness": bound,
                "p_straggle": p_straggle, "rounds": rounds,
                "rmse": float(rmse), "final_cost": float(res.final_cost),
                "rmse_vs_clean": float(rmse / clean_rmse),
                "counters": counters,
                "expected_drops": expected,
                "sim_round_slowdown":
                    1.0 + p_round * (fp.straggler_scale - 1.0),
            })
            print(f"gossip_faults p_drop={pd} bound={bound}: "
                  f"rmse={rmse:.4f} ({rows[-1]['rmse_vs_clean']:.2f}x clean), "
                  f"dropped={counters['gossip_edges_dropped_total']:.0f}, "
                  f"stale_rounds={counters['gossip_stale_rounds_total']:.0f}")
    return {
        "grid": f"{p}x{q}", "devices": plan.num_devices, "m": m, "n": n,
        "clean_rmse": float(clean_rmse),
        "clean_final_cost": float(clean.final_cost),
        "p0_bit_identical": p0_bit_identical,
        "rows": rows,
    }


def _expected_drops(fp: FaultPlan, plan: MeshPlan, rounds: int) -> int:
    """Exact drop count from the host-side replay, masked to edges that
    exist on the plan's device grid (boundary devices have no outer
    neighbours)."""

    rp = fp.replay(rounds, plan.num_devices)
    R, C = plan.row_size, plan.col_size
    exists = np.zeros((plan.num_devices, 4), bool)
    for di in range(R):
        for dj in range(C):
            exists[di * C + dj] = (dj > 0, dj < C - 1, di > 0, di < R - 1)
    return int((rp["drops"] & exists[None]).sum())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--drops", type=str, default="0,0.05,0.1,0.2")
    ap.add_argument("--staleness-bounds", type=str, default="1,3")
    ap.add_argument("--p-straggle", type=float, default=0.0)
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args(argv)

    drops = [float(x) for x in args.drops.split(",")]
    bounds = [int(x) for x in args.staleness_bounds.split(",")]
    result = run_sweep(args.rounds, drops, bounds, args.p_straggle)
    print(f"grid {result['grid']}: clean rmse {result['clean_rmse']:.4f}, "
          f"p_drop=0 bit-identical: {result['p0_bit_identical']}")

    if args.json:
        emit_json(args.json, "gossip_faults",
                  {"rounds": args.rounds, "drops": drops,
                   "staleness_bounds": bounds,
                   "p_straggle": args.p_straggle,
                   "p_drop": max(drops)},
                  **result)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
