"""Round-time vs RMSE frontier: asynchronous stochastic gossip
(DESIGN.md §15) against synchronous full-gradient rounds at equal
wall-clock budget on the forced-host device grid.

Three arms, all on the same plan-placed sparse problem:

* ``sync_full`` — the §2 synchronous full-gradient schedule; its wall
  time is the budget every other arm must fit inside.
* ``sync_minibatch`` — stochastic rounds (``batch=``), exchange every
  round.
* ``async_minibatch`` — stochastic rounds with the non-blocking
  ``exchange_every`` clock, one arm per ``e``.

Each stochastic arm is allocated rounds from a two-point calibration
(slope = marginal round cost, intercept = per-fit fixed cost — ingest
sync and the final eval would otherwise be billed as round time), so the
frontier compares equal wall clock, not equal rounds.  Two proof
columns ride along:

* ``async_e1_bit_identical``: the degenerate async regime
  (``exchange_every=1, max_staleness=0, batch=None``) is bit-identical
  to the synchronous step — async is a strict generalization.
* per-arm ``counters``: the obs registry diffs must satisfy the exact
  skip accounting (``skipped == rounds - ceil(rounds/e)``) or the bench
  fails loudly.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python benchmarks/gossip_async.py --json BENCH_async.json
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import obs
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mc import CompletionProblem, Gossip, Trainer
from repro.mesh import MeshPlan, build_mesh

try:                                   # package mode (python -m benchmarks.x)
    from benchmarks.run import emit_json
except ImportError:                    # script mode (python benchmarks/x.py)
    from run import emit_json

ARM_COUNTERS = ("train_gossip_rounds_total", "train_gossip_halo_bytes_total",
                "gossip_skipped_exchanges_total", "gossip_stale_rounds_total")


def _grid_plan():
    """One block per device over every available device (2×2 under the
    4-device CI forcing; 1×1 on a bare host — no halos, frontier still
    runs)."""

    ndev = len(jax.devices())
    dr = 2 if ndev % 2 == 0 and ndev > 1 else 1
    dc = ndev // dr
    mesh = build_mesh((dr, dc), ("data", "model"))
    return MeshPlan.build(dr, dc, mesh=mesh)


def _counter_snapshot():
    snap = obs.snapshot()["counters"]
    return {k: snap.get(k, 0.0) for k in ARM_COUNTERS}


def run_frontier(smoke: bool, rounds_sync: int | None, batch: int | None,
                 exchange_every: list[int], seed: int = 0):
    plan = _grid_plan()
    p, q = plan.p, plan.q
    if smoke:
        m = n = 128 * max(p, q, 2)
        r, density = 8, 0.3
        batch = batch or 512
        rounds_sync = rounds_sync or 8
    else:
        # full-gradient rounds must be compute-bound (nnz/block >> batch)
        # for the frontier to measure gradient economics, not dispatch
        m = n = 1024 * max(p, q, 2)
        r, density = 16, 0.3
        batch = batch or 8192
        rounds_sync = rounds_sync or 16
    ds = lowrank_problem(m, n, r, density=density, seed=seed)
    problem = CompletionProblem.from_dataset(ds, p, q, rank=r,
                                             layout="sparse", mesh=plan)
    cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)
    nnz_per_block = float(np.asarray(problem.data.nnz).mean())

    def fit(R, **kw):
        t0 = time.perf_counter()
        res = Trainer(cfg).fit(
            problem, Gossip(num_rounds=R, plan=plan, **kw), seed=seed)
        return res, time.perf_counter() - t0

    def measured_arm(name, R, budget=None, fixed=0.0, **kw):
        before = _counter_snapshot()
        res, wall = fit(R, **kw)
        if budget is not None and wall > 1.05 * budget and wall > fixed:
            # calibration under-billed the marginal round cost and the arm
            # overshot its wall budget: rescale on the *measured* marginal
            # cost and re-run once (equal wall clock is the claim)
            R = max(4, int(R * max(budget - fixed, 0.1 * budget)
                           / (wall - fixed)))
            before = _counter_snapshot()
            res, wall = fit(R, **kw)
        after = _counter_snapshot()
        counters = {k: after[k] - before[k] for k in ARM_COUNTERS}
        e = kw.get("exchange_every", 1)
        if kw.get("async_rounds"):
            want = R - -(-R // e)            # planned skips, exactly
            got = int(counters["gossip_skipped_exchanges_total"])
            if got != want:
                raise AssertionError(
                    f"{name}: skip accounting off — observed {got} skipped "
                    f"exchanges over {R} rounds at e={e}, schedule says "
                    f"{want}")
        rmse = float(res.rmse())
        row = {"arm": name, "rounds": R, "wall_seconds": wall,
               "ms_per_round": wall / R * 1e3, "rmse": rmse,
               "final_cost": float(res.final_cost), "batch": kw.get("batch"),
               "exchange_every": e if kw.get("async_rounds") else 1,
               "counters": counters}
        print(f"gossip_async {name}: {R} rounds {wall:.2f}s "
              f"({row['ms_per_round']:.1f} ms/rd) rmse={rmse:.4f}")
        return row

    def rounds_for(budget, cal_lo, cal_hi, **kw):
        """Two-point calibration -> (rounds, fixed) for the wall budget."""
        _, t_lo = fit(cal_lo, **kw)
        _, t_hi = fit(cal_hi, **kw)
        slope = max((t_hi - t_lo) / float(cal_hi - cal_lo), 1e-4)
        fixed = max(t_lo - cal_lo * slope, 0.0)
        # floor of 4: at smoke scale the per-fit fixed cost can eat the
        # whole budget; the arm still runs enough rounds to exercise the
        # exchange clock (dominance is only asserted at full scale)
        rounds = max(4, min(16 * rounds_sync, int((budget - fixed) / slope)))
        return rounds, fixed

    # compile both step variants off the clock
    fit(2)
    fit(2, batch=batch)

    rows = [measured_arm("sync_full", rounds_sync)]
    budget = rows[0]["wall_seconds"]
    cal = (max(2, rounds_sync // 2), max(4, rounds_sync))

    R, fixed = rounds_for(budget, *cal, batch=batch)
    rows.append(measured_arm("sync_minibatch", R, budget=budget,
                             fixed=fixed, batch=batch))
    for e in exchange_every:
        kw = dict(batch=batch, async_rounds=True, exchange_every=e,
                  max_staleness=e)
        fit(2, **kw)
        R, fixed = rounds_for(budget, *cal, **kw)
        rows.append(measured_arm(f"async_minibatch_e{e}", R, budget=budget,
                                 fixed=fixed, **kw))

    # proof: degenerate async == sync, bit for bit
    a, _ = fit(8)
    b, _ = fit(8, async_rounds=True, exchange_every=1, max_staleness=0)
    bit_identical = bool(
        np.array_equal(np.asarray(a.state.U), np.asarray(b.state.U))
        and np.array_equal(np.asarray(a.state.W), np.asarray(b.state.W)))

    sync_rmse = rows[0]["rmse"]
    in_budget = [row for row in rows[1:]
                 if row["wall_seconds"] <= 1.1 * budget]
    best = min(in_budget or rows[1:], key=lambda row: row["rmse"])
    dominates = bool(best["rmse"] <= sync_rmse
                     and best["wall_seconds"] <= 1.1 * budget)
    print(f"gossip_async: budget {budget:.2f}s, sync rmse {sync_rmse:.4f}, "
          f"best stochastic arm {best['arm']} rmse {best['rmse']:.4f} "
          f"({best['wall_seconds']:.2f}s), e1 bit-identical: {bit_identical}")
    return {
        "grid": f"{p}x{q}", "devices": plan.num_devices, "m": m, "n": n,
        "rank": r, "density": density, "nnz_per_block": nnz_per_block,
        "budget_seconds": budget, "async_e1_bit_identical": bit_identical,
        "stochastic_dominates": dominates, "rows": rows,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=None,
                    help="sync full-gradient anchor rounds (sets the budget)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--exchange-every", type=str, default="2,4")
    ap.add_argument("--smoke", action="store_true",
                    help="small scale: envelope/counter checks only, no "
                    "dominance claim")
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args(argv)

    es = [int(x) for x in args.exchange_every.split(",")]
    result = run_frontier(args.smoke, args.rounds, args.batch, es)

    if not result["async_e1_bit_identical"]:
        raise AssertionError("async e=1 s=0 is not bit-identical to sync")
    if not args.smoke and not result["stochastic_dominates"]:
        raise AssertionError(
            "stochastic rounds did not dominate sync full-gradient rounds "
            f"at equal wall clock: {result['rows']}")

    if args.json:
        emit_json(args.json, "gossip_async",
                  {"rounds_sync": result["rows"][0]["rounds"],
                   "batch": result["rows"][1]["batch"],
                   "exchange_every": max(es), "async_rounds": True,
                   "smoke": args.smoke},
                  **result)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
