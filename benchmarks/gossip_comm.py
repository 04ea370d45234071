"""Communication accounting: gossip halo exchange vs centralized baselines.

The paper's core claim is decentralization — no server, neighbour-only
messages.  This bench quantifies per-round wire bytes *per agent* for

(a) the paper's gossip halo exchange: ≤2 U edges + ≤2 W edges to grid
    neighbours (what core/gossip.py's 4 collective-permutes move),
(b) a parameter-server sync: every agent uploads its block factors and
    downloads the *global* consensus view of its row-U and column-W
    (the [7]-style architecture the paper argues against): the download
    alone is q× / p× larger than the gossip edges,
(c) ring all-reduce consensus over each row's U and column's W
    (2·(g−1)/g · payload per member, g = row/col length),

plus the int8/top-k compressed gossip variants.  Derived column: ICI time
at 50 GB/s/link and the byte ratios.

Geometry comes from a ``MeshPlan`` (one block per device — the paper's
one-agent-per-block deployment), and ``--measure`` additionally runs a
small real fit through the session facade (``Trainer.fit`` with the
``Gossip`` schedule on the default 1×1 plan, or the forced multi-device
mesh when ``XLA_FLAGS=--xla_force_host_platform_device_count`` is set)
to report measured wall-clock per gossip round next to the analytic wire
bytes — the bench no longer drives ``core/gossip`` loops directly.

    PYTHONPATH=src python benchmarks/gossip_comm.py \
        [--rank 64] [--measure] [--measure-rounds 30] [--json PATH]
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.core import compress as C
from repro.core.gossip import halo_bytes_per_round
from repro.mesh import MeshPlan, build_mesh

try:                                   # package mode (python -m benchmarks.x)
    from benchmarks.run import emit_json
except ImportError:                    # script mode (python benchmarks/x.py)
    from run import emit_json

ICI = 50e9


def bytes_per_round(plan: MeshPlan, mb: int, nb: int, r: int,
                    compression: str = "none"):
    """Per-agent wire bytes for one round, from the plan's grid geometry
    (p×q agents, each owning an mb×nb block with rank-r factors)."""

    p, q = plan.p, plan.q
    u_msg, w_msg = mb * r, nb * r
    # (a) gossip: send+receive 2 U edges and 2 W edges (interior agent)
    gossip = 2 * (C.message_bytes_n(u_msg, compression)
                  + C.message_bytes_n(w_msg, compression))
    # (b) server round-trip: upload own U,W; download the row's global U
    #     (m·r/p numbers would suffice at consensus, but pre-consensus the
    #     server must ship all q versions) and the column's global W
    up = (u_msg + w_msg) * 4
    down = (q * u_msg + p * w_msg) * 4
    ps = up + down
    # (c) ring all-reduce over row (q members, U) and column (p, W)
    ar = 2 * (q - 1) / q * u_msg * 4 + 2 * (p - 1) / p * w_msg * 4
    return gossip, ps, ar


def analytic_rows(r: int):
    """The paper-scale deployments: one agent per block, blocks over a
    matching device grid (analytic — no physical devices required)."""

    rows = []
    for (m, n, p, q) in [(1 << 20, 1 << 20, 16, 16),
                         (1 << 20, 1 << 20, 64, 64),
                         (5000, 5000, 5, 5)]:
        # geometry-only plan: p×q blocks on an abstract p×q device grid
        # (row/col sizes 1 keeps it constructible on any host)
        plan = MeshPlan.build(p, q)
        mb, nb = m // p, n // q
        for comp in ("none", "int8", "topk"):
            g, ps, ar = bytes_per_round(plan, mb, nb, r, comp)
            # exact mesh-wide accounting from the same edge geometry the
            # runtime ppermutes (halo_bytes_per_round lives next to
            # exchange_halos): boundary agents send fewer edges, so the
            # total is NOT p·q × the interior-agent figure
            halo = halo_bytes_per_round(plan, mb, nb, r, comp, grid=(p, q))
            assert halo["per_interior_agent_bytes"] == g
            rows.append({
                "grid": f"{p}x{q}", "m": m, "n": n, "rank": r,
                "compression": comp,
                "gossip_bytes": g, "server_bytes": ps,
                "ring_allreduce_bytes": ar,
                "halo_total_bytes": halo["total_bytes"],
                "ici_us": g / ICI * 1e6,
                "vs_server": g / ps, "vs_allreduce": g / ar,
            })
    return rows


def measured_row(rounds: int):
    """A real (small) gossip fit through the facade: the mesh spans every
    available device, the problem is placed by its MeshPlan, and we time
    the jitted distributed rounds."""

    from repro.config import GossipMCConfig
    from repro.data import lowrank_problem
    from repro.mc import CompletionProblem, Gossip, Trainer

    ndev = len(jax.devices())
    dr = 2 if ndev % 2 == 0 and ndev > 1 else 1
    dc = ndev // dr
    p, q = max(2, dr), max(2, dc)
    m = n = 64 * max(p, q)
    mesh = build_mesh((dr, dc), ("data", "model"))
    plan = MeshPlan.build(p, q, mesh=mesh)
    ds = lowrank_problem(m, n, r=4, density=0.2, seed=0)
    problem = CompletionProblem.from_dataset(ds, p, q, rank=4,
                                             layout="sparse", mesh=plan)
    cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=4)

    # steady-state timing without compile pollution: one fit, eval
    # boundaries every `rounds` rounds, timestamps via the callback
    # protocol.  The jitted step and the cost fn compile inside the
    # first chunk; every later inter-boundary interval is pure round
    # execution (+ one synced cost eval), so we average those.
    class _Stamps:
        def __init__(self):
            self.t = []

        def on_fit_start(self, problem, schedule, cfg):
            pass

        def on_eval(self, unit, cost, state, key):
            self.t.append(time.perf_counter())

        def on_fit_end(self, result):
            pass

    chunks = 4
    stamps = _Stamps()
    res = Trainer(cfg, callbacks=[stamps]).fit(
        problem, Gossip(num_rounds=chunks * rounds, eval_every=rounds,
                        plan=plan), seed=0)
    steady = [b - a for a, b in zip(stamps.t[1:-1], stamps.t[2:])]
    mb, nb = m // p, n // q
    g, ps, ar = bytes_per_round(plan, mb, nb, 4)
    # what the fit above actually moved: exact per-round wire bytes from
    # the plan's device-grid edge geometry (the same figure the Gossip
    # schedule streams into train_gossip_halo_bytes_total)
    halo = halo_bytes_per_round(plan, mb, nb, 4)
    cu, cw = res.consensus_error()
    return {
        "grid": f"{p}x{q}", "m": m, "n": n, "rank": 4,
        "devices": ndev, "rounds": rounds,
        "ms_per_round": min(steady) / rounds * 1e3,
        "final_cost": res.final_cost,
        "consensus_error": max(float(cu), float(cw)),
        "halo": halo,
        "gossip_bytes": g, "server_bytes": ps,
        "ring_allreduce_bytes": ar, "vs_server": g / ps,
    }


def main(argv=None):
    """``argv=None`` parses sys.argv (CLI); pass a list to embed — the
    ``benchmarks/run.py`` driver calls ``main([])`` so its own flags
    (e.g. ``--full``) never leak into this parser."""

    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--measure", action="store_true",
                    help="also run a small real gossip fit via the "
                         "facade and report ms/round")
    ap.add_argument("--measure-rounds", type=int, default=30)
    ap.add_argument("--json", type=str, default=None,
                    help="write results as JSON to this path")
    args = ap.parse_args(argv)

    rows = analytic_rows(args.rank)
    for r_ in rows:
        print(f"gossip_comm_{r_['grid']}_{r_['compression']},"
              f"{r_['ici_us']:.2f},"
              f"gossip_B={r_['gossip_bytes']:.3g};"
              f"server_B={r_['server_bytes']:.3g};"
              f"ring_allreduce_B={r_['ring_allreduce_bytes']:.3g};"
              f"vs_server={r_['vs_server']:.4f};"
              f"vs_allreduce={r_['vs_allreduce']:.3f}")

    measured = None
    if args.measure:
        measured = measured_row(args.measure_rounds)
        print(f"measured {measured['grid']} grid on {measured['devices']} "
              f"device(s): {measured['ms_per_round']:.2f} ms/round "
              f"({measured['rounds']} rounds, cost "
              f"{measured['final_cost']:.3e}, consensus "
              f"{measured['consensus_error']:.3e}, "
              f"{measured['halo']['total_bytes']} halo B/round)")

    if args.json:
        payload = {"rows": rows}
        if measured is not None:
            payload["measured"] = measured
        emit_json(args.json, "gossip_comm",
                  {"rank": args.rank, "ici_gbps": ICI / 1e9,
                   "measure": bool(args.measure)},
                  **payload)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
