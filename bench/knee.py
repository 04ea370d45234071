"""Serving knee sweep on the chip: the highest offered rate at which the
queue does not grow across a window.

    python3 bench/knee.py --workload ml1m-r32.serve-poisson \
        --rates 50,60,70,80,90,100 [--seconds 20] [--seeds 1,2]

Sets the cell up once for each seed, then replays one tape per rate (same
sizes and arrivals, scaled to the rate) and prints per seed and rate: the
completed rate, p50/p95, the generator's lag, and the median latency of
the last fifth of the requests against the first fifth.  A rate whose
last fifth waits more than 1.25 times as long as its first, or that
completes under 97% of what is offered, has a growing queue.  The knee is
the highest rate at and below which no run of any seed grew.  The cell's
traffic file records the rate chosen from it; the benchmark's runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
GROWTH = 1.25     # last fifth over first fifth of a growing queue
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="1,2")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import numpy as np

    import common
    import openloop
    import serve_driver

    cell = common.load_cell(args.workload)
    devices = common.require_tpu(cell.chips)
    tr = cell.traffic
    m = cell.config["data"]["users"]
    for seed in (int(x) for x in args.seeds.split(",")):
        with common.CompileClock() as clock:
            run = serve_driver.ServeRun(cell, seed, common.Phases(clock),
                                        devices, args.seconds)
        for rate in (float(x) for x in args.rates.split(",")):
            n = max(1, round(rate * args.seconds))
            run.tape = openloop.make_tape(n, rate, tr["size_min"],
                                          tr["size_max"], m, seed)
            rp = openloop.replay(run.engine.submit, run.tape)
            lat = rp.latency
            fifth = max(1, n // 5)
            first, last = np.median(lat[:fifth]), np.median(lat[-fifth:])
            done = int(np.isfinite(lat).sum())
            completed = done / (rp.end - rp.start)
            print(json.dumps({
                "seed": seed, "rate": rate, "requests": n,
                "completed_per_s": completed,
                "p50_ms": openloop.percentile_ms(lat, 50),
                "p95_ms": openloop.percentile_ms(lat, 95),
                "first_fifth_p50_ms": first * 1e3,
                "last_fifth_p50_ms": last * 1e3,
                "lag_max_ms": float(rp.lag.max()) * 1e3,
                "growing": bool(last > GROWTH * first
                                or completed < 0.97 * rate),
            }), flush=True)
        run.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
