"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--seconds 20]

For every seed it runs the cell's set-up (and, for serving, a window at
the cell's own load) and prints one JSON line of the compared numbers of
the program against the float32 reference.  For ``--control-seeds`` it
prints the same numbers for the control: the reference itself computed in
the precision below the configuration's (bfloat16 for the fits, three
bf16 passes for the scores).  For ``--fault-seeds`` of a fit cell it
plants each fault the cell can have and prints its numbers: half of the
ratings left out of every gradient with the rest counted twice.  A state left unchanged reads 1 on
every change number by definition and needs no run.  Lines also go to
``chiprun_out/calib/<cell>.jsonl``.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


@contextlib.contextmanager
def half_ratings():
    """Every block's f-gradient from every other stored rating, doubled."""

    import jax
    import jax.numpy as jnp
    from repro.sparse import objective as so

    orig = so.f_grads_sparse

    def half(entries, u, w, *a, **kw):
        keep = (jnp.arange(entries.valid.shape[-1]) % 2 == 0)
        entries = entries._replace(valid=entries.valid * keep)
        f, gu, gw = orig(entries, u, w, *a, **kw)
        return 2 * f, 2 * gu, 2 * gw

    so.f_grads_sparse = half
    jax.clear_caches()
    try:
        yield
    finally:
        so.f_grads_sparse = orig
        jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp

    import common
    import fit_driver
    import ratings
    import serve_driver

    cell = common.load_cell(args.workload)
    devices = common.require_tpu(cell.chips)
    out_dir = ROOT / "chiprun_out" / "calib"
    out_dir.mkdir(parents=True, exist_ok=True)
    sink = open(out_dir / f"{cell.name}.jsonl", "a")
    # one generation per seed, shared by the program's variants
    generate, memo = ratings.from_config, {}

    def cached(cfg, seed):
        if (cfg["name"], seed) not in memo:
            memo.clear()
            memo[(cfg["name"], seed)] = generate(cfg, seed)
        return memo[(cfg["name"], seed)]

    ratings.from_config = cached

    def emit(**kw):
        line = json.dumps({"cell": cell.name, **kw}, default=float)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    kind = cell.traffic["kind"]
    for seed in sorted(set(args.seeds) | set(args.control_seeds)
                       | set(args.fault_seeds)):
        t0 = time.perf_counter()
        with common.CompileClock() as clock:
            phases = common.Phases(clock)
            if kind == "fit":
                run = fit_driver.FitRun(cell, seed, phases, devices,
                                        args.seconds)
                extra = {"block_nnz_max": int(run.nnz.max())}
            else:
                run = serve_driver.ServeRun(cell, seed, phases, devices,
                                            args.seconds)
                w = run.window(args.seconds, common.NullSpan)
                extra = {"window": w}
        extra["setup"] = phases.split
        extra["memory_peak_bytes"] = common.peak_bytes(devices)
        run.release()
        if kind == "fit":
            ref = run.reference()
            if seed in args.seeds:
                emit(seed=seed, variant="program",
                     numbers=fit_driver.fit_numbers(run.theta0,
                                                    run.readings(), ref),
                     costs=run.costs, ref_costs=ref["costs"], **extra)
            if seed in args.control_seeds:
                ctl = run.reference(jnp.bfloat16)
                emit(seed=seed, variant="control_bf16",
                     numbers=fit_driver.fit_numbers(run.theta0, ctl, ref))
            if seed in args.fault_seeds:
                for name, fault in [("half_ratings", half_ratings)]:
                    with fault(), common.CompileClock() as clock:
                        bad = fit_driver.FitRun(cell, seed,
                                                common.Phases(clock),
                                                devices, args.seconds)
                        bad.release()
                    emit(seed=seed, variant=f"fault_{name}",
                         numbers=fit_driver.fit_numbers(bad.theta0,
                                                        bad.readings(), ref))
        else:
            if seed in args.seeds:
                emit(seed=seed, variant="program",
                     numbers=run.numbers("program"), **extra)
            if seed in args.control_seeds:
                emit(seed=seed, variant="control_high",
                     numbers=run.numbers("control"))
        emit(seed=seed, variant="seconds", seconds=time.perf_counter() - t0)
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
