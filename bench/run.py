"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``; its configuration,
traffic mix and correctness limits are files under ``bench/`` found by
name, and its traffic's ``kind`` picks the driver (``fit`` or
``serve_openloop``).  A run sets up (generates its data from ``--seed``,
ingests, compiles or loads from the compile cache in ``.jax_cache/``,
warms up), measures for ``--seconds``, frees the program's state and
compares what the timed path produced with the plain reference.

Standard output carries diagnostics lines (``{"bench": "setup", ...}``,
``{"bench": "diagnostics", ...}``) and, last, the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the per-layer ones, each read by ``bench/metrics/<name>.py``.
The last lines of standard error give each compared number beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, the run
exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

DRIVERS = {"fit": ("fit_driver", "FitRun"),
           "serve_openloop": ("serve_driver", "ServeRun")}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def read_metric(name: str, run: dict):
    """The per-layer metric ``name`` from its reader, or None."""

    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def traced(window, trace_dir):
    """Run ``window(span)`` under the profiler; returns its result."""

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            return window(jax.profiler.TraceAnnotation)


def main(argv=None, *, root=ROOT, require_chip=True, peak_table=None,
         t_start=None) -> int:
    import common

    args = parse(argv)
    t_start = T_START if t_start is None else t_start
    cell = common.load_cell(args.workload, root)
    try:
        if require_chip:
            devices = common.require_tpu(cell.chips)
        else:
            import jax

            devices = jax.devices()[:cell.chips]
        peak = common.peaks(devices[0].device_kind, peak_table)
    except (common.NoChip, common.UnknownDevice) as e:
        print(f"bench/run.py: {e}; nothing was measured", file=sys.stderr)
        return 3

    import jax
    from repro import obs

    kind = cell.traffic["kind"]
    module, cls = DRIVERS[kind]
    driver = getattr(importlib.import_module(module), cls)
    with common.CompileClock() as clock:
        phases = common.Phases(clock)
        run = driver(cell, args.seed, phases, devices, args.seconds)
        setup_s = time.perf_counter() - t_start
        common.log_line("setup", setup_s=setup_s, split=phases.split,
                        compile_s=clock.seconds, cache_hits=clock.cache_hits)
        compiles0 = clock.compiles
        trace, trace_dir = None, None
        if args.trace:
            trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench_trace_"))
            w = traced(lambda span: run.window(args.seconds, span), trace_dir)
        else:
            w = run.window(args.seconds, common.NullSpan)
        window_compiles = clock.compiles - compiles0
    memory = common.peak_bytes(devices)
    snap = obs.snapshot()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}

    breakdown = None
    if args.trace:
        import trace_reduce as tr
        import workcount

        trace = tr.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tr.window(trace)
        if not trace.ops:          # no device plane: the CPU of a test
            trace = None
        work = getattr(run, "work", None)
        least = (workcount.least_seconds(work, peak, len(devices))
                 if work else None)
        ctx = {"trace": trace, "lo": lo, "hi": hi, "window": w,
               "work": work, "least": least, "peak": peak,
               "chips": len(devices), "obs": snap}
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = tr.busy_seconds(trace, lo, hi)
            device["window_s"] = (hi - lo) * 1e-9
            breakdown = {"device_ops": tr.top_ops(trace, lo, hi),
                         "idle_gaps": tr.idle_gaps(trace, lo, hi)}
        if least:
            common.log_line("roofline", least_s_per_round=least[0],
                            bound=least[1], work=work)
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else w[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    counters = snap.get("counters", {})
    common.log_line(
        "diagnostics", window=w, window_compiles=window_compiles,
        kernel_fallbacks_total=sum(v for k, v in counters.items()
                                   if k.startswith("kernel_fallbacks_total")),
        serve_compiles_total=getattr(run, "compiles", None))

    run.release()
    t0 = time.perf_counter()
    numbers = run.check()
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in numbers.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    common.log_line("check", seconds=time.perf_counter() - t0)
    common.check_lines(checks)
    result = {"correct": correct, "attempted": w["attempted"],
              "failed": w["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
