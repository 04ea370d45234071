"""Benchmark driver + the shared ``--json`` schema every bench emits.

Driver: prints ``name,us_per_call,derived`` CSV rows.  ``--full`` runs the
paper-scale horizons (Exp#5/#6, ML-1M-scale proxy); default finishes in
minutes on CPU.

Schema (``emit_json`` / ``bench_json``): every ``benchmarks/*.py --json``
writes one dict with the same envelope —

    bench      str   — which bench produced this file
    backend    str   — jax.default_backend() (autotune.py keys on it)
    machine    dict  — platform/python/jax/device_count provenance
    git_rev    str?  — short commit hash (None outside a git checkout)
    config     dict  — the bench's resolved arguments
    <payload>  ...   — the bench's own result keys, unchanged from the
                       pre-schema files (rows / measured / append / ...)
    metrics    dict  — ``repro.obs`` registry snapshot: every counter,
                       gauge and histogram the instrumented planes
                       recorded during the run (DESIGN.md §12)

Committed baselines (``BENCH_*.json``) written before this schema stay
readable: old top-level keys are preserved verbatim as payload keys, the
envelope only adds.  ``scripts/obs_report.py`` renders the ``metrics``
key of any such file (or a bare snapshot) as a terminal table.
"""

from __future__ import annotations

import json
import os
import platform
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def machine_info() -> dict:
    """Reproducibility provenance for a bench JSON."""

    import jax

    return {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }


def git_rev() -> str | None:
    """Short HEAD hash of the repo this bench ran from, or None."""

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=_REPO_ROOT, timeout=10,
        )
    except (OSError, subprocess.SubprocessError):
        return None
    rev = out.stdout.strip()
    return rev if out.returncode == 0 and rev else None


def bench_json(bench: str, config: dict, **payload) -> dict:
    """The one bench-JSON envelope (see module docstring).

    ``payload`` keys land top-level so files written before the schema
    keep their old readers; the ``metrics`` key snapshots the process
    ``repro.obs`` registry at call time — call once, at the end."""

    import jax

    from repro import obs

    out = {
        "bench": bench,
        "backend": jax.default_backend(),
        "machine": machine_info(),
        "git_rev": git_rev(),
        "config": config,
    }
    for k, v in payload.items():
        if k in out:
            raise ValueError(f"payload key {k!r} collides with the envelope")
        out[k] = v
    out["metrics"] = obs.snapshot()
    return out


def emit_json(path: str, bench: str, config: dict, **payload) -> dict:
    """Write ``bench_json(...)`` to ``path`` and return it."""

    out = bench_json(bench, config, **payload)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {path}")
    return out


def main() -> None:
    full = "--full" in sys.argv
    print("name,us_per_call,derived")

    from benchmarks import table2_synthetic
    table2_synthetic.main(full=full)

    from benchmarks import table3_rmse
    table3_rmse.main(full=full)

    from benchmarks import kernels_bench
    kernels_bench.main()

    from benchmarks import gossip_comm
    gossip_comm.main([])      # empty argv: don't re-parse run.py's flags

    from benchmarks import roofline_bench
    roofline_bench.main()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
