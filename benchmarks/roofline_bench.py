"""Roofline table from the dry-run records (results/dryrun.jsonl).

One row per (arch × shape × mesh): the three terms in seconds, the
bottleneck, roofline fraction (compute / dominant term) and the
MODEL_FLOPS / HLO_FLOPS useful-compute ratio.  This bench only *reads*
dry-run output — regenerate with ``python -m repro.launch.dryrun --all``.
"""

from __future__ import annotations

import json
import os

from repro.roofline.analysis import analyze_record

import glob as _glob

DEFAULT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                            "dryrun*.jsonl")


def load_records(path=DEFAULT_PATH):
    records = []
    for p in sorted(_glob.glob(path)) or ([path] if os.path.exists(path) else []):
        with open(p) as f:
            for line in f:
                line = line.strip()
                if line:
                    records.append(json.loads(line))
    # keep last record per cell key (reruns append; later files win)
    by_key = {}
    for r in records:
        by_key[(r["arch"], r["shape"], r["mesh"])] = r
    return list(by_key.values())


def main(out=print, path=DEFAULT_PATH):
    records = load_records(path)
    if not records:
        out("roofline,0,no dryrun.jsonl found — run repro.launch.dryrun --all")
        return
    for r in records:
        a = analyze_record(r)
        dom_s = max(a["compute_s"], a["memory_s"], a["collective_s"])
        out(
            f"roofline_{a['arch']}_{a['shape']}_{a['mesh']},{dom_s*1e6:.1f},"
            f"compute_s={a['compute_s']:.3e};memory_s={a['memory_s']:.3e};"
            f"collective_s={a['collective_s']:.3e};bottleneck={a['bottleneck']};"
            f"frac={a['roofline_fraction']:.3f};"
            f"useful={a.get('useful_flops_ratio', 0):.3f}"
        )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
