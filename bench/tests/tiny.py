"""A tiny benchmark tree for driving ``bench/run.py`` on the CPU.

``make_root(tmp)`` writes a ``BENCHMARK.json`` with small cells of every
traffic kind, their configuration, traffic and limit files, and returns
the root; ``run_cell(root, name, ...)`` runs one cell in this process with
the chip check skipped and returns the parsed result line.
"""

from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CPU_PEAKS = {"source": "test", "devices": {"cpu": {
    "bf16_flops_per_s": 1e12, "int8_ops_per_s": 1e12,
    "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9, "ici_bits_per_s": 1e9}}}

CONFIG = {
    "name": "tiny",
    "data": {"users": 96, "items": 64, "ratings": 2400, "latent_rank": 3,
             "noise": 0.5, "item_zipf": 0.8, "user_zipf": 0.6,
             "train_share": 0.8},
    "fit": {"rank": 4, "grid": [4, 4], "layout": "sparse",
            "mean_center": True, "block_capacity": 512, "engine": {},
            "hparams": {"rho": 100.0, "lam": 1e-6, "a": 1e-3, "b": 5e-7}},
    "serve": {"buckets": [4, 16], "k": 5, "exclude_seen": True,
              "quant": None, "seen_capacity": 64},
}
TRAFFIC = {
    "wave": {"kind": "fit", "schedule": "wave", "chunk_rounds": 2},
    "serve": {"kind": "serve_openloop", "rate_per_s": 40.0, "size_min": 1,
              "size_max": 24},
}
# each tiny cell is held to the limits of the real cell of its shape
LIMITS_OF = {"wave": "ml1m-r32.wave-fit", "serve": "ml1m-r32.serve-poisson"}


def make_root(tmp) -> pathlib.Path:
    root = pathlib.Path(tmp)
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    cells = []
    for name, tr in TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
        cell = f"tiny.{name}"
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            (BENCH / "limits" / f"{LIMITS_OF[name]}.json").read_text())
        cells.append({"name": cell, "config": "tiny", "traffic": name,
                      "chips": 1})
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    # a tiny cell reports what the real cells of its traffic shape report
    shape = {}
    for w in spec["workloads"]:
        tr = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                        .read_text())
        shape[w["name"]] = _shape(tr)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            shapes = {shape[w] for w in m["workloads"]}
            m["workloads"] = [c["name"] for c in cells
                              if _shape(TRAFFIC[c["traffic"]]) in shapes]
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": []}]
    spec["workloads"] = cells
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _shape(traffic: dict) -> str:
    return f"{traffic['kind']}/{traffic.get('schedule', '')}"


def run_cell(root, name: str, seed: int = 7, seconds: float = 1.0,
             trace: int = 0) -> tuple:
    """(result dict, stdout text, stderr text) of one in-process run."""

    import run

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", name, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, require_chip=False, peak_table=CPU_PEAKS)
    lines = out.getvalue().strip().splitlines()
    assert rc == 0, (rc, err.getvalue()[-3000:])
    return json.loads(lines[-1]), out.getvalue(), err.getvalue()
