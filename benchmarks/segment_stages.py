"""One tile-less block visit of the segment engine, stage by stage.

Times the stages of ``kernels/sddmm/segment.py``'s gradient on one block
of Zipf-skewed ratings (the work a ``wave_step`` does per tile-less
block), each stage a jitted call of its own on precomputed inputs, best
of ``--reps`` after a warm-up:

    gather_rows    U[rows] (sorted rows)
    gather_cols    W[cols]
    residual       e = valid·(vals − ⟨U[rows], W[cols]⟩), ‖e‖²
    reduce_rows    segment_reduce(−2e·W[cols], row_ptr)
    col_permute    (−2e·U[rows])[col_perm]: the CSC view as a permutation
                   of an (E, r) array
    col_gather     U[rows[col_perm]] and (−2e)[col_perm]: the same view
                   gathered from the factor
    reduce_cols    segment_reduce(CSC contributions, col_ptr)
    scatter_rows   jax.ops.segment_sum of the row contributions (a sorted
                   scatter-add), for comparison
    visit          the whole ``sddmm_segment_grad_ref``

at block capacities shrinking by 4× a level (``--levels``), with every
stage vmapped over ``--batch`` copies of the block (a wave visits up to
twelve).  ``segment_reduce`` and the visit are whatever the ``repro`` on
``PYTHONPATH`` holds, so the same script times an older tree's engine.
A stage whose time projected to the next level (×16) passes
``--limit-s`` is not run there.  ``--chunks`` sweeps the reduction's
chunk over the whole visit at the full capacity.  ``--trace DIR`` also
records one visit under the profiler and prints its costliest device ops.

    PYTHONPATH=src python benchmarks/segment_stages.py \\
        [--mb 17892] [--nb 2671] [--rank 64] [--capacity 573440] \\
        [--fill 0.9297] [--levels 4] [--batch 1] [--chunk 32] \\
        [--chunks 32,64,128,256] [--reps 5] [--limit-s 20] [--json PATH]

The defaults are the ML-10M block of a 4×4 grid (71,567 × 10,681 users ×
items) at the benchmark configuration's capacity, filled as the largest
block of the seeds surveyed (571,235 of 573,440 slots).  Numbers mean something on
the chip only.
"""

from __future__ import annotations

import argparse
import glob
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sddmm.segment import sddmm_segment_grad_ref, segment_reduce
from repro.sparse import drop_tile, from_entries


def zipf_block(mb: int, nb: int, capacity: int, nnz: int, seed: int):
    """One block's store: ``nnz`` distinct (row, col) pairs, rows Zipf
    0.6 and columns Zipf 0.8 over shuffled ids, at ``capacity`` slots."""

    rng = np.random.default_rng(seed)
    pr = 1.0 / np.arange(1, mb + 1) ** 0.6
    pc = 1.0 / np.arange(1, nb + 1) ** 0.8
    lin = np.zeros(0, np.int64)
    while len(lin) < nnz:
        rows = rng.permutation(mb)[rng.choice(mb, 2 * nnz, p=pr / pr.sum())]
        cols = rng.permutation(nb)[rng.choice(nb, 2 * nnz, p=pc / pc.sum())]
        lin = np.unique(np.concatenate([lin, rows * nb + cols]))
    lin = rng.permutation(lin)[:nnz]
    vals = rng.normal(size=nnz).astype(np.float32)
    sp, _ = from_entries(lin // nb, lin % nb, vals, mb, nb, 1, 1,
                         bucket=capacity, headroom=capacity - nnz)
    return jax.tree.map(lambda a: a[0, 0], drop_tile(sp).entries)


def stages(chunk: int) -> dict:
    """name -> fn(entries, u, w, aux) of one block; ``aux`` holds the
    per-entry inputs a stage starts from."""

    def take(a, i, **kw):
        return jnp.take(a, i, axis=0, mode="clip", **kw)

    return {
        "gather_rows": lambda e, u, w, x: take(u, e.rows,
                                               indices_are_sorted=True),
        "gather_cols": lambda e, u, w, x: take(w, e.cols),
        "residual": lambda e, u, w, x: jnp.sum(jnp.square(e.valid * (
            e.vals - jnp.sum(x["ue"] * x["we"], -1)))),
        "reduce_rows": lambda e, u, w, x: segment_reduce(
            x["d"][:, None] * x["we"], e.row_ptr, chunk),
        "col_permute": lambda e, u, w, x: take(
            x["d"][:, None] * x["ue"], e.col_perm),
        "col_gather": lambda e, u, w, x: (
            take(u, take(e.rows, e.col_perm)), take(x["d"], e.col_perm)),
        "reduce_cols": lambda e, u, w, x: segment_reduce(
            x["cw"], e.col_ptr, chunk),
        "scatter_rows": lambda e, u, w, x: jax.ops.segment_sum(
            x["d"][:, None] * x["we"], e.rows, num_segments=u.shape[0],
            indices_are_sorted=True),
        "visit": lambda e, u, w, x: sddmm_segment_grad_ref(e, u, w, chunk),
    }


def aux_inputs(e, u, w):
    ue = jnp.take(u, e.rows, axis=0)
    we = jnp.take(w, e.cols, axis=0)
    d = -2.0 * e.valid * (e.vals - jnp.sum(ue * we, -1))
    cw = jnp.take(d[:, None] * ue, e.col_perm, axis=0)
    return {"ue": ue, "we": we, "d": d, "cw": cw}


def seconds(fn, args, reps: int) -> tuple:
    """(first call incl. compile, best of ``reps``) in seconds."""

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return first, best


def top_device_ops(trace_dir: str, n: int = 12) -> list:
    """The ``n`` costliest op names on the first device's op line."""

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not path:
        return []
    data = jax.profiler.ProfileData.from_file(path[-1])
    total: dict = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
    return sorted(((k, v * 1e-6) for k, v in total.items()),
                  key=lambda kv: -kv[1])[:n]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mb", type=int, default=17892)
    ap.add_argument("--nb", type=int, default=2671)
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--capacity", type=int, default=573440)
    ap.add_argument("--fill", type=float, default=571235 / 573440)
    ap.add_argument("--levels", type=int, default=4)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--chunks", default="")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--limit-s", type=float, default=20.0)
    ap.add_argument("--only", default="", help="comma-separated stages")
    ap.add_argument("--trace", default=None)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    ku, kw = jax.random.split(jax.random.PRNGKey(0))
    sd = 1.0 / np.sqrt(args.rank)
    u = sd * jax.random.normal(ku, (args.mb, args.rank), jnp.float32)
    w = sd * jax.random.normal(kw, (args.nb, args.rank), jnp.float32)
    B = args.batch
    stack = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.broadcast_to(a, (B,) + a.shape), t)
    names = [s for s in stages(args.chunk)
             if not args.only or s in args.only.split(",")]
    rows, blocked, one = [], set(), None
    for lvl in reversed(range(args.levels)):       # smallest first
        cap = args.capacity // 4 ** lvl
        nnz = int(round(args.fill * cap))
        one = zipf_block(args.mb, args.nb, cap, nnz, seed=lvl)
        x = aux_inputs(one, u, w)
        inputs = stack((one, u, w, x))
        for name in names:
            if name in blocked:
                rows.append({"capacity": cap, "stage": name,
                             "skipped": f"over {args.limit_s} s projected"})
                continue
            fn = jax.jit(jax.vmap(stages(args.chunk)[name]))
            first, best = seconds(fn, inputs, args.reps)
            rows.append({"capacity": cap, "nnz": nnz, "stage": name,
                         "batch": B, "ms": best * 1e3,
                         "first_s": first})
            print(json.dumps(rows[-1]), flush=True)
            if best * 16 > args.limit_s or first > args.limit_s:
                blocked.add(name)
    sweep = []
    for c in [int(c) for c in args.chunks.split(",") if c]:
        fn = jax.jit(jax.vmap(stages(c)["visit"]))
        first, best = seconds(fn, stack((one, u, w, {})), args.reps)
        sweep.append({"chunk": c, "visit_ms": best * 1e3, "first_s": first})
        print(json.dumps(sweep[-1]), flush=True)
    top = []
    if args.trace:
        fn = jax.jit(jax.vmap(stages(args.chunk)["visit"]))
        args_ = stack((one, u, w, {}))
        jax.block_until_ready(fn(*args_))
        with jax.profiler.trace(args.trace):
            jax.block_until_ready(fn(*args_))
        top = top_device_ops(args.trace)
        for name, ms in top:
            print(f"device op {ms:10.3f} ms  {name}", flush=True)
    out = {"device": {"platform": dev.platform,
                      "device_kind": dev.device_kind},
           "block": [args.mb, args.nb], "rank": args.rank,
           "chunk": args.chunk, "batch": B, "rows": rows,
           "chunk_sweep": sweep, "top_device_ops_ms": top}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
