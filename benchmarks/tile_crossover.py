"""One block visit, segment engine against dense masked tile.

Times ``f_grads_sparse`` on one block — the f-term and both factor
gradients, the work a ``wave_step`` does per block — once from the
segment-sorted entries and once from the block's dense masked tile, at a
fixed block shape while the entry capacity shrinks by 4× a level.  The
segment engine's time grows with the capacity, the tile's with the block
area, so the sweep brackets the capacity density at which they cross:
``sparse.store.TILE_CROSSOVER`` takes its value per backend from this run.

Each timing is a jitted scan of ``--visits`` dependent visits (the factors
move a little each visit, so none is hoisted), best of ``--reps`` after a
warm-up, divided by the visits: dispatch is paid once per scan.

    PYTHONPATH=src python benchmarks/tile_crossover.py \
        [--mb 1510] [--nb 927] [--rank 32] [--capacity 61440] \
        [--fill 0.931] [--levels 4] [--visits 32] [--reps 5] [--json PATH]

The defaults are the ML-1M block of a 4×4 grid (6,040 × 3,706 users ×
items) at the benchmark configuration's capacity, filled as its largest
block (57,211 of 61,440 slots).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse import drop_tile, from_entries, with_tile
from repro.sparse.objective import f_grads_sparse


def block_store(mb: int, nb: int, capacity: int, nnz: int, seed: int):
    """A one-block store of ``nnz`` distinct random entries at exactly
    ``capacity`` slots, with its dense tile attached."""

    rng = np.random.default_rng(seed)
    lin = rng.choice(mb * nb, size=nnz, replace=False)
    rows, cols = lin // nb, lin % nb
    vals = rng.normal(size=nnz).astype(np.float32)
    sp, _ = from_entries(rows, cols, vals, mb, nb, 1, 1, bucket=capacity,
                         headroom=capacity - nnz)
    return with_tile(drop_tile(sp))


def visit_seconds(entries, u, w, visits: int, reps: int) -> float:
    """Best-of-``reps`` seconds per visit over a scan of ``visits``."""

    @jax.jit
    def run(entries, u, w):
        def body(carry, _):
            u, w = carry
            _, gu, gw = f_grads_sparse(entries, u, w)
            return (u - 1e-9 * gu, w - 1e-9 * gw), None

        (u, w), _ = jax.lax.scan(body, (u, w), None, length=visits)
        return u, w

    jax.block_until_ready(run(entries, u, w))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(entries, u, w))
        best = min(best, time.perf_counter() - t0)
    return best / visits


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mb", type=int, default=1510)
    ap.add_argument("--nb", type=int, default=927)
    ap.add_argument("--rank", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=61440)
    ap.add_argument("--fill", type=float, default=57211 / 61440)
    ap.add_argument("--levels", type=int, default=4)
    ap.add_argument("--visits", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    key = jax.random.PRNGKey(0)
    ku, kw = jax.random.split(key)
    sd = 1.0 / np.sqrt(args.rank)
    u = sd * jax.random.normal(ku, (args.mb, args.rank), jnp.float32)
    w = sd * jax.random.normal(kw, (args.nb, args.rank), jnp.float32)
    rows = []
    for lvl in range(args.levels):
        cap = args.capacity // 4 ** lvl
        nnz = int(round(args.fill * cap))
        sp = block_store(args.mb, args.nb, cap, nnz, seed=lvl)
        one = jax.tree.map(lambda a: a[0, 0], sp.entries)
        seg = visit_seconds(one.without_tile(), u, w, args.visits, args.reps)
        tile = visit_seconds(one, u, w, args.visits, args.reps)
        rows.append({"capacity": cap, "nnz": nnz,
                     "capacity_density": cap / (args.mb * args.nb),
                     "segment_us": seg * 1e6, "tile_us": tile * 1e6})
        print(f"capacity {cap:6d} ({rows[-1]['capacity_density']:.5f}): "
              f"segment {seg * 1e6:9.1f} us  tile {tile * 1e6:9.1f} us",
              flush=True)
    out = {"device": {"platform": dev.platform,
                      "device_kind": dev.device_kind},
           "block": [args.mb, args.nb], "rank": args.rank, "rows": rows}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
