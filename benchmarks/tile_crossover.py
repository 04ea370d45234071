"""One block visit, segment engine against dense masked tile.

Times ``f_grads_sparse`` on one block — the f-term and both factor
gradients, the work a ``wave_step`` does per block — from the
segment-sorted entries, from the block's dense tile as float32 values and
a mask, and from the same tile as 1-byte rating codes, at a fixed block
shape while the entry capacity shrinks by 4× a level.  The segment
engine's time grows with the capacity, the tile's with the block area, so
the sweep brackets the capacity density at which they cross:
``sparse.store.TILE_CROSSOVER`` takes its value per backend from this run.
The values are centred half-star ratings, so both tile encodings apply.

Each timing is a jitted scan of ``--visits`` dependent visits (the factors
move a little each visit, so none is hoisted), best of ``--reps`` after a
warm-up, divided by the visits: dispatch is paid once per scan.

    PYTHONPATH=src python benchmarks/tile_crossover.py [--block ml1m] \
        [--mb 1510] [--nb 927] [--rank 32] [--capacity 61440] \
        [--fill 0.931] [--levels 4] [--visits 32] [--reps 5] [--json PATH]

``--block`` sets the other sizes' defaults (:data:`BLOCKS`): ``ml1m`` is
the ML-1M block of a 4×4 grid (6,040 × 3,706 users × items) at rank 32,
at the benchmark configuration's capacity, filled as its largest block
(57,211 of 61,440 slots); ``ml10m`` is the ML-10M block of a 4×4 grid
(71,567 × 10,681) at rank 64, at its configuration's 573,440 slots,
filled as the largest block surveyed (571,235).
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

BLOCKS = {
    "ml1m": dict(mb=1510, nb=927, rank=32, capacity=61440,
                 fill=57211 / 61440),
    "ml10m": dict(mb=17892, nb=2671, rank=64, capacity=573440,
                  fill=571235 / 573440),
}


def block_store(mb: int, nb: int, capacity: int, nnz: int, seed: int):
    """A one-block store of ``nnz`` distinct random entries at exactly
    ``capacity`` slots, half stars minus their mean: the store without a
    tile, with its float32 tile and with its code tile."""

    rng = np.random.default_rng(seed)
    lin = rng.choice(mb * nb, size=nnz, replace=False)
    rows, cols = lin // nb, lin % nb
    vals = rng.integers(2, 11, size=nnz).astype(np.float32) * 0.5
    vals -= np.float32(vals.mean())
    sp, _ = from_entries(rows, cols, vals, mb, nb, 1, 1, bucket=capacity,
                         headroom=capacity - nnz)
    sp = drop_tile(sp)
    return sp, with_tile(sp, "f32"), with_tile(sp, "codes")


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
    ap.add_argument("--block", choices=sorted(BLOCKS), default="ml1m")
    ap.add_argument("--mb", type=int)
    ap.add_argument("--nb", type=int)
    ap.add_argument("--rank", type=int)
    ap.add_argument("--capacity", type=int)
    ap.add_argument("--fill", type=float)
    ap.add_argument("--levels", type=int, default=4)
    ap.add_argument("--visits", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    for k, v in BLOCKS[args.block].items():
        if getattr(args, k) is None:
            setattr(args, k, v)

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
        stores = block_store(args.mb, args.nb, cap, nnz, seed=lvl)
        seg, f32, codes = (
            visit_seconds(jax.tree.map(lambda a: a[0, 0], sp.entries), u, w,
                          args.visits, args.reps) * 1e6
            for sp in stores)
        rows.append({"capacity": cap, "nnz": nnz,
                     "capacity_density": cap / (args.mb * args.nb),
                     "segment_us": seg, "f32_tile_us": f32,
                     "code_tile_us": codes})
        print(f"capacity {cap:6d} ({rows[-1]['capacity_density']:.5f}): "
              f"segment {seg:9.1f} us  f32 tile {f32:9.1f} us  "
              f"code tile {codes:9.1f} us", flush=True)
    out = {"device": {"platform": dev.platform,
                      "device_kind": dev.device_kind},
           "block": [args.mb, args.nb], "rank": args.rank, "rows": rows}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
