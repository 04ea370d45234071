"""Per-backend segment-reduce chunk selection (``EngineOptions.chunk=None``).

PR 2 made the two-level segment reduction's chunk size a tunable; PR 3
exposed it as ``EngineOptions.chunk`` and taught
``benchmarks/sparse_vs_dense.py --chunks`` to sweep it.  This module
closes the loop: ``resolve_chunk(None)`` consults the **committed** sweep
results (``benchmarks/BENCH_sparse.json``) for the running backend and
picks the chunk minimizing total gradient time across the swept
densities; with no committed sweep for this backend it falls back to a
per-backend default.  Explicit chunks always win — ``resolve_chunk(c)``
is the identity for ``c is not None``.

The lookup is cached per backend and reads one small JSON at most once
per process; everything stays deterministic within a run (the resolved
chunk is a trace-time static, exactly like a hand-passed one).
"""

from __future__ import annotations

import functools
import json
import os

from repro.kernels.sddmm.segment import SEG_CHUNK

# defaults when no committed sweep covers the backend.  tpu: one block
# visit on a TPU v5 lite (benchmarks/segment_stages.py, DESIGN.md §3),
# two runs of best-of-5 at chunks 64 / 128 / 256: 18.37, 18.22 / 18.87,
# 19.04 / 18.37, 18.34 ms at the ML-10M block (17,892 x 2,671, rank 64,
# 573,440 slots), 1.86 / 1.66 / 1.83 ms at the ML-1M block (1,510 x 927,
# rank 32, 61,440 slots).  64 and 256 are within the runs' spread at
# ML-10M and 128 is 3 % slower there; 256, at which the ML-10M cell was
# measured, is kept, since the ML-10M block is where the segment engine
# runs (ML-1M blocks carry a dense tile on the TPU, sparse/store.py
# tile_rule).  cpu keeps the original SEG_CHUNK
# scale; gpu is unmeasured.
FALLBACK_CHUNK = {"cpu": SEG_CHUNK, "gpu": 64, "tpu": 256}

# repo-relative location of the committed sweep (absent in installed
# trees — the fallback table then applies)
_SWEEP_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    *([os.pardir] * 4), "benchmarks", "BENCH_sparse.json",
)


def _sweep_table(path: str) -> dict[str, int]:
    """backend -> best chunk from a committed sparse_vs_dense --chunks run.

    The bench records per-density ``chunk_sweep_ms``; the winner is the
    chunk with the lowest *total* time over all swept densities (one knob
    serves every density, so optimize the sum, not a single row)."""

    with open(path) as f:
        data = json.load(f)
    totals: dict[str, float] = {}
    for row in data.get("rows", []):
        for chunk, ms in row.get("chunk_sweep_ms", {}).items():
            totals[chunk] = totals.get(chunk, 0.0) + float(ms)
    if not totals:
        return {}
    return {data.get("backend", "cpu"): int(min(totals, key=totals.get))}


@functools.lru_cache(maxsize=None)
def _committed_sweep() -> dict[str, int]:
    try:
        return _sweep_table(_SWEEP_PATH)
    except (OSError, ValueError, KeyError):
        return {}


def resolve_chunk(chunk: int | None, backend: str | None = None) -> int:
    """The segment-reduce chunk to compile with.

    ``chunk`` not None → returned unchanged.  Otherwise: the committed
    sweep's winner for ``backend`` (default: the running jax backend),
    else the hardcoded per-backend fallback, else ``SEG_CHUNK``."""

    if chunk is not None:
        return chunk
    if backend is None:
        import jax

        backend = jax.default_backend()
    best = _committed_sweep().get(backend)
    if best is not None:
        return best
    return FALLBACK_CHUNK.get(backend, SEG_CHUNK)
