"""The work a round needs, counted from shapes and stored nnz.

Counted from the algorithm, never from an implementation: for each block
visit a round makes, every stored rating costs a residual (a dot of two
rank-r rows) and two rank-r axpys into the factor gradients, 6r FLOPs, and
reads its entry fields (row, column, value, validity and the column-order
permutation, 4 bytes each) and the two factor rows it touches.  Padding
slots and one-hot or triangular selections of an implementation are not
counted, so they show as lost share of the roofline.

A ``wave`` round visits the 3 blocks of each of the 2(p-1)(q-1)
structures once.
"""

from __future__ import annotations

import numpy as np

ENTRY_FIELD_BYTES = 5 * 4
FACTOR_BYTES = 4


def structure_blocks(p: int, q: int) -> list:
    """The three blocks of every structure of a p x q grid."""

    out = []
    for i in range(p - 1):
        for j in range(q - 1):
            out.append(((i, j), (i + 1, j), (i, j + 1)))
    for i in range(1, p):
        for j in range(1, q):
            out.append(((i, j), (i - 1, j), (i, j - 1)))
    return out


def visited_entries(schedule: str, nnz: np.ndarray) -> int:
    """Stored ratings summed over the block visits of one round."""

    nnz = np.asarray(nnz)
    if schedule == "wave":
        p, q = nnz.shape
        return int(sum(nnz[b] for s in structure_blocks(p, q) for b in s))
    raise ValueError(f"unknown schedule {schedule!r}")


def round_work(schedule: str, nnz: np.ndarray, rank: int) -> dict:
    """FLOPs and bytes one round needs."""

    e = visited_entries(schedule, nnz)
    return {"entries": e, "flops": 6 * rank * e,
            "bytes": e * (ENTRY_FIELD_BYTES + 2 * rank * FACTOR_BYTES)}


def least_seconds(work: dict, peak: dict, chips: int = 1) -> tuple:
    """(seconds, bound): the larger of FLOPs over the chips' peak FLOP/s
    and bytes over their HBM bandwidth, and which of the two it is."""

    t_flops = work["flops"] / (chips * peak["bf16_flops_per_s"])
    t_bytes = work["bytes"] / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
