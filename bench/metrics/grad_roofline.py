"""Least time of a round's gradient work (``workcount``: the larger of
FLOPs over peak and bytes over HBM bandwidth) over the device time per
round of the fit programs' non-collective ops, in %."""

import trace_reduce as tr


def read(run):
    rounds = run["window"].get("rounds")
    if run["trace"] is None or not rounds or not run["least"]:
        return None
    busy = tr.compute_seconds(run["trace"], run["lo"], run["hi"])
    if busy <= 0:
        return None
    return 100.0 * run["least"][0] * rounds / busy
