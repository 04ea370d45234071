"""Share of the traced fit window in which no op ran on the device
(1 - union of op intervals / window), the mean over the chips used."""

import trace_reduce as tr


def read(run):
    if run["trace"] is None or run["window"].get("rounds") is None:
        return None
    return tr.idle_share(run["trace"], run["lo"], run["hi"])
