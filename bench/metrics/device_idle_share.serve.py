"""Share of the traced serving window in which no op ran on the device
(1 - union of op intervals / window)."""

import trace_reduce as tr


def read(run):
    if run["trace"] is None or run["window"].get("completed") is None:
        return None
    return tr.idle_share(run["trace"], run["lo"], run["hi"])
