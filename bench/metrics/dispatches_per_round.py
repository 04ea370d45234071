"""XLA program executions per fit round in the traced window (the mean
over the chips used), from the device's ``XLA Modules`` line."""

import trace_reduce as tr


def read(run):
    rounds = run["window"].get("rounds")
    if run["trace"] is None or not rounds:
        return None
    return tr.dispatches(run["trace"], run["lo"], run["hi"]) / rounds
