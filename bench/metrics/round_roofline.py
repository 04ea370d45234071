"""Least time of a round's gradient work over the wall time per round of
the traced window, in %: the whole round's share of the roofline."""


def read(run):
    rounds = run["window"].get("rounds")
    if run["trace"] is None or not rounds or not run["least"]:
        return None
    wall = (run["hi"] - run["lo"]) * 1e-9
    return 100.0 * run["least"][0] * rounds / wall
