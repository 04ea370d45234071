"""Device time of the serving bucket executables (module
``jit_recommend_topk``) in the traced window over the requests completed
there, in ms."""

import trace_reduce as tr

MODULES = ("jit_recommend_topk",)


def read(run):
    done = run["window"].get("completed")
    if run["trace"] is None or not done:
        return None
    s = tr.compute_seconds(run["trace"], run["lo"], run["hi"], MODULES)
    return 1e3 * s / done if s > 0 else None
