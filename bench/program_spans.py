"""What the per-layer readers take from the program's own spans.

The serving worker records ``serve.request``, ``serve.dispatch`` and
``serve.fetch`` into the registry (``span_seconds{name=...}``), which
``serve_driver.py`` resets after warm-up; the Wave loop's ``fit.round`` and
``fit.cost`` are read as host spans of the trace.  Every function returns
``None`` where the program records no such span.
"""

from __future__ import annotations

import trace_reduce as tr


def _hist(run, name):
    h = run["obs"].get("histograms", {}).get(f"span_seconds{{name={name}}}")
    return h if h and h.get("count") else None


def ms_per_request(run, name):
    """Seconds in the span ``name`` over the ``serve.request`` spans'
    count, in ms."""

    req, h = _hist(run, "serve.request"), _hist(run, name)
    if req is None or h is None:
        return None
    return 1e3 * h["sum"] / req["count"]


def idle_ms_per_round(run, name):
    """Device-idle time inside the union of the host spans ``name`` over
    the window's rounds, in ms, the mean over the chips used."""

    rounds = run["window"].get("rounds")
    trace = run["trace"]
    if trace is None or not rounds:
        return None
    spans = [(s.start, s.end) for s in trace.host if s.name == name]
    if not spans:
        return None
    lo, hi = run["lo"], run["hi"]
    inside = tr.union(spans, lo, hi)
    idle = [tr.length(tr.subtract(inside, tr.busy(ops, lo, hi)))
            for ops in trace.ops.values()]
    return 1e-6 * sum(idle) / len(idle) / rounds
