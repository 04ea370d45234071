"""From a profiler trace to the numbers the per-layer readers take.

``load`` reads the ``.xplane.pb`` the JAX profiler writes and keeps, per
TPU device, the ``XLA Ops`` events (each with the XLA module whose
execution contains it) and the ``XLA Modules`` events, plus the host's
named spans (``TraceAnnotation``s such as ``bench.window``, ``fit.wave``).
Everything after ``load`` is plain interval arithmetic on those lists, so
tests can hand-build them.  Times are nanoseconds on the trace's clock,
which the host and device planes share.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all)"
    r"(?:-start|-done)?\(")
HOST_SPAN_PREFIXES = ("bench.", "fit.")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str          # short HLO name, e.g. "fusion.16"
    module: str        # XLA module without its hash, e.g. "jit_wave_step"
    start: float
    end: float
    collective: bool


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: dict          # device id -> [Op] sorted by start
    modules: dict      # device id -> [Span] of module executions
    host: list         # [Span] of named host spans


def _short(hlo_text: str) -> str:
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _module_name(name: str) -> str:
    return name.split("(", 1)[0]


def _attach_modules(raw_ops, modules):
    """Name each op by the module execution that contains its start."""

    out, k = [], 0
    for start, end, text in sorted(raw_ops):
        while k < len(modules) and modules[k].end < start:
            k += 1
        mod = (modules[k].name if k < len(modules)
               and modules[k].start <= start else "?")
        body = text.split(" = ", 1)[-1]
        out.append(Op(_short(text), mod, start, end,
                      bool(COLLECTIVE.search(body))))
    return out


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest one under a directory)."""

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            raw, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [Span(_module_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    raw = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events]
            mods.sort(key=lambda s: s.start)
            modules[dev] = mods
            ops[dev] = _attach_modules(raw, mods)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIXES):
                        host.append(Span(e.name, e.start_ns, e.end_ns))
    host.sort(key=lambda s: s.start)
    return Trace(ops, modules, host)


# --------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------- #


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint sorted union of ``intervals`` clipped to [lo, hi]."""

    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""

    out, k = [], 0
    for s, e in a:
        cur = s
        while k < len(b) and b[k][1] <= cur:
            k += 1
        j = k
        while j < len(b) and b[j][0] < e:
            if b[j][0] > cur:
                out.append([cur, b[j][0]])
            cur = max(cur, b[j][1])
            j += 1
        if cur < e:
            out.append([cur, e])
    return out


def window(trace: Trace, name: str = "bench.window"):
    """(start, end) of the named host span that brackets the window."""

    spans = [s for s in trace.host if s.name == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return spans[0].start, spans[-1].end


def busy(ops, lo, hi) -> list:
    return union(((o.start, o.end) for o in ops), lo, hi)


def idle_share(trace: Trace, lo, hi) -> float:
    """1 - busy / window, the mean over the devices in the trace."""

    shares = [1.0 - length(busy(ops, lo, hi)) / (hi - lo)
              for ops in trace.ops.values()]
    return sum(shares) / len(shares)


def busy_seconds(trace: Trace, lo, hi) -> float:
    """Seconds in which an op ran, the mean over devices."""

    vals = [length(busy(ops, lo, hi)) for ops in trace.ops.values()]
    return sum(vals) / len(vals) * 1e-9


def dispatches(trace: Trace, lo, hi) -> float:
    """Module executions starting inside [lo, hi), the mean over devices."""

    vals = [sum(lo <= m.start < hi for m in mods)
            for mods in trace.modules.values()]
    return sum(vals) / len(vals)


def compute_seconds(trace: Trace, lo, hi, modules=None) -> float:
    """Seconds in which a non-collective op ran (optionally only ops of
    ``modules``, matched by prefix), the mean over devices."""

    vals = []
    for ops in trace.ops.values():
        sel = [o for o in ops if not o.collective
               and (modules is None or o.module.startswith(tuple(modules)))]
        vals.append(length(busy(sel, lo, hi)))
    return sum(vals) / len(vals) * 1e-9


def top_ops(trace: Trace, lo, hi, n: int = 10) -> list:
    """[["module/op", seconds], ...]: the ops that took most device time,
    summed over devices and divided by their number."""

    tot: dict = {}
    for ops in trace.ops.values():
        for o in ops:
            s, e = max(o.start, lo), min(o.end, hi)
            if e > s:
                key = f"{o.module}/{o.name}"
                tot[key] = tot.get(key, 0.0) + (e - s)
    k = max(len(trace.ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / k] for name, ns in best]


def idle_gaps(trace: Trace, lo, hi, n: int = 10) -> list:
    """[["host span", seconds], ...]: the longest gaps on the first device
    with no op running, each named by the innermost host span open at the
    gap's middle (``"none"`` where no span is)."""

    dev = min(trace.ops)
    gaps = subtract([[lo, hi]], busy(trace.ops[dev], lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        open_ = [h for h in trace.host if h.start <= mid < h.end
                 and h.name != "bench.window"]
        name = (min(open_, key=lambda h: h.end - h.start).name
                if open_ else "none")
        out.append([name, (e - s) * 1e-9])
    return out
