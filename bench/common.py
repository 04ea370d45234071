"""What every cell shares: finding its files by name, the peaks table,
the compile clock, seeds, and the result line.

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<traffic>.json``) and the limits its correctness numbers
are held to (``bench/limits/<cell>.json``).  Nothing here names a cell, so
a later cell is added by adding files.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer TPU chips than the cell asks for."""


class UnknownDevice(KeyError):
    """The device kind has no row in ``bench/peaks.json``."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root=ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its three files."""

    root = pathlib.Path(root)
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
    )


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChip`: a run never
    carries on on the CPU."""

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (default devices: "
                     f"{devs[0].platform} x{len(devs)})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peaks(device_kind: str, table=None) -> dict:
    """The published peaks of one chip of ``device_kind``."""

    table = table if table is not None else load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise UnknownDevice(
            f"device kind {device_kind!r} has no row in bench/peaks.json "
            f"(rows: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def key_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` drawn from any
    non-negative ``--seed`` (which may exceed 32 bits)."""

    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


class CompileClock:
    """Seconds XLA spent compiling, or loading from the persistent cache,
    and cache hits, summed from JAX's own monitoring events.  ``compiles``
    counts backend compiles, so a compile inside a window shows."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.compiles = 0

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)


class GcClock:
    """Pauses of Python's garbage collector while it is entered, per
    generation: how many, their total and the longest, in ms.  Every
    thread of the process waits while one runs."""

    def __init__(self):
        self.t0 = None
        self.pauses = {g: [] for g in range(3)}

    def _on_gc(self, phase: str, info: dict) -> None:
        import time

        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self.t0)
            self.t0 = None

    def __enter__(self) -> "GcClock":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self) -> dict:
        return {f"gen{g}": {"n": len(v), "total_ms": sum(v) * 1e3,
                            "max_ms": max(v, default=0.0) * 1e3}
                for g, v in self.pauses.items()}


def peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``."""

    vals = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            vals.append(int(stats["peak_bytes_in_use"]))
    return max(vals) if vals else None


class NullSpan:
    """A span that records nothing: the window's annotations when the
    profiler is off."""

    def __init__(self, *_):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def log_line(tag: str, **fields) -> None:
    """One diagnostics line on standard output, before the result."""

    print(json.dumps({"bench": tag, **fields}, default=float), flush=True)


def check_lines(checks: dict) -> None:
    """Each compared number beside its limit, as the last lines on
    standard error."""

    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()


class Phases:
    """Seconds of each set-up phase, and the compile seconds and cache
    hits inside it, for the ``setup`` diagnostics line."""

    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.split: dict = {}

    def __call__(self, name: str):
        return _Phase(self, name)


class _Phase:
    def __init__(self, owner: Phases, name: str):
        self.owner, self.name = owner, name

    def __enter__(self):
        import time

        self.t0 = time.perf_counter()
        self.c0 = self.owner.clock.seconds
        self.h0 = self.owner.clock.cache_hits
        return self

    def __exit__(self, exc_type, *_):
        import time

        if exc_type is None:
            self.owner.split[self.name] = {
                "s": time.perf_counter() - self.t0,
                "compile_s": self.owner.clock.seconds - self.c0,
                "cache_hits": self.owner.clock.cache_hits - self.h0,
            }
