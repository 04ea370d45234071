"""Device-true timed regions + Perfetto trace capture for jax workloads.

``time.perf_counter()`` around a jitted call measures *dispatch*, not
compute: jax returns futures, and the work finishes whenever the device
drains its queue.  Every hand-rolled timer in this repo that forgot a
``block_until_ready`` reported dispatch skew — :func:`span` is the one
primitive that gets it right:

    from repro import obs

    with obs.span("gossip.rounds") as sp:
        carry = step(problem, carry)
        sp.outputs(carry)              # declare what must be materialized

    sp.seconds       # device-true: clock stops after block_until_ready
    sp.host_seconds  # dispatch-only wall, for async-depth diagnosis

``seconds`` lands in the default registry as the histogram
``span_seconds{name=...}``, so any snapshot carries p50/p99 per region.
``annotate=True`` additionally wraps the region in
``jax.profiler.TraceAnnotation`` so spans line up by name in a Perfetto
trace captured via :func:`trace`; keyword metadata rides along as the
event's stats (the name stays plain, so matching by name still works):

    with obs.trace("/tmp/trace"):           # then: perfetto ui, load the
        with obs.span("fit", annotate=True) as sp:   # .trace.json.gz
            ...
        with obs.span("serve.request", annotate=True, rid=7):
            ...

``device_sync`` is the exported sync primitive (``BenchLogger`` uses it so
its eval stamps and span timings agree — same internals, same semantics).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Optional

from repro.obs import registry as _reg


def device_sync(tree: Any) -> Any:
    """Block until every jax array in ``tree`` is materialized; non-array
    leaves (floats, ints, None) pass through untouched.  Returns ``tree``.

    The one definition of "the work is actually done" that every timer in
    the repo shares (spans, ``BenchLogger``, benches)."""

    if tree is None:
        return tree
    import jax

    try:
        return jax.block_until_ready(tree)
    except (TypeError, ValueError):
        # pytrees with non-blockable leaves: sync leaf-by-leaf
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()
        return tree


class Span:
    """One timed region; use via :func:`span`.

    ``outputs(x)`` declares the arrays whose materialization defines the
    region's end — the exit path blocks on them *before* stopping the
    clock, so ``seconds`` is device-true.  Without declared outputs the
    span degrades to host wall-clock (still recorded; ``host_seconds ==
    seconds``)."""

    __slots__ = ("name", "registry", "annotate", "meta", "_outputs", "_t0",
                 "host_seconds", "seconds", "_annotation")

    def __init__(self, name: str, registry: Optional[_reg.Registry] = None,
                 annotate: bool = False, **meta: Any):
        self.name = name
        self.registry = registry if registry is not None else _reg.get_registry()
        self.annotate = annotate
        self.meta = meta
        self._outputs: Any = None
        self._annotation = None
        self.host_seconds: Optional[float] = None
        self.seconds: Optional[float] = None

    def outputs(self, tree: Any) -> Any:
        """Declare (accumulate) the arrays that end this span; returns the
        tree unchanged so call sites can wrap a producing expression."""

        if self._outputs is None:
            self._outputs = tree
        else:
            self._outputs = (self._outputs, tree)
        return tree

    def __enter__(self) -> "Span":
        if self.annotate:
            try:
                import jax

                self._annotation = jax.profiler.TraceAnnotation(
                    self.name, **self.meta)
                self._annotation.__enter__()
            except Exception:       # profiler unavailable: time anyway
                self._annotation = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.host_seconds = time.perf_counter() - self._t0
        if exc_type is None and self._outputs is not None:
            device_sync(self._outputs)
        self.seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is None and self.registry.enabled:
            self.registry.histogram(
                "span_seconds", name=self.name).observe(self.seconds)


def span(name: str, registry: Optional[_reg.Registry] = None,
         annotate: bool = False, **meta: Any) -> Span:
    """Context manager: a named, registry-recorded, device-true timer.
    ``meta`` (e.g. ``rid=7``) is attached to the trace event when
    ``annotate=True``; the registry keys on ``name`` alone."""

    return Span(name, registry=registry, annotate=annotate, **meta)


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a Perfetto/TensorBoard trace of the enclosed region into
    ``log_dir`` (``jax.profiler.trace``); spans entered with
    ``annotate=True`` show up as named slices.  Load the
    ``*.trace.json.gz`` under ``log_dir/plugins/profile/*/`` in
    https://ui.perfetto.dev.  A profiler that cannot start raises: a
    run asked to be traced never silently yields no trace."""

    import jax

    with jax.profiler.trace(str(log_dir)):
        yield
