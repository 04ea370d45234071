"""``FaultPlan`` — deterministic, seed-keyed fault injection for gossip.

The paper's setting is a network of agents with no central coordinator;
real deployments of that shape lose messages, straggle, and corrupt
state.  This module is the *fault model*: a pure-function description of
which halo edges fail at which round, so every injected failure replays
bit-exactly — chaos runs are reproducible experiments, not flaky tests.

Every decision is a function of ``(key, round, edge)`` only:

    plan = FaultPlan(key=0, p_drop_edge=0.2, p_straggle=0.05)
    drops, straggles = plan.edge_events(rnd, edge_index)   # (4,) bools each

``edge_index`` identifies the *receiver* (its linear device-grid index);
the 4 lanes are the halo directions in ``core.gossip.DIRECTIONS`` order
(left_u, right_u, up_w, down_w).  The same call is valid under jit
(traced ``rnd``) and on the host (``replay`` materializes whole masks for
tests and benches) and produces identical booleans either way —
``jax.random.fold_in`` is the only source of randomness.

Failure semantics (wired in ``core/gossip.py``):

* **drop** — the receiver does not get this round's edge message and
  falls back to the *last received* halo; the halo's age (rounds since a
  successful receive) grows.  Past ``max_staleness`` the seam degrades to
  the block's local-only gradient instead of pulling toward stale data.
* **straggle** — the neighbour is late; for the synchronous simulation
  this is a drop (the stale halo is reused) accounted separately.
  ``straggler_scale`` is the modelled slowdown of a straggling round —
  pure accounting (``benchmarks/gossip_faults.py`` derives simulated
  wall-clock from it), never a sleep.
* **nan_at** — a one-shot corruption: at absolute round ``nan_at`` every
  delivered halo message carries NaN (a poisoned update), which
  propagates into the factors and trips the ``DivergenceGuard`` at the
  next eval boundary.  ``refold`` clears it: a restored fit does not
  replay a transient corruption (the fault was in the message, not the
  data).

See DESIGN.md §13 and docs/robustness.md.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Halo directions, in the order core/gossip.py exchanges them.  The age
# lane layout of ``HaloState.age`` and every (4,)-shaped fault mask use
# this order.
DIRECTIONS = ("left_u", "right_u", "up_w", "down_w")

# Sentinel age for "never received" — any bound check fails against it,
# so an un-gossiped zero halo can never pull a seam toward zero.
AGE_NEVER = 1_000_000


@dataclasses.dataclass(frozen=True, eq=False)
class FaultPlan:
    """Seed-keyed fault schedule for the gossip plane.

    ``key`` is an int seed or a jax PRNG key.  Probabilities are per
    round, per directed edge, evaluated independently at each refresh
    round.  ``restart`` tags the recovery generation: :meth:`refold`
    bumps it, so a self-healed fit draws a fresh (but still
    deterministic) fault stream instead of replaying the one that
    killed it.

    Plans compare and hash by identity (``key`` may be an array), so a
    plan can key the cache of compiled gossip steps."""

    key: Any = 0
    p_drop_edge: float = 0.0
    p_straggle: float = 0.0
    straggler_scale: float = 4.0
    nan_at: Optional[int] = None
    restart: int = 0

    def __post_init__(self) -> None:
        for name in ("p_drop_edge", "p_straggle"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"{name} is a probability, got {v}"
                )
        if self.straggler_scale < 1.0:
            raise ValueError(
                f"straggler_scale models a slowdown (>= 1), got "
                f"{self.straggler_scale}"
            )
        if self.nan_at is not None and self.nan_at < 0:
            raise ValueError(f"nan_at must be a round index, got {self.nan_at}")

    # ------------------------------------------------------------------ #
    # the pure fault function
    # ------------------------------------------------------------------ #

    @property
    def prng(self) -> jax.Array:
        """The plan's PRNG key (int seeds are materialized lazily so a
        FaultPlan can be built before jax initializes devices)."""

        k = self.key
        if not isinstance(k, jax.Array) and np.ndim(k) == 0:
            k = jax.random.PRNGKey(int(k))
        if self.restart:
            k = jax.random.fold_in(k, self.restart)
        return k

    def edge_events(self, rnd, edge_index):
        """(dropped, straggled): two (4,) bool vectors for the receiver
        ``edge_index`` at absolute round ``rnd`` — one lane per
        :data:`DIRECTIONS` entry.  Pure in ``(key, rnd, edge_index)``;
        ``rnd``/``edge_index`` may be traced."""

        k = jax.random.fold_in(jax.random.fold_in(self.prng, rnd), edge_index)
        drops = jax.random.uniform(jax.random.fold_in(k, 0), (4,)) \
            < self.p_drop_edge
        straggles = jax.random.uniform(jax.random.fold_in(k, 1), (4,)) \
            < self.p_straggle
        return drops, straggles

    def nan_event(self, rnd):
        """True at the one-shot corruption round (always False when
        ``nan_at`` is unset)."""

        if self.nan_at is None:
            return jnp.asarray(False)
        return jnp.asarray(rnd) == self.nan_at

    # ------------------------------------------------------------------ #
    # replay + recovery
    # ------------------------------------------------------------------ #

    def replay(self, rounds: int, num_edges: int) -> dict:
        """Materialize the full fault schedule on the host: bool arrays of
        shape (rounds, num_edges, 4) for drops and straggles.  This is the
        *same* function the jitted gossip step evaluates — tests and
        benches diff injected-vs-observed counts against it."""

        drops = np.zeros((rounds, num_edges, 4), bool)
        straggles = np.zeros((rounds, num_edges, 4), bool)
        for rnd in range(rounds):
            for e in range(num_edges):
                d, s = self.edge_events(rnd, e)
                drops[rnd, e] = np.asarray(d)
                straggles[rnd, e] = np.asarray(s)
        return {"drops": drops, "straggles": straggles}

    def refold(self, restart: int) -> "FaultPlan":
        """The plan a self-healed fit resumes under: same probabilities,
        the PRNG stream folded by the restart generation, and the one-shot
        ``nan_at`` corruption cleared (transient faults do not replay)."""

        return dataclasses.replace(self, restart=restart, nan_at=None)

    def expected_drops(self, plan, rounds: int) -> float:
        """Analytic E[dropped edges] over ``rounds`` on a ``MeshPlan``'s
        device grid — what the bench compares the observed
        ``gossip_edges_dropped_total`` counter against."""

        return self.p_drop_edge * plan.num_halo_edges * rounds
