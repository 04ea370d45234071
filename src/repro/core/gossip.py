"""Distributed gossip matrix completion: shard_map + collective-permute.

The p×q block grid is tiled over a 2-D slice of the device mesh
(``row_axes`` × ``col_axes``; multi-pod runs pass ``("pod","data")`` as the
row axes so the grid spans pods).  Per round each device:

  1. exchanges factor *edges* with its 4 mesh neighbours via
     ``jax.lax.ppermute`` — the TPU-native gossip primitive (one ICI hop on
     the torus, no all-reduce, no central server: DESIGN.md §2),
  2. computes the full local gradient of the collapsed objective L
     (waves.full_gradients) using the halos for seam consensus pairs,
  3. takes the γ_t SGD step.

Bounded staleness (``staleness k``): halos are refreshed every k-th round
and reused in between — a straggling neighbour delays only its seam, never
the pod.  Optional int8/top-k message compression (compress.py) with error
feedback rides on the halo exchange.

Fault tolerance (``faults=FaultPlan(...)``, DESIGN.md §13): a dropped or
straggling edge message leaves the receiver on its **last received** halo;
``HaloState.age`` tracks rounds-since-receive per direction, and past
``max_staleness`` missed refreshes the seam degrades to the block's
local-only gradient instead of pulling toward stale (or never-received)
data.  Fault decisions are pure functions of ``(key, round, edge)``
(``repro.faults.FaultPlan``), so chaos runs replay bit-exactly; with
``p_drop=0`` the fault path is bit-identical to the fault-free one
(pinned by test).  Drop/stale/straggle counts accumulate in the carry
(``FaultStats``) for the ``Gossip`` schedule to stream into ``repro.obs``.

Asynchronous stochastic rounds (``async_rounds=True``, DESIGN.md §15): the
NOMAD-style non-blocking regime.  Halo exchange happens only every
``exchange_every``-th round; in between each block updates against its
neighbours' last *received* halos while ``HaloState.age`` counts the
rounds since each receive — planned staleness rides the exact same
age/gate machinery as faults, so the two compose (a dropped exchange just
extends the age run until the next successful one, bounded by
``max_staleness``).  ``batch=`` additionally makes each round's
f-gradients stochastic: the step consumes a per-round minibatch store plus
the ``minibatch_grad_scale`` correction (nnz/batch per block), so a round
costs O(batch) instead of O(nnz) per device.  With ``exchange_every=1,
max_staleness=0, batch=None`` the async step is bit-identical to the
synchronous one (pinned by test).

Every step here lowers to: 4 collective-permutes of (edge × r) floats +
purely local compute.  That is the paper's communication pattern, verbatim.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import GossipMCConfig
from repro.core import objective as obj
from repro.core.state import Problem, State
from repro.core import compress as C
from repro.faults.plan import AGE_NEVER
from repro.mesh.plan import MeshPlan
from repro.sparse.store import SparseProblem, drop_tile


class HaloState(NamedTuple):
    """Cached neighbour edges (refreshed every ``staleness`` rounds).

    ``age`` counts *missed refreshes* since each direction's halo was last
    successfully received: 0 = fresh, k = k refresh rounds dropped or
    straggled in a row, ``AGE_NEVER`` = never received (the init sentinel,
    so zero-initialized halos can never pull a seam toward zero).  Lanes
    follow ``repro.faults.DIRECTIONS`` order; the array is shaped on the
    block grid ``(p, q, 4)`` so it shards exactly like the factor stacks
    and ``init_carry`` needs no device count.  Ages move under a
    ``FaultPlan`` (missed refreshes) and under ``async_rounds`` (planned
    exchange skipping counts rounds-since-receive) — the plain synchronous
    path threads them through untouched."""

    left_u: jax.Array    # left neighbour's last block-col U   (pl, mb, r)
    right_u: jax.Array   # right neighbour's first block-col U (pl, mb, r)
    up_w: jax.Array      # upper neighbour's last block-row W  (ql, nb, r)
    down_w: jax.Array    # lower neighbour's first block-row W (ql, nb, r)
    age: jax.Array       # rounds since last receive            (pl, ql, 4) i32


class FaultStats(NamedTuple):
    """Per-device fault counters accumulated inside the jitted step.

    Each leaf is an int32 array on the block grid ``(p, q)``; a device
    records into its *first local block* only, so the host-side sum over
    the whole array is the true cross-device total (no per-block
    double-count).  The ``Gossip`` schedule diffs these between chunks
    into the obs counters ``gossip_edges_dropped_total`` /
    ``gossip_stale_rounds_total`` / ``gossip_straggled_edges_total``."""

    dropped: jax.Array    # edge messages lost outright
    stale: jax.Array      # rounds computed on >=1 fault-stale halo
    straggled: jax.Array  # edge messages late (reused-stale, counted apart)


class GossipCarry(NamedTuple):
    state: State
    halos: HaloState
    ef_u_last: jax.Array  # error-feedback residuals (top-k/int8 compression)
    ef_u_first: jax.Array
    ef_w_last: jax.Array
    ef_w_first: jax.Array
    rnd: jax.Array        # absolute gossip round (the FaultPlan clock), () i32
    stats: FaultStats


def _shift(x, axis_name, mesh_size, direction: int):
    """ppermute by one along a (possibly composite) mesh axis.

    direction=+1: each device receives its *left* (lower-index) neighbour's
    message; boundary devices receive zeros (masked by the caller)."""

    perm = [(i, i + direction) for i in range(mesh_size)
            if 0 <= i + direction < mesh_size]
    return jax.lax.ppermute(x, axis_name, perm)


def exchange_halos(U, W, row_axes, col_axes, compression="none",
                   ef=None, topk_fraction=0.25, age=None):
    """One gossip exchange; returns HaloState + updated error feedback.

    Messages: my last/first block column of U (along col axes) and my
    last/first block row of W (along row axes).  ``age`` is threaded into
    the returned HaloState untouched (fault handling merges/ages it in
    ``make_gossip_step``); when omitted, a fresh all-received age of 0 is
    used — every message of this exchange did arrive."""

    dc = jax.lax.axis_size(col_axes)
    dr = jax.lax.axis_size(row_axes)
    msgs = {
        "u_last": U[:, -1],   # -> right neighbour's left_u
        "u_first": U[:, 0],   # -> left neighbour's right_u
        "w_last": W[-1],      # -> lower neighbour's up_w
        "w_first": W[0],      # -> upper neighbour's down_w
    }
    new_ef = {}
    if compression != "none":
        for k in msgs:
            st = C.CompressState(ef[k]) if ef is not None else None
            msgs[k], stn = C.compress_message(
                msgs[k], compression, st, topk_fraction
            )
            new_ef[k] = stn.residual if stn is not None else None
    if age is None:
        age = jnp.zeros(U.shape[:2] + (4,), jnp.int32)
    halos = HaloState(
        left_u=_shift(msgs["u_last"], col_axes, dc, +1),
        right_u=_shift(msgs["u_first"], col_axes, dc, -1),
        up_w=_shift(msgs["w_last"], row_axes, dr, +1),
        down_w=_shift(msgs["w_first"], row_axes, dr, -1),
        age=age,
    )
    return halos, new_ef


def _local_gradients(problem: Problem, U, W, halos: HaloState,
                     row_axes, col_axes, rho, lam, use_kernel=False,
                     method="segment", chunk=None, gates=None,
                     f_scale=None):
    """∇L on the local tile, seam terms from halos, boundaries masked.

    ``f_scale`` (minibatch rounds): per-block factor multiplying only the
    f-part of the gradient — ``minibatch_grad_scale`` hands nnz/batch so
    the stochastic gradient is an unbiased estimate of the full one.  The
    consensus/regularization terms are deterministic and stay unscaled.

    ``gates`` (fault/async path only): 4 scalar bools in DIRECTIONS order —
    edge-exists AND halo-age within ``max_staleness``.  A gated-off seam
    contributes nothing: the block degrades to its local-only gradient
    instead of pulling toward stale/never-received data.  Gating
    substitutes the *halo operand* (``where(gate, halo, own_edge)`` makes
    the seam difference exactly x - x = 0) rather than masking the
    product, for two reasons: an injected NaN halo would leak through a
    multiply mask (0.0 * NaN = NaN), and keeping the seam expression
    token-identical to the ungated path preserves XLA's fusion choices —
    with every gate open the result is bit-identical to ``gates=None``
    (pinned by test)."""

    from repro.core.waves import full_gradients

    # interior (within-tile) consensus + f + reg — rho halved like
    # full_gradient_step? No: damping is applied by the caller via step
    # scale; here we produce the exact ∇L of the local restriction.
    gU, gW = full_gradients(problem, U, W, rho=rho, lam=lam,
                            use_kernel=use_kernel, method=method, chunk=chunk,
                            f_scale=f_scale)

    c = jax.lax.axis_index(col_axes)
    r_ = jax.lax.axis_index(row_axes)
    dc = jax.lax.axis_size(col_axes)
    dr = jax.lax.axis_size(row_axes)

    if gates is None:
        left_h, right_h = halos.left_u, halos.right_u
        up_h, down_h = halos.up_w, halos.down_w
    else:
        g_left, g_right, g_up, g_down = gates
        left_h = jnp.where(g_left, halos.left_u, U[:, 0])
        right_h = jnp.where(g_right, halos.right_u, U[:, -1])
        up_h = jnp.where(g_up, halos.up_w, W[0])
        down_h = jnp.where(g_down, halos.down_w, W[-1])

    # seam pair (left neighbour's last col, my first col): d/dU_mine = 2ρ(mine-theirs)
    left_valid = (c > 0).astype(U.dtype)
    gU = gU.at[:, 0].add(2.0 * rho * left_valid * (U[:, 0] - left_h))
    right_valid = (c < dc - 1).astype(U.dtype)
    gU = gU.at[:, -1].add(2.0 * rho * right_valid * (U[:, -1] - right_h))

    up_valid = (r_ > 0).astype(W.dtype)
    gW = gW.at[0].add(2.0 * rho * up_valid * (W[0] - up_h))
    down_valid = (r_ < dr - 1).astype(W.dtype)
    gW = gW.at[-1].add(2.0 * rho * down_valid * (W[-1] - down_h))
    return gU, gW


def make_gossip_step(
    mesh,
    spec_pq: tuple[int, int],
    cfg: GossipMCConfig,
    *,
    plan: MeshPlan | None = None,
    row_axes="data",
    col_axes="model",
    staleness: int = 1,
    compression: str = "none",
    topk_fraction: float = 0.25,
    use_kernel: bool = False,
    steps_per_call: int = 1,
    layout: str = "dense",
    method: str = "segment",
    chunk: int | None = None,
    faults=None,
    max_staleness: int = 3,
    async_rounds: bool = False,
    exchange_every: int = 1,
    batch: int | None = None,
):
    """Build the jitted distributed gossip round.

    Returns (step_fn, in_shardings) where
    ``step_fn(problem, carry) -> carry`` advances ``steps_per_call`` rounds.
    Placement comes from the ``MeshPlan``: every grid-stacked array shards
    on its leading (p, q) dims per ``plan.grid_spec``.  Passing
    ``mesh``/``row_axes``/``col_axes`` without a plan builds the
    equivalent plan — ``plan`` wins when both are given.

    ``layout="sparse"`` expects a ``SparseProblem`` (padded-COO store) and
    runs each round's f-gradients on nnz-proportional compute; the halo
    exchange is identical in both layouts — only factor edges ever travel.
    Hand a store already placed by ``ShardedEntries``/``plan.place_entries``
    and the jitted step consumes the device-resident shards directly (no
    input resharding).  ``method``/``chunk`` select the sparse gradient
    engine (see ``repro.mc.EngineOptions``).  The session-level entry
    point is ``repro.mc.Trainer.fit(problem, schedule=Gossip(...))``.

    ``faults`` takes a ``repro.faults.FaultPlan`` (duck-typed: anything
    with ``edge_events``/``nan_event``/``nan_at``/``p_drop_edge``); each
    round it draws drop/straggle masks keyed on ``(key, carry.rnd,
    receiver_device)`` and a missed edge keeps the last received halo.
    Once a direction's ``HaloState.age`` exceeds ``max_staleness`` missed
    refreshes, that seam is gated out of the gradient entirely.  With
    ``faults=None`` the legacy code path runs verbatim (bit-identical).
    Faults + compression is rejected: dropping a compressed message after
    its error-feedback residual update would corrupt the EF invariant.

    ``async_rounds=True`` is the NOMAD-style non-blocking regime
    (DESIGN.md §15): exchanges fire only when ``carry.rnd %
    exchange_every == 0`` (keyed on the *absolute* round, so chunked calls
    and checkpoint resume see the same schedule) and skipped rounds
    compute against the last received halos with ``HaloState.age``
    counting every round since the receive — planned skips age exactly
    like fault drops, and both compose (``faults=`` draws its events on
    exchange rounds only).  A direction past ``max_staleness`` gates its
    seam out.  ``exchange_every=1, max_staleness=0`` is bit-identical to
    the synchronous step (pinned by test).

    ``batch=<int>`` makes the round stochastic: the step's signature
    becomes ``step_fn(problem, f_scale, carry)`` where ``problem`` is a
    per-round minibatch store (``MinibatchStream.batch_at``) and
    ``f_scale`` is the ``minibatch_grad_scale`` of the *full* store —
    (p, q) nnz/batch, sharded like the grid — making the stochastic
    f-gradient unbiased.  Requires ``layout="sparse"`` and
    ``steps_per_call=1`` (each round consumes a fresh minibatch).
    """

    p, q = spec_pq
    if exchange_every < 1:
        raise ValueError(f"exchange_every must be >= 1, got {exchange_every}")
    if async_rounds and staleness != 1:
        raise ValueError(
            "async_rounds replaces the synchronous staleness schedule with "
            "exchange_every; leave staleness=1"
        )
    if not async_rounds and exchange_every != 1:
        raise ValueError(
            "exchange_every > 1 is the asynchronous regime; set "
            "async_rounds=True (synchronous halo reuse is staleness=k)"
        )
    if batch is not None:
        if layout != "sparse":
            raise ValueError(
                "minibatch gossip (batch=) needs the sparse layout: the "
                "minibatch is a sampled sparse store"
            )
        if steps_per_call != 1:
            raise ValueError(
                "minibatch gossip consumes one sampled store per round; "
                "steps_per_call must be 1"
            )
    if faults is not None and compression != "none":
        raise ValueError(
            "faults cannot be combined with message compression: a dropped "
            "compressed message would desynchronize the error-feedback "
            "residuals (the sender already folded the residual update in)"
        )
    if plan is None:
        plan = MeshPlan.build(p, q, mesh=mesh, row_axes=row_axes,
                              col_axes=col_axes)
    elif (plan.p, plan.q) != (p, q):
        raise ValueError(
            f"plan is for a {plan.p}x{plan.q} grid, problem has {p}x{q}"
        )
    mesh = plan.mesh
    row_axes = plan.row_spec_axes
    col_axes = plan.col_spec_axes
    rho, lam, a, b = cfg.rho, cfg.lam, cfg.a, cfg.b
    n_struct = 2 * (p - 1) * (q - 1)

    def local_round(problem: Problem, carry: GossipCarry, step_i,
                    f_scale=None) -> GossipCarry:
        state, prev = carry.state, carry.halos
        ef = {
            "u_last": carry.ef_u_last, "u_first": carry.ef_u_first,
            "w_last": carry.ef_w_last, "w_first": carry.ef_w_first,
        }

        def refresh(_):
            h, ef_new = exchange_halos(
                state.U, state.W, row_axes, col_axes, compression,
                ef if compression != "none" else None, topk_fraction,
                age=prev.age,
            )
            if compression == "none":
                return h, tuple(ef.values())
            return h, tuple(ef_new[k] for k in ef)

        def keep(_):
            return prev, tuple(ef.values())

        if async_rounds:
            # the absolute round is the clock: chunked calls and resumed
            # fits land on the same exchange schedule
            is_refresh = carry.rnd % exchange_every == 0
        else:
            is_refresh = step_i % staleness == 0
        halos, ef_vals = jax.lax.cond(is_refresh, refresh, keep, operand=None)

        stats = carry.stats
        gates = None
        if faults is not None or async_rounds:
            c = jax.lax.axis_index(col_axes)
            r_ = jax.lax.axis_index(row_axes)
            dc = jax.lax.axis_size(col_axes)
            dr = jax.lax.axis_size(row_axes)
            # which of my 4 halo directions have a real neighbour
            exists = jnp.stack([c > 0, c < dc - 1, r_ > 0, r_ < dr - 1])
            if faults is not None:
                # fault decisions keyed on the *receiver* device's linear
                # index, drawn on exchange rounds only (async skips are
                # planned, not faults — no events burn on them)
                drops, straggles = faults.edge_events(carry.rnd, r_ * dc + c)
            else:
                drops = jnp.zeros((4,), bool)
                straggles = jnp.zeros((4,), bool)
            # straggler = late message: for this synchronous simulation the
            # receiver reuses the stale halo exactly like a drop, but the
            # event is accounted separately (and costed by the bench via
            # FaultPlan.straggler_scale)
            arrived = is_refresh & ~(drops | straggles)
            fresh = (halos.left_u, halos.right_u, halos.up_w, halos.down_w)
            stale = (prev.left_u, prev.right_u, prev.up_w, prev.down_w)
            merged, ages = [], []
            for d in range(4):
                v = jnp.where(arrived[d], fresh[d], stale[d])
                if faults is not None and faults.nan_at is not None:
                    inject = faults.nan_event(carry.rnd)
                    v = jnp.where(inject & exists[d],
                                  jnp.full_like(v, jnp.nan), v)
                if async_rounds:
                    # age counts rounds-since-receive: planned skips age
                    # exactly like fault drops (NOMAD staleness semantics),
                    # so with exchange_every=e and no faults age = rnd % e
                    a_d = jnp.where(
                        arrived[d], 0,
                        jnp.minimum(prev.age[..., d] + 1, AGE_NEVER),
                    )
                else:
                    # age: reset on receive, saturating +1 per missed
                    # refresh, frozen on planned keep rounds (those are
                    # not faults)
                    a_d = jnp.where(
                        arrived[d], 0,
                        jnp.where(is_refresh,
                                  jnp.minimum(prev.age[..., d] + 1,
                                              AGE_NEVER),
                                  prev.age[..., d]),
                    )
                merged.append(v)
                ages.append(a_d)
            age = jnp.stack(ages, axis=-1)
            halos = HaloState(*merged, age)
            # scalar per-direction seam gates (every local block of a shard
            # shares one device, hence one age) — beyond the bound the
            # block runs on its local-only gradient
            a0 = age[0, 0]
            gates = tuple(exists[d] & (a0[d] <= max_staleness)
                          for d in range(4))
            # record at the first local block only: host-side sum over the
            # (p, q) stats grid = true cross-device totals
            n_drop = jnp.sum((drops & exists & is_refresh).astype(jnp.int32))
            n_strag = jnp.sum(
                (straggles & ~drops & exists & is_refresh).astype(jnp.int32))
            was_stale = jnp.any(exists & (a0 >= 1)).astype(jnp.int32)
            stats = FaultStats(
                dropped=stats.dropped.at[0, 0].add(n_drop),
                stale=stats.stale.at[0, 0].add(was_stale),
                straggled=stats.straggled.at[0, 0].add(n_strag),
            )

        # consensus damped 1/2 in deterministic full-grad mode (waves.py)
        gU, gW = _local_gradients(
            problem, state.U, state.W, halos, row_axes, col_axes,
            rho=rho * 0.5, lam=lam, use_kernel=use_kernel,
            method=method, chunk=chunk, gates=gates, f_scale=f_scale,
        )
        lr = obj.gamma(state.t.astype(jnp.float32), a, b)
        new_state = State(state.U - lr * gU, state.W - lr * gW,
                          state.t + n_struct)
        return GossipCarry(new_state, halos, *ef_vals,
                           carry.rnd + 1, stats)

    def shard_body(problem: Problem, carry: GossipCarry) -> GossipCarry:
        def body(c, i):
            return local_round(problem, c, i), None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(steps_per_call))
        return carry

    def shard_body_minibatch(problem: Problem, f_scale,
                             carry: GossipCarry) -> GossipCarry:
        # one sampled store per round: no scan, the schedule feeds a fresh
        # minibatch (and the same full-store nnz/batch scale) every call
        return local_round(problem, carry, jnp.asarray(0), f_scale=f_scale)

    # every placement decision reads the plan: store leaves and factor
    # stacks shard on their leading (p, q) axes, halos/error-feedback on
    # their single grid axis — MeshPlan is the source of truth, so new
    # store fields or axis layouts never touch this scheduler
    pspec2 = plan.grid_spec
    if layout == "sparse":
        problem_spec = plan.entries_spec()
    else:
        problem_spec = Problem(pspec2, pspec2)
    state_spec = plan.state_spec()
    re_, ce = plan.row_edge_spec, plan.col_edge_spec
    halo_spec = HaloState(re_, re_, ce, ce, pspec2)
    carry_spec = GossipCarry(state_spec, halo_spec, re_, re_, ce, ce,
                             P(), FaultStats(pspec2, pspec2, pspec2))

    if batch is not None:
        in_specs = (problem_spec, pspec2, carry_spec)
        body_fn = shard_body_minibatch
    else:
        in_specs = (problem_spec, carry_spec)
        body_fn = shard_body
    sharded = jax.shard_map(
        body_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=carry_spec,
        check_vma=False,
    )
    if layout == "sparse":
        # a store's dense tile stays out of the mesh: gossip blocks take
        # the segment path (the placed store's spec has no tile fields)
        step = jax.jit(lambda problem, *rest: sharded(drop_tile(problem),
                                                      *rest))
    else:
        step = jax.jit(sharded)
    return step, (problem_spec, carry_spec)


def exchange_rounds_in(start: int, n: int, exchange_every: int = 1) -> int:
    """How many of rounds ``[start, start + n)`` actually exchange halos.

    The async schedule fires an exchange when ``rnd % exchange_every == 0``
    (absolute round — ``make_gossip_step``'s clock), so this is exact, not
    an ``n / exchange_every`` amortization: the ``Gossip`` schedule uses it
    to account ``train_gossip_halo_bytes_total`` and
    ``gossip_skipped_exchanges_total`` per chunk with no rounding drift."""

    if exchange_every == 1:
        return n
    first = -(-start // exchange_every) * exchange_every
    if first >= start + n:
        return 0
    return (start + n - 1 - first) // exchange_every + 1


def halo_bytes_per_round(plan: MeshPlan, mb: int, nb: int, r: int,
                         compression: str = "none",
                         grid: tuple[int, int] | None = None) -> dict:
    """Exact wire bytes one gossip round moves — from the plan's edge specs.

    No estimation: this is the same geometry ``exchange_halos`` executes.
    Each device's U-edge message is its first/last local block *column*,
    shape ``(blocks_per_row_shard, mb, r)`` (sharded ``plan.row_edge_spec``),
    ppermuted along the col axes; W edges are the dual.  The boundary sends
    are dropped by the permutation (``_shift`` excludes out-of-range
    pairs), so only *interior* device pairs carry bytes — on a 1×1 plan
    the total is exactly 0, and the per-round counter the ``Gossip``
    schedule keeps (``train_gossip_halo_bytes_total``) matches the wires.

    ``grid=(R, C)`` overrides the device grid for analytic accounting
    (``benchmarks/gossip_comm.py`` models the paper's one-agent-per-block
    deployment without materializing devices).  Compression (int8/top-k)
    is applied per message via ``compress.message_bytes_n`` — again the
    byte model the wire format defines, not a ratio guess.
    """

    R, Cc = grid if grid is not None else (plan.row_size, plan.col_size)
    bpr = plan.p // R
    bpc = plan.q // Cc
    u_floats = bpr * mb * r                 # one U edge message, in floats
    w_floats = bpc * nb * r
    u_msg = C.message_bytes_n(u_floats, compression)
    w_msg = C.message_bytes_n(w_floats, compression)
    # 2 directions (first/last edge) x interior neighbour pairs
    u_bytes = 2 * R * (Cc - 1) * u_msg
    w_bytes = 2 * Cc * (R - 1) * w_msg
    interior = 2 * (u_msg + w_msg)          # what one interior agent sends
    return {
        "u_edge_message_bytes": u_msg,
        "w_edge_message_bytes": w_msg,
        "u_bytes": u_bytes,
        "w_bytes": w_bytes,
        "total_bytes": u_bytes + w_bytes,
        "per_interior_agent_bytes": interior,
    }


def init_carry(state: State, round0: int = 0) -> GossipCarry:
    """Zero halos + zero error feedback (shapes are the *global* array
    shapes; shard_map slices them).

    Ages start at ``AGE_NEVER`` (nothing has been received yet) and the
    fault clock at ``round0`` — a resumed fit passes its completed round
    count so ``FaultPlan`` replay continues at the right position."""

    p, q, mb, r = state.U.shape
    nb = state.W.shape[2]
    halos = HaloState(
        left_u=jnp.zeros((p, mb, r), jnp.float32),
        right_u=jnp.zeros((p, mb, r), jnp.float32),
        up_w=jnp.zeros((q, nb, r), jnp.float32),
        down_w=jnp.zeros((q, nb, r), jnp.float32),
        age=jnp.full((p, q, 4), AGE_NEVER, jnp.int32),
    )
    return GossipCarry(
        state, halos,
        jnp.zeros((p, mb, r), jnp.float32),
        jnp.zeros((p, mb, r), jnp.float32),
        jnp.zeros((q, nb, r), jnp.float32),
        jnp.zeros((q, nb, r), jnp.float32),
        jnp.asarray(round0, jnp.int32),
        FaultStats(
            dropped=jnp.zeros((p, q), jnp.int32),
            stale=jnp.zeros((p, q), jnp.int32),
            straggled=jnp.zeros((p, q), jnp.int32),
        ),
    )


@functools.lru_cache(maxsize=64)
def gossip_step(spec_pq: tuple[int, int], cfg: GossipMCConfig, **kw):
    """:func:`make_gossip_step` (``mesh=None``, so pass ``plan=``), built
    once per argument set: a fit that resumes or repeats another's rounds
    reuses its compiled step instead of tracing and compiling it again.
    A ``FaultPlan`` is keyed by identity."""

    return make_gossip_step(None, spec_pq, cfg, **kw)


@functools.lru_cache(maxsize=None)
def _distributed_cost_fn(plan: MeshPlan, lam: float, sparse: bool):
    """Jitted Σ-cost for one (plan, λ, layout) — cached so eval
    boundaries inside a fit (and successive fits on the same plan) reuse
    the compiled program instead of re-jitting per call."""

    pspec2 = plan.grid_spec
    axes = plan.all_axes
    problem_spec = plan.entries_spec() if sparse else Problem(pspec2, pspec2)

    def local_cost(prob, U, W):
        c = obj.total_cost(prob, U, W, lam)
        return jax.lax.psum(c, axes)

    sharded = jax.shard_map(
        local_cost, mesh=plan.mesh,
        in_specs=(problem_spec, pspec2, pspec2),
        out_specs=P(),
        check_vma=False,
    )
    if sparse:
        return jax.jit(lambda prob, U, W: sharded(drop_tile(prob), U, W))
    return jax.jit(sharded)


def distributed_cost(mesh, problem: Problem | SparseProblem, state: State,
                     lam: float, row_axes="data", col_axes="model",
                     plan: MeshPlan | None = None):
    """Σ f + λ‖·‖² with a single final psum (evaluation only).

    Works for both layouts: the local tile cost dispatches on the problem
    pytree (dense tensors vs padded-COO store)."""

    if plan is None:
        p, q = problem.nnz.shape if isinstance(problem, SparseProblem) \
            else problem.xb.shape[:2]
        plan = MeshPlan.build(p, q, mesh=mesh, row_axes=row_axes,
                              col_axes=col_axes)
    fn = _distributed_cost_fn(plan, float(lam),
                              isinstance(problem, SparseProblem))
    return fn(problem, state.U, state.W)
