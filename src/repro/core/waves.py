"""Parallel wave scheduler — the paper's "non-overlapping structures can be
processed in parallel" future-work note, implemented.

All structures are partitioned into ≤8 parity waves (grid.wave_schedule);
within a wave no block is shared, so the whole wave's structure updates are
one conflict-free vectorized SGD step (vmap over structures + scatter-add).
One *round* = all waves in random order.  ``t`` advances by the number of
structure updates performed, so the γ_t schedule matches the sequential
algorithm's per-update decay.

``full_gradient_step`` is the deterministic limit (all structures at once =
gradient descent on the collapsed objective L — see objective.full_objective)
and is what the distributed gossip step (gossip.py) computes per device tile.

The supported session entry point is ``repro.mc.Trainer.fit(problem,
schedule="wave" | "full")`` — the module-level :func:`fit` is a deprecated
shim over the same internal loop (:func:`_fit`).

The loop is annotated for a profiler capture (``obs.trace``): each
round's body is the span ``fit.round``, the wave order's draw and host
read inside it ``fit.order``, and each eval boundary's cost ``fit.cost``.
None declares outputs, so the spans add no device sync.
"""

from __future__ import annotations

import functools
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import GossipMCConfig
from repro.core import grid as G
from repro.core import objective as obj
from repro.core.state import Problem, State, Tables, build_tables
from repro.sparse import objective as sparse_obj
from repro.sparse.store import SparseProblem, ensure_layout


def wave_tables(p: int, q: int) -> list[Tables]:
    return [build_tables(p, q, w) for w in G.wave_schedule(p, q)]


@functools.partial(jax.jit, static_argnames=("rho", "lam", "a", "b",
                                              "use_kernel", "method", "chunk"))
def wave_step(
    problem: Problem,
    state: State,
    tables: Tables,
    *,
    rho: float,
    lam: float,
    a: float,
    b: float,
    use_kernel: bool = False,
    method: str = "segment",
    chunk: int | None = None,
) -> State:
    """Update every structure of one wave in parallel."""

    idx = tables.blocks                               # (S, 3, 2)
    bi, bj = idx[..., 0], idx[..., 1]                 # (S, 3)
    u3 = state.U[bi, bj]
    w3 = state.W[bi, bj]
    if isinstance(problem, SparseProblem):            # layout="sparse"
        # one block per loop step: a dense masked tile where the store
        # carries one, else the block's entries (sparse/objective.py)
        _, gu_f, gw_f = sparse_obj.f_grads_at(
            problem.entries, bi, bj, u3, w3,
            use_kernel=use_kernel, method=method, chunk=chunk)
        gu3, gw3 = jax.vmap(functools.partial(
            obj.finish_structure_grads, rho=rho, lam=lam,
        ))(gu_f, gw_f, u3, w3, tables.cf, tables.cu, tables.cw)
    else:
        grad = jax.vmap(
            lambda x, m, u, w, cf, cu, cw: obj.structure_grads(
                x, m, u, w, cf, cu, cw, rho=rho, lam=lam, use_kernel=use_kernel
            )
        )
        gu3, gw3 = grad(problem.xb[bi, bj], problem.maskb[bi, bj],
                        u3, w3, tables.cf, tables.cu, tables.cw)
    lr = obj.gamma(state.t.astype(jnp.float32), a, b)
    # blocks within a wave are pairwise distinct -> conflict-free scatter
    U = state.U.at[bi, bj].add(-lr * gu3)
    W = state.W.at[bi, bj].add(-lr * gw3)
    return State(U, W, state.t + idx.shape[0])


# ---------------------------------------------------------------------------
# Deterministic full-gradient step (= sum of all waves; basis of gossip.py)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("rho", "lam", "use_kernel",
                                              "method", "chunk"))
def full_gradients(
    problem: Problem | SparseProblem, U: jax.Array, W: jax.Array, *,
    rho: float, lam: float, use_kernel: bool = False,
    method: str = "segment", chunk: int | None = None,
    f_scale: jax.Array | None = None,
):
    """∇L of the collapsed objective (objective.full_objective).

    Accepts either layout; a SparseProblem routes the f-part through the
    nnz-proportional SDDMM path with identical consensus/reg terms.
    ``f_scale`` (per-block, shape (p, q)) multiplies only the f-part —
    the minibatch unbiasedness correction (``minibatch_grad_scale``);
    ``None`` leaves the expression untouched (bit-identical)."""

    if isinstance(problem, SparseProblem):
        return sparse_obj.full_gradients_sparse(
            problem, U, W, rho=rho, lam=lam, use_kernel=use_kernel,
            method=method, chunk=chunk, f_scale=f_scale,
        )
    _, gu_f, gw_f = jax.vmap(jax.vmap(
        lambda x, m, u, w: obj.f_grads(x, m, u, w, use_kernel=use_kernel)
    ))(problem.xb, problem.maskb, U, W)
    if f_scale is not None:
        gu_f = gu_f * f_scale[..., None, None]
        gw_f = gw_f * f_scale[..., None, None]
    # consensus stencil shared with the sparse path (sparse.objective)
    gU = gu_f + 2.0 * lam * U + 2.0 * rho * sparse_obj.consensus_pulls(U, axis=1)
    gW = gw_f + 2.0 * lam * W + 2.0 * rho * sparse_obj.consensus_pulls(W, axis=0)
    return gU, gW


@functools.partial(jax.jit, static_argnames=("rho", "lam", "a", "b",
                                              "use_kernel", "method", "chunk"))
def full_gradient_step(
    problem: Problem, state: State, *,
    rho: float, lam: float, a: float, b: float, use_kernel: bool = False,
    method: str = "segment", chunk: int | None = None,
) -> State:
    """One GD step on L.  The consensus part of the step is damped by 1/2
    (a block can be pulled by two pairs per axis; the paper's hyper-params
    put γ·2ρ at exactly 1 per pair, so the undamped full step would
    oscillate — sequential/wave modes never stack pairs, full mode does)."""

    n_struct = 2 * (state.U.shape[0] - 1) * (state.U.shape[1] - 1)
    gU, gW = full_gradients(problem, state.U, state.W, rho=rho * 0.5, lam=lam,
                            use_kernel=use_kernel, method=method, chunk=chunk)
    lr = obj.gamma(state.t.astype(jnp.float32), a, b)
    return State(
        state.U - lr * gU, state.W - lr * gW, state.t + n_struct
    )


@functools.partial(jax.jit, static_argnames=("rounds", "rho", "lam", "a", "b",
                                              "use_kernel", "method", "chunk"))
def full_gd_rounds(problem: Problem, state: State, *, rounds: int,
                   rho: float, lam: float, a: float, b: float,
                   use_kernel: bool = False, method: str = "segment",
                   chunk: int | None = None) -> State:
    """``rounds`` deterministic full-GD steps under one jitted scan
    (dispatch-free inner loop for the Table-2 horizons)."""

    def body(st, _):
        return full_gradient_step(problem, st, rho=rho, lam=lam, a=a, b=b,
                                  use_kernel=use_kernel, method=method,
                                  chunk=chunk), None

    state, _ = jax.lax.scan(body, state, None, length=rounds)
    return state


def _fit(
    problem: Problem | SparseProblem,
    spec: G.GridSpec,
    cfg: GossipMCConfig,
    key: jax.Array,
    *,
    num_rounds: int,
    eval_every: int = 0,
    mode: str = "wave",
    callback: Callable[[int, float], None] | None = None,
    state: State | None = None,
    use_kernel: bool = False,
    layout: str | None = None,
    method: str = "segment",
    chunk: int | None = None,
    start_round: int = 0,
    progress_cb: Callable[[int, float, State, jax.Array], None] | None = None,
) -> tuple[State, list[tuple[int, float]]]:
    """Run ``num_rounds`` rounds of wave (or full-GD) updates.

    One round ≈ num_structures sequential iterations of Algorithm 1; the
    cost history is reported against the equivalent sequential iteration
    count ``t`` so curves are comparable with the paper's Table 2.
    ``layout="sparse"`` runs all f-terms on the padded-COO store; the
    default infers the layout from the problem type.  ``start_round``
    resumes mid-run (checkpoint restore: ``state``/``key`` must be the
    values saved at that round boundary); ``progress_cb(round, cost,
    state, key)`` fires at every eval boundary for restart-exact
    checkpointing.
    """

    from repro.core.state import init_state

    problem = ensure_layout(problem, layout)
    tables = wave_tables(spec.p, spec.q)
    if state is None:
        key, ik = jax.random.split(key)
        state = init_state(ik, spec)
    history: list[tuple[int, float]] = []
    eval_every = eval_every or num_rounds

    def one_round(state: State, key: jax.Array) -> State:
        if mode == "full":
            return full_gradient_step(
                problem, state,
                rho=cfg.rho, lam=cfg.lam, a=cfg.a, b=cfg.b,
                use_kernel=use_kernel, method=method, chunk=chunk,
            )
        # static python order, reshuffled per round; the host read blocks
        # until the device has computed the permutation
        with obs.span("fit.order", annotate=True):
            order = np.asarray(jax.random.permutation(key, len(tables)))
        for w in order:
            state = wave_step(
                problem, state, tables[int(w)],
                rho=cfg.rho, lam=cfg.lam, a=cfg.a, b=cfg.b,
                use_kernel=use_kernel, method=method, chunk=chunk,
            )
        return state

    for rd in range(start_round, num_rounds):
        with obs.span("fit.round", annotate=True):
            key, rk = jax.random.split(key)
            state = one_round(state, rk)
        if (rd + 1) % eval_every == 0 or rd == num_rounds - 1:
            with obs.span("fit.cost", annotate=True):
                cost = float(obj.total_cost(problem, state.U, state.W,
                                            cfg.lam))
            history.append((int(state.t), cost))
            if callback:
                callback(int(state.t), cost)
            if progress_cb:
                progress_cb(rd + 1, cost, state, key)
    return state, history


def fit(*args, **kwargs) -> tuple[State, list[tuple[int, float]]]:
    """Deprecated shim — use ``repro.mc.Trainer``::

        from repro.mc import CompletionProblem, Trainer
        Trainer(cfg).fit(problem, schedule="wave")   # or "full"

    Same signature and bit-identical behaviour as before (it calls the same
    internal loop the facade's ``Wave``/``FullGD`` schedules use)."""

    warnings.warn(
        "repro.core.waves.fit is deprecated; use repro.mc.Trainer.fit("
        "problem, schedule='wave' or 'full') — see DESIGN.md §4 Session API",
        DeprecationWarning, stacklevel=2,
    )
    return _fit(*args, **kwargs)
