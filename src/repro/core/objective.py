"""The paper's objective (eq. 1–3) and its closed-form block gradients.

State layout: all block factors live in two stacked arrays

    U : (p, q, mb, r)     W : (p, q, nb, r)

f_ij  = ||M_ij ⊙ (X_ij − U_ij W_ijᵀ)||²_F            (observed entries only)
dU_ij = ||U_ij − U_i(j+1)||²_F                        (horizontal consensus)
dW_ij = ||W_ij − W_(i+1)j||²_F                        (vertical consensus)

The reported convergence cost (paper Table 2) is
    Σ_ij f_ij + λ‖U_ij‖² + λ‖W_ij‖².

Gradients are written in closed form (the structure losses are quadratic in
each factor) — this is what the Pallas kernel `masked_factor_grad`
accelerates for the f-part.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.masked_factor_grad import ops as mfg_ops
from repro.sparse import objective as sparse_obj
from repro.sparse.store import SparseProblem


def block_residual(x, mask, u, w):
    """R = M ⊙ (X − U Wᵀ) for one block."""

    return mask * (x - u @ w.T)


def f_cost(x, mask, u, w):
    r = block_residual(x, mask, u, w)
    return jnp.sum(r * r)


def f_grads(x, mask, u, w, use_kernel: bool = False):
    """(f, gU, gW) for one block; closed form.

    gU = −2 R W,  gW = −2 Rᵀ U.
    """

    if use_kernel:
        return mfg_ops.masked_factor_grad(x, mask, u, w)
    r = block_residual(x, mask, u, w)
    return jnp.sum(r * r), -2.0 * r @ w, -2.0 * r.T @ u


def total_report_cost(xb, maskb, U, W, lam: float):
    """Paper Table-2 cost: Σ f_ij + λ‖U_ij‖² + λ‖W_ij‖² (vectorized)."""

    def per_block(x, m, u, w):
        return f_cost(x, m, u, w) + lam * jnp.sum(u * u) + lam * jnp.sum(w * w)

    per = jax.vmap(jax.vmap(per_block))(xb, maskb, U, W)
    return jnp.sum(per)


def total_cost(problem, U, W, lam: float):
    """Layout-dispatching Table-2 cost: dense ``Problem`` tensors or the
    padded-COO ``SparseProblem`` store (nnz-proportional)."""

    if isinstance(problem, SparseProblem):
        return sparse_obj.total_report_cost_sparse(problem, U, W, lam)
    return total_report_cost(problem.xb, problem.maskb, U, W, lam)


def consensus_costs(U, W):
    """(Σ dU over horizontal pairs, Σ dW over vertical pairs) — diagnostics."""

    du = jnp.sum((U[:, 1:] - U[:, :-1]) ** 2)
    dw = jnp.sum((W[1:] - W[:-1]) ** 2)
    return du, dw


def full_objective(xb, maskb, U, W, rho: float, lam: float):
    """Eq. (3) with the normalization coefficients folded in.

    Normalization (paper §4, Fig. 2): each block's f (and λ-reg) gradient is
    scaled by 1/count_f[block], and each consensus pair's gradient by
    1/count_pair[pair].  Summed over all structures the objective then
    collapses to *exactly one* f per block, one dU per horizontal pair and
    one dW per vertical pair — the "equal representation" the paper asks
    for.  (We normalize the pair terms per-*pair* rather than per-block: the
    per-block reading of Fig. 2 would make the consensus force field
    non-conservative; per-pair matches the stated intent and yields a
    well-defined objective.  Noted in DESIGN.md.)

        L = Σ_b [f_b + λ(‖U_b‖²+‖W_b‖²)] + ρ Σ_hpairs dU + ρ Σ_vpairs dW
    """

    total = total_report_cost(xb, maskb, U, W, lam)
    du, dw = consensus_costs(U, W)
    return total + rho * (du + dw)


# ---------------------------------------------------------------------------
# Structure gradient (the SGD inner loop of Algorithm 1)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("rho", "lam", "use_kernel"))
def structure_grads(
    x3, m3, u3, w3, cf3, cu_pair, cw_pair, rho: float, lam: float,
    use_kernel: bool = False,
):
    """Gradients of one structure's cost w.r.t. its three blocks' factors.

    Inputs are stacked (3, ...) arrays ordered (pivot, vert, horiz) as in
    :func:`repro.core.grid.structure_blocks`.  ``cf3`` are the three blocks'
    f-normalization coefficients; ``cu_pair``/``cw_pair`` are the (2,)
    dU/dW coefficients for (pivot, horiz) and (pivot, vert) respectively.

    Returns (gU3, gW3) with the same stacking.  Closed form:

      ∂f/∂U = −2 R W + 2 λ U          ∂dU/∂U_ij = 2 (U_ij − U_partner)
    """

    f, gu_f, gw_f = jax.vmap(
        lambda x, m, u, w: f_grads(x, m, u, w, use_kernel=use_kernel)
    )(x3, m3, u3, w3)
    del f
    return finish_structure_grads(
        gu_f, gw_f, u3, w3, cf3, cu_pair, cw_pair, rho, lam
    )


def finish_structure_grads(gu_f, gw_f, u3, w3, cf3, cu_pair, cw_pair, rho, lam):
    """Shared tail of the structure gradient: λ-reg + Fig.-2 normalization +
    the two consensus pulls (identical for dense and sparse f-parts)."""

    # f + λ reg, per-block normalized
    gu = cf3[:, None, None] * (gu_f + 2.0 * lam * u3)
    gw = cf3[:, None, None] * (gw_f + 2.0 * lam * w3)
    # U consensus: pivot (index 0) <-> horiz (index 2)
    du = 2.0 * rho * (u3[0] - u3[2])
    gu = gu.at[0].add(cu_pair[0] * du)
    gu = gu.at[2].add(-cu_pair[1] * du)
    # W consensus: pivot (index 0) <-> vert (index 1)
    dw = 2.0 * rho * (w3[0] - w3[1])
    gw = gw.at[0].add(cw_pair[0] * dw)
    gw = gw.at[1].add(-cw_pair[1] * dw)
    return gu, gw


@partial(jax.jit, static_argnames=("rho", "lam", "use_kernel", "method",
                                    "chunk"))
def structure_grads_sparse(
    entries3, u3, w3, cf3, cu_pair, cw_pair,
    rho: float, lam: float, use_kernel: bool = False, method: str = "segment",
    chunk: int | None = None,
):
    """Sparse-layout twin of :func:`structure_grads`: the three blocks' f
    gradients come from their segment-sorted entry lists (O(nnz·r) streaming
    CSR/CSC reductions, one stacked ``BlockEntries`` pytree of (3, ...)
    leaves); the consensus/reg/normalization tail is byte-identical."""

    f, gu_f, gw_f = jax.vmap(
        lambda entries, u, w: sparse_obj.f_grads_sparse(
            entries, u, w, use_kernel=use_kernel, method=method, chunk=chunk,
        )
    )(entries3, u3, w3)
    del f
    return finish_structure_grads(
        gu_f, gw_f, u3, w3, cf3, cu_pair, cw_pair, rho, lam
    )


def gamma(t, a: float, b: float):
    """Paper step size γ_t = a / (1 + b t)."""

    return a / (1.0 + b * t)
