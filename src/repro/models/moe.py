"""Mixture-of-Experts FFN: top-k router + grouped-GEMM experts + EP.

Dispatch is sort-based and *dropless*: the (T·k) token-slots are sorted by
expert id and hit the experts through ``jax.lax.ragged_dot`` (grouped GEMM —
the TPU-native MoE formulation; no capacity buffers, no one-hot dispatch
tensors).

Expert parallelism (EP): experts are sharded over the ``model`` mesh axis.
Inside ``shard_map`` each rank rotates the sort key by its first local
expert id — ``(expert − e0) mod E`` — so *its* experts sort to the front,
runs the grouped GEMM over exactly its shard (ragged_dot zero-fills the
foreign tail rows), and a single ``psum`` over the EP axis combines expert
outputs.  Communication per MoE layer: one (T_loc, d) all-reduce.  (The
all-to-all dispatch variant is a recorded §Perf iteration — see
EXPERIMENTS.md.)

Aux losses: switch-style load-balance loss + router z-loss, both returned
to the caller for accumulation across layers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import MoEConfig
from repro.models import layers as L


def padded_experts(cfg: MoEConfig, pad_to: int) -> int:
    """Expert count padded to a multiple of the EP axis (dummy experts get
    zero weights and are never routed to — the router only has E outputs;
    their ragged_dot groups are permanently empty)."""

    E = cfg.num_experts
    if pad_to and E % pad_to:
        return (E // pad_to + 1) * pad_to
    return E


def init_moe(key, d_model: int, cfg: MoEConfig, dtype, pad_to: int = 0) -> dict:
    kr, kg, ku, ko, ks = jax.random.split(key, 5)
    E, ff = cfg.num_experts, cfg.expert_d_ff
    Ep = padded_experts(cfg, pad_to)
    s_in, s_ff = d_model**-0.5, ff**-0.5
    p = {
        "router": (jax.random.normal(kr, (d_model, E)) * s_in).astype(jnp.float32),
        "wi_gate": (jax.random.normal(kg, (Ep, d_model, ff)) * s_in).astype(dtype),
        "wi_up": (jax.random.normal(ku, (Ep, d_model, ff)) * s_in).astype(dtype),
        "wo": (jax.random.normal(ko, (Ep, ff, d_model)) * s_ff).astype(dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = L.init_mlp_swiglu(
            ks, d_model, cfg.num_shared_experts * ff, dtype
        )
    return p


def _expert_compute(wi_gate, wi_up, wo, xs, group_sizes):
    """Grouped GEMM over sorted token-slots; rows past Σgroup_sizes -> 0."""

    g = jax.nn.silu(jax.lax.ragged_dot(xs, wi_gate, group_sizes))
    u = jax.lax.ragged_dot(xs, wi_up, group_sizes)
    return jax.lax.ragged_dot(g * u, wo, group_sizes)


def _moe_partial(params, xt, top_idx, top_w, e0, num_local: int, num_total: int):
    """Expert outputs for the ``num_local`` experts starting at ``e0``.

    xt: (T, d); top_idx/top_w: (T, k).  Returns (T, d) partial combine.
    """

    T, d = xt.shape
    k = top_idx.shape[1]
    slot_expert = top_idx.reshape(-1)                       # (T*k,)
    slot_token = jnp.repeat(jnp.arange(T), k)
    slot_w = top_w.reshape(-1)
    key = (slot_expert - e0) % num_total                    # local experts first
    order = jnp.argsort(key)
    xs = xt[slot_token[order]]                              # (T*k, d) gather
    counts = jnp.bincount(key, length=num_total)
    group_sizes = jax.lax.dynamic_slice_in_dim(counts, 0, num_local)
    ys = _expert_compute(
        params["wi_gate"], params["wi_up"], params["wo"], xs, group_sizes
    )
    ys = ys * slot_w[order][:, None].astype(ys.dtype)
    out = jnp.zeros((T, d), ys.dtype).at[slot_token[order]].add(ys)
    return out


def route(params, xt, cfg: MoEConfig):
    """Router: probabilities, top-k, and aux losses."""

    logits = (xt.astype(jnp.float32)) @ params["router"]    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # switch-style load-balance loss + z-loss
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)
    onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
    fe = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = E * jnp.sum(me * fe) * cfg.router_aux_loss_coef
    aux = aux + 1e-4 * jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2)
    return top_idx, top_w, aux


def _moe_local(params, x, cfg: MoEConfig, ep_axis: str | None,
               aux_axes=None):
    """Single-program (or per-EP-rank, inside shard_map) MoE FFN body."""

    B, Lx, d = x.shape
    xt = x.reshape(-1, d)
    top_idx, top_w, aux = route(params, xt, cfg)
    Ep = params["wi_gate"].shape[0]                        # padded expert count
    if ep_axis is None:
        y = _moe_partial(params, xt, top_idx, top_w, 0, Ep, Ep)
    else:
        rank = jax.lax.axis_index(ep_axis)
        n_ranks = jax.lax.axis_size(ep_axis)
        Ep_global = Ep * n_ranks                           # params arrive pre-sliced
        y = _moe_partial(params, xt, top_idx, top_w, rank * Ep, Ep, Ep_global)
        y = jax.lax.psum(y, ep_axis)
        # aux averages over every rank that holds distinct tokens or experts
        aux = jax.lax.pmean(aux, aux_axes or ep_axis)
    if "shared" in params:
        y = y + L.mlp_swiglu(params["shared"], xt)
    return y.reshape(B, Lx, d).astype(x.dtype), aux


def _moe_a2a(params, x, cfg: MoEConfig, ep_axis: str, aux_axes,
             cap_factor: float = 2.0):
    """All-to-all expert dispatch (production path, §Perf iteration).

    Sequence is sharded over the EP axis on entry: each rank routes only
    its t = B_loc·L/n tokens.  Slots are bucketed by destination rank
    (expert // E_local) into fixed-capacity buffers, shipped with one
    all_to_all, grouped-GEMM'd on the owning rank, and shipped back; the
    source rank applies routing weights and scatter-adds.  vs the psum
    combine this moves ~3·C·d instead of 2·t·d per rank per layer and
    divides router/sort work by n.  Overflow beyond capacity is dropped
    (cap_factor 2.0; standard).
    """

    B, Lx, d = x.shape
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    k = cfg.num_experts_per_tok
    n = jax.lax.axis_size(ep_axis)
    Ep_local = params["wi_gate"].shape[0]
    C = max(1, int(t * k / n * cap_factor))

    top_idx, top_w, aux = route(params, xt, cfg)
    aux = jax.lax.pmean(aux, aux_axes)
    slot_expert = top_idx.reshape(-1)                   # (t·k,)
    slot_token = jnp.repeat(jnp.arange(t), k)
    slot_w = top_w.reshape(-1)

    dst = slot_expert // Ep_local
    order = jnp.argsort(dst)                            # stable
    dst_s = dst[order]
    run_start = jnp.searchsorted(dst_s, dst_s, side="left")
    pos = jnp.arange(t * k) - run_start                 # index within bucket
    keep = pos < C
    rows = jnp.where(keep, dst_s, 0)
    cols = jnp.where(keep, pos, 0)

    send_x = jnp.zeros((n, C, d), x.dtype)
    send_e = jnp.full((n, C), Ep_local, jnp.int32)      # sentinel = invalid
    gathered = xt[slot_token[order]]
    send_x = send_x.at[rows, cols].set(
        jnp.where(keep[:, None], gathered, 0.0))
    send_e = send_e.at[rows, cols].set(
        jnp.where(keep, (slot_expert % Ep_local)[order], Ep_local))

    recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, ep_axis, 0, 0, tiled=False)

    flat_x = recv_x.reshape(n * C, d)
    flat_e = recv_e.reshape(n * C)
    o2 = jnp.argsort(flat_e)                            # sentinels sort last
    xs = flat_x[o2]
    group_sizes = jnp.bincount(flat_e, length=Ep_local + 1)[:Ep_local]
    ys = _expert_compute(params["wi_gate"], params["wi_up"], params["wo"],
                         xs, group_sizes)
    flat_y = jnp.zeros_like(flat_x).at[o2].set(ys.astype(flat_x.dtype))
    ret = jax.lax.all_to_all(flat_y.reshape(n, C, d), ep_axis, 0, 0,
                             tiled=False)

    contrib = ret[rows, cols] * jnp.where(keep, slot_w[order], 0.0)[:, None]
    y = jnp.zeros((t, d), ret.dtype).at[slot_token[order]].add(contrib)
    if "shared" in params:
        y = y + L.mlp_swiglu(params["shared"], xt)
    return y.reshape(B, Lx, d).astype(x.dtype), aux


def moe_ffn(params, x, cfg: MoEConfig, *, ep_axis: str | None = None,
            mesh=None, dp=None, impl: str = "psum",
            a2a_capacity_factor: float = 2.0):
    """MoE FFN.  x: (B, L, d) -> (y, aux_loss).

    Execution modes:
    * ``mesh`` + ``ep_axis``, impl="psum": expert parallelism — a shard_map
      slices the (padded) expert arrays over ``ep_axis``; activations stay
      replicated across EP ranks, each rank grouped-GEMMs its experts
      ((e−e0) mod E sort rotation) and one psum combines.
    * ``mesh`` + ``ep_axis``, impl="a2a": sequence sharded over the EP axis
      + all-to-all dispatch (see _moe_a2a) — the collective-lean production
      path (§Perf).
    * ``ep_axis`` only: already inside an enclosing shard_map (psum form).
    * neither: single-program grouped GEMM (smoke tests / 1 device).
    """

    if mesh is None or ep_axis is None:
        return _moe_local(params, x, cfg, ep_axis)

    from jax.sharding import PartitionSpec as P

    ep = ep_axis
    pspec = {
        "router": P(),
        "wi_gate": P(ep, None, None),
        "wi_up": P(ep, None, None),
        "wo": P(ep, None, None),
    }
    if "shared" in params:
        pspec["shared"] = {"wi_gate": P(), "wi_up": P(), "wo": P()}
    dp_axes = tuple(dp) if isinstance(dp, (tuple, list)) else (dp,)
    aux_axes = tuple(a for a in dp_axes if a) + (ep,)

    if impl == "a2a":
        xspec = P(dp, ep, None)                        # sequence over EP
        fn = jax.shard_map(
            lambda p, xx: _moe_a2a(p, xx, cfg, ep, aux_axes,
                                   a2a_capacity_factor),
            mesh=mesh, in_specs=(pspec, xspec), out_specs=(xspec, P()),
            check_vma=False,
        )
        return fn(params, x)

    xspec = P(dp, None, None)
    fn = jax.shard_map(
        lambda p, xx: _moe_local(p, xx, cfg, ep, aux_axes),
        mesh=mesh, in_specs=(pspec, xspec), out_specs=(xspec, P()),
        check_vma=False,
    )
    return fn(params, x)


def moe_ffn_reference(params, x, cfg: MoEConfig):
    """Dense all-experts oracle (tests only): computes every expert for every
    token and combines with routing weights."""

    B, Lx, d = x.shape
    xt = x.reshape(-1, d)
    top_idx, top_w, aux = route(params, xt, cfg)
    gate = jnp.einsum("td,edf->tef", xt, params["wi_gate"])
    up = jnp.einsum("td,edf->tef", xt, params["wi_up"])
    per_expert = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * up, params["wo"])
    combine = jnp.zeros((xt.shape[0], params["wi_gate"].shape[0]),
                        per_expert.dtype)
    combine = combine.at[
        jnp.repeat(jnp.arange(xt.shape[0]), cfg.num_experts_per_tok),
        top_idx.reshape(-1),
    ].add(top_w.reshape(-1).astype(per_expert.dtype))
    y = jnp.einsum("ted,te->td", per_expert, combine)
    if "shared" in params:
        y = y + L.mlp_swiglu(params["shared"], xt)
    return y.reshape(B, Lx, d).astype(x.dtype), aux
