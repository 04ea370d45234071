"""Plain references of what the cells compute, in ``jax.numpy``.

Nothing here imports the program.  The fits follow the paper's equations
on dense masked blocks (missing ratings are NaN in ``X``), the form the
paper writes them in:

    f_b  = || M_b * (X_b - U_b W_b^T) ||^2
    cost = sum_b f_b + lam (||U_b||^2 + ||W_b||^2)

* ``wave_rounds``: Algorithm 1's structure updates, all structures of one
  parity wave per step, waves in the order ``jax.random.permutation``
  draws from the round's key (the schedule the paper's future-work note
  describes).  Each block's f-term and each consensus pair are weighted by
  the inverse of how many structures touch them (paper Fig. 2).
* ``topk``: top-k unseen items by ``u @ w.T`` per user.

``dtype=bfloat16`` gives the lower-precision control of the fits;
``mode="high"`` the three-pass bf16 control of the scores.  The matrix
products of the float32 reference state ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------- #
# the block grid
# --------------------------------------------------------------------- #


def structures(p: int, q: int) -> list:
    """Every (kind, i, j): 0 is {(i,j),(i+1,j),(i,j+1)}, 1 is
    {(i,j),(i-1,j),(i,j-1)}."""

    up = [(0, i, j) for i in range(p - 1) for j in range(q - 1)]
    lo = [(1, i, j) for i in range(1, p) for j in range(1, q)]
    return up + lo


def trio(kind: int, i: int, j: int):
    """(pivot, vertical partner, horizontal partner)."""

    d = 1 if kind == 0 else -1
    return (i, j), (i + d, j), (i, j + d)


def waves(p: int, q: int) -> list:
    """Structures grouped by kind and pivot parity; within a group no
    block repeats.  Groups in the order the permutation indexes them."""

    out = []
    for kind in (0, 1):
        for pi in (0, 1):
            for pj in (0, 1):
                g = [s for s in structures(p, q)
                     if s[0] == kind and s[1] % 2 == pi and s[2] % 2 == pj]
                if g:
                    out.append(g)
    return out


def weights(p: int, q: int):
    """Inverse counts: per block for f, per horizontal pair (p, q-1) for
    U consensus, per vertical pair (p-1, q) for W consensus."""

    f = np.zeros((p, q))
    du = np.zeros((p, max(q - 1, 1)))
    dw = np.zeros((max(p - 1, 1), q))
    for kind, i, j in structures(p, q):
        piv, vert, hor = trio(kind, i, j)
        for b in (piv, vert, hor):
            f[b] += 1
        du[piv[0], min(piv[1], hor[1])] += 1
        dw[min(piv[0], vert[0]), piv[1]] += 1
    inv = lambda c: np.where(c > 0, 1.0 / np.maximum(c, 1), 0.0)
    return inv(f), inv(du), inv(dw)


def dense_blocks(rows, cols, vals, m: int, n: int, p: int, q: int,
                 dtype=jnp.float32):
    """(p, q, mb, nb) blocks of the ratings on the device, NaN where no
    rating is; blocks of ceil(m/p) x ceil(n/q), zero-padded at the end."""

    mb, nb = -(-m // p), -(-n // q)
    rows = jnp.asarray(np.asarray(rows, np.int32))
    cols = jnp.asarray(np.asarray(cols, np.int32))
    vals = jnp.asarray(np.asarray(vals, np.float32))
    x = jnp.full((p, q, mb, nb), jnp.nan, dtype)
    return x.at[rows // mb, cols // nb, rows % mb, cols % nb].set(
        vals.astype(dtype))


# --------------------------------------------------------------------- #
# fits
# --------------------------------------------------------------------- #


def _block_terms(x, u, w):
    """(f, dF/du, dF/dw) of one dense masked block."""

    pred = jnp.matmul(u, w.T, precision=HIGHEST)
    r = jnp.where(jnp.isnan(x), jnp.zeros((), x.dtype), x - pred)
    gu = -2 * jnp.matmul(r, w, precision=HIGHEST)
    gw = -2 * jnp.matmul(r.T, u, precision=HIGHEST)
    return jnp.sum(r * r), gu, gw


def _lr(t, a: float, b: float, dtype):
    t = jnp.asarray(t).astype(jnp.float32)
    return (jnp.float32(a) / (1 + jnp.float32(b) * t)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("lam", "rho"),
                   donate_argnums=(1, 2))
def _structure_step(X, U, W, idx, cf, cu, cw, lr, *, lam, rho):
    """One structure's update; ``idx`` is (3, 2) = pivot, vert, horiz."""

    xs = [X[idx[k, 0], idx[k, 1]] for k in range(3)]
    us = [U[idx[k, 0], idx[k, 1]] for k in range(3)]
    ws = [W[idx[k, 0], idx[k, 1]] for k in range(3)]
    gus, gws = [], []
    for k in range(3):
        _, gu, gw = _block_terms(xs[k], us[k], ws[k])
        gus.append(cf[k] * (gu + 2 * lam * us[k]))
        gws.append(cf[k] * (gw + 2 * lam * ws[k]))
    pu = 2 * rho * (us[0] - us[2])        # pivot - horizontal partner
    gus[0] = gus[0] + cu * pu
    gus[2] = gus[2] - cu * pu
    pw = 2 * rho * (ws[0] - ws[1])        # pivot - vertical partner
    gws[0] = gws[0] + cw * pw
    gws[1] = gws[1] - cw * pw
    for k in range(3):
        U = U.at[idx[k, 0], idx[k, 1]].set(us[k] - lr * gus[k])
        W = W.at[idx[k, 0], idx[k, 1]].set(ws[k] - lr * gws[k])
    return U, W


def wave_rounds(X, U, W, t: int, key, rounds: int, hp: dict,
                dtype=jnp.float32):
    """``rounds`` wave rounds from (U, W) at structure count ``t``; the
    round keys are split from ``key`` as the operator's chunk would.
    Returns (U, W, t)."""

    p, q = U.shape[:2]
    wv = waves(p, q)
    cf, cu, cw = weights(p, q)
    X, U, W = X.astype(dtype), U.astype(dtype), W.astype(dtype)
    for _ in range(rounds):
        key, rk = jax.random.split(key)
        order = np.asarray(jax.random.permutation(rk, len(wv)))
        for g in order:
            lr = _lr(t, hp["a"], hp["b"], dtype)
            for kind, i, j in wv[int(g)]:
                blocks = trio(kind, i, j)
                idx = jnp.asarray(np.array(blocks, np.int32))
                c3 = jnp.asarray([cf[b] for b in blocks], dtype)
                piv, vert, hor = blocks
                c_u = jnp.asarray(cu[piv[0], min(piv[1], hor[1])], dtype)
                c_w = jnp.asarray(cw[min(piv[0], vert[0]), piv[1]], dtype)
                U, W = _structure_step(X, U, W, idx, c3, c_u, c_w, lr,
                                       lam=hp["lam"], rho=hp["rho"])
            t += len(wv[int(g)])
    return U, W, t


@jax.jit
def _block_cost(x, u, w):
    return _block_terms(x, u, w)[0]


def cost(X, U, W, lam: float) -> float:
    """sum_b f_b + lam (||U_b||^2 + ||W_b||^2), summed in float64 on the
    host over blocks."""

    p, q = U.shape[:2]
    dtype = U.dtype
    total = 0.0
    for i in range(p):
        for j in range(q):
            total += float(_block_cost(X[i, j].astype(dtype), U[i, j],
                                       W[i, j]))
    return total + lam * (float(jnp.sum(jnp.square(U.astype(jnp.float32))))
                          + float(jnp.sum(jnp.square(W.astype(jnp.float32)))))


# --------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------- #


def _scores(u, w, mode: str):
    if mode == "highest":
        return jnp.matmul(u, w.T, precision=HIGHEST)
    if mode != "high":
        raise ValueError(f"mode {mode!r}: 'highest' or 'high'")
    # three bf16 passes, hi*hi + hi*lo + lo*hi, each product exact in f32.
    # reduce_precision keeps the split: XLA may fold an f32->bf16->f32
    # round trip away (excess precision), which would leave one pass
    bf = functools.partial(jax.lax.reduce_precision, exponent_bits=8,
                           mantissa_bits=7)
    uh, wh = bf(u), bf(w)
    ul, wl = bf(u - uh), bf(w - wh)
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    return mm(uh, wh.T) + mm(uh, wl.T) + mm(ul, wh.T)


@functools.partial(jax.jit, static_argnames=("k", "mode"))
def _topk_block(u, w, seen, ids, items, *, k, mode):
    s = _scores(u[ids], w, mode)
    s = jnp.where(seen[ids], -jnp.inf, s)
    top, top_items = jax.lax.top_k(s, k)
    ok = (items >= 0) & (items < w.shape[0])
    got = jnp.take_along_axis(s, jnp.clip(items, 0, w.shape[0] - 1), axis=1)
    return top, top_items, jnp.where(ok, got, -jnp.inf), jnp.max(
        jnp.where(jnp.isfinite(s), jnp.abs(s), 0.0))


def seen_mask(rows, cols, m: int, n: int):
    """(m, n) bool on the device, True where the user rated the item."""

    return jnp.zeros((m, n), bool).at[
        jnp.asarray(np.asarray(rows, np.int32)),
        jnp.asarray(np.asarray(cols, np.int32))].set(True)


def topk(u, w, seen, ids: np.ndarray, items: np.ndarray | None, k: int,
         mode: str = "highest", block: int = 2048):
    """For users ``ids``: the reference top-k values and items, the
    reference score of each item in ``items`` (-inf for an item out of
    range or seen), and the largest |finite score|.  Runs in blocks of
    ``block`` users, padded so every block has one shape."""

    n_ids = len(ids)
    if items is None:
        items = np.zeros((n_ids, k), np.int32)
    tops, top_items, gots, scale = [], [], [], 0.0
    for s in range(0, n_ids, block):
        b_ids = np.zeros(block, np.int32)
        b_items = np.zeros((block, k), np.int32)
        e = min(s + block, n_ids)
        b_ids[: e - s] = ids[s:e]
        b_items[: e - s] = items[s:e]
        top, ti, got, sc = _topk_block(u, w, seen, jnp.asarray(b_ids),
                                       jnp.asarray(b_items), k=k, mode=mode)
        tops.append(np.asarray(top)[: e - s])
        top_items.append(np.asarray(ti)[: e - s])
        gots.append(np.asarray(got)[: e - s])
        scale = max(scale, float(sc))
    return (np.concatenate(tops), np.concatenate(top_items),
            np.concatenate(gots), scale)
