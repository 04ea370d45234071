"""Seeded MovieLens-shaped ratings as COO triplets, with no dense matrix.

The same draws as ``repro.data.movielens_proxy``: low-rank user/item
structure plus biases and noise, Zipf item popularity and user activity,
half-star ratings clipped to [1, 5], duplicates dropped (first draw kept)
and an 80/20 train/test split.  It returns the triplets instead of a
dense ``(users, items)`` array, so an ML-10M shape needs a few hundred MB
of host memory rather than gigabytes.  ``bench/tests/test_ratings.py``
checks that both give the same train and test triplets for one seed.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Ratings:
    """Train and test triplets; ``train_*`` are sorted by (user, item)."""

    num_users: int
    num_items: int
    train_rows: np.ndarray
    train_cols: np.ndarray
    train_vals: np.ndarray
    test_rows: np.ndarray
    test_cols: np.ndarray
    test_vals: np.ndarray


def generate(num_users: int, num_items: int, num_ratings: int, *,
             r_true: int = 12, noise: float = 0.5, item_zipf: float = 0.8,
             user_zipf: float = 0.6, train_share: float = 0.8,
             seed: int = 0) -> Ratings:
    rng = np.random.default_rng(seed)
    a = (rng.standard_normal((num_users, r_true)).astype(np.float32)
         / np.sqrt(r_true))
    b = rng.standard_normal((num_items, r_true)).astype(np.float32)
    user_bias = 0.3 * rng.standard_normal(num_users).astype(np.float32)
    item_bias = 0.5 * rng.standard_normal(num_items).astype(np.float32)
    item_p = 1.0 / np.arange(1, num_items + 1) ** item_zipf
    item_p /= item_p.sum()
    user_p = 1.0 / np.arange(1, num_users + 1) ** user_zipf
    user_p /= user_p.sum()
    item_perm = rng.permutation(num_items)
    user_perm = rng.permutation(num_users)

    num_ratings = min(num_ratings, num_users * num_items // 2)
    rows = user_perm[rng.choice(num_users, 2 * num_ratings, p=user_p)]
    cols = item_perm[rng.choice(num_items, 2 * num_ratings, p=item_p)]
    lin = rows.astype(np.int64) * num_items + cols
    _, first = np.unique(lin, return_index=True)
    first = np.sort(first)[:num_ratings]
    rows, cols = rows[first], cols[first]

    raw = (3.5 + np.einsum("kr,kr->k", a[rows], b[cols])
           + user_bias[rows] + item_bias[cols]
           + noise * rng.standard_normal(len(rows)).astype(np.float32))
    vals = np.clip(np.round(raw * 2) / 2, 1.0, 5.0).astype(np.float32)

    perm = rng.permutation(len(rows))
    cut = int(train_share * len(rows))
    tr, te = perm[:cut], perm[cut:]
    order = np.lexsort((cols[tr], rows[tr]))
    tr = tr[order]
    return Ratings(num_users, num_items,
                   rows[tr].astype(np.int64), cols[tr].astype(np.int64),
                   vals[tr], rows[te].astype(np.int64),
                   cols[te].astype(np.int64), vals[te])


def from_config(cfg: dict, seed: int) -> Ratings:
    """The ratings of a configuration file's ``data`` section."""

    d = cfg["data"]
    return generate(d["users"], d["items"], d["ratings"],
                    r_true=d["latent_rank"], noise=d["noise"],
                    item_zipf=d["item_zipf"], user_zipf=d["user_zipf"],
                    train_share=d["train_share"], seed=seed)


def block_nnz(rows, cols, num_users, num_items, p, q) -> np.ndarray:
    """(p, q) count of ratings per grid block, blocks of ceil(m/p) rows
    and ceil(n/q) columns as the program pads them."""

    mb, nb = -(-num_users // p), -(-num_items // q)
    return np.bincount(rows // mb * q + cols // nb,
                       minlength=p * q).reshape(p, q)
