"""The COO generator draws what ``repro.data.movielens_proxy`` draws."""

import numpy as np

import ratings
from repro.data import movielens_proxy


def test_same_triplets_as_movielens_proxy():
    ds = movielens_proxy(300, 200, 8000, seed=7)
    r = ratings.generate(300, 200, 8000, seed=7)
    rows, cols = np.nonzero(ds.train_mask)
    np.testing.assert_array_equal(r.train_rows, rows)
    np.testing.assert_array_equal(r.train_cols, cols)
    np.testing.assert_array_equal(r.train_vals, ds.x[rows, cols])
    np.testing.assert_array_equal(r.test_rows, ds.test_rows)
    np.testing.assert_array_equal(r.test_cols, ds.test_cols)
    np.testing.assert_array_equal(r.test_vals, ds.test_vals)


def test_large_seed_and_block_counts():
    r = ratings.generate(120, 90, 2000, seed=2 ** 31 + 12345)
    nnz = ratings.block_nnz(r.train_rows, r.train_cols, 120, 90, 4, 4)
    assert nnz.sum() == len(r.train_vals) == int(0.8 * 2000)
    assert ((r.train_vals >= 1) & (r.train_vals <= 5)).all()
