"""Work counts against hand counts."""

import numpy as np
import pytest

import workcount


def test_two_by_two_by_hand():
    nnz = np.array([[10, 20], [30, 40]])
    # upper (0,0): (0,0) (1,0) (0,1) = 10+30+20; lower (1,1): (1,1) (0,1)
    # (1,0) = 40+20+30
    assert workcount.visited_entries("wave", nnz) == 60 + 90
    w = workcount.round_work("wave", nnz, rank=8)
    assert w["flops"] == 150 * 6 * 8
    assert w["bytes"] == 150 * (20 + 2 * 8 * 4)


def test_four_by_four_by_hand():
    nnz = np.arange(1, 17).reshape(4, 4)
    # every block's number of structure visits on a 4x4 grid
    visits = np.zeros((4, 4), int)
    for s in workcount.structure_blocks(4, 4):
        for b in s:
            visits[b] += 1
    assert visits.sum() == 3 * 2 * 3 * 3 == 54
    assert visits.tolist() == [[1, 3, 3, 2],
                               [3, 6, 6, 3],
                               [3, 6, 6, 3],
                               [2, 3, 3, 1]]
    assert workcount.visited_entries("wave", nnz) == int(
        (visits * nnz).sum())
    with pytest.raises(ValueError):
        workcount.visited_entries("full", nnz)


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = workcount.least_seconds({"flops": 6e9, "bytes": 1e9}, peak)
    assert bound == "hbm" and t == pytest.approx(1e9 / 819e9)
    t, bound = workcount.least_seconds({"flops": 1e15, "bytes": 1e9}, peak,
                                       chips=4)
    assert bound == "flops" and t == pytest.approx(1e15 / (4 * 197e12))
