"""The peaks table: the v5e row, and an unknown device is an error."""

import pytest

import common


def test_v5e_row():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_kind_raises():
    with pytest.raises(common.UnknownDevice):
        common.peaks("TPU v9 imaginary")
    with pytest.raises(common.UnknownDevice):
        common.peaks("cpu")
