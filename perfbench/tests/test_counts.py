"""The required-work counts, pinned at small shapes by hand."""

import json
import os

import pytest

from perfbench import counts

HERE = os.path.dirname(os.path.abspath(__file__))


def test_search_range_clamps_rlo_at_8_bins():
    assert counts.search_range(1000, 2.0, 1.0) == (8.0, 999.0)
    assert counts.search_range(1000, 100.0, 2.0) == (200.0, 999.0)


def test_accel_build_small_shape():
    # numbins 1000, T 100 s, flo 2 Hz: r in [200, 999) = 799 bins,
    # 2 columns per bin = 1598 columns; zmax 4 -> z in -4..4 step 2 =
    # 5 rows; 7990 cells
    c = counts.accel_build(1000, 100.0, 4, 2.0, 3)
    assert counts.plane_cells(1000, 100.0, 4, 2.0) == 7990
    assert c["bytes"] == 3 * (7990 * 4 + 799 * 8)
    assert c["flops"] == 3 * 8 * 7990


def test_accel_scan_small_shape():
    # zmax 0: one row of 1598 columns; numharm 8: 7 adds + 4 compares
    c = counts.accel_scan(1000, 100.0, 0, 8, 2.0, 2)
    assert c["bytes"] == 2 * 1598 * 4
    assert c["flops"] == 2 * 1598 * 11


def test_dedisp_step_small_shape():
    # 64 channels, 8 subbands, 16 DMs, 1024-sample blocks
    c = counts.dedisp_step(64, 8, 16, 1024)
    assert c["bytes"] == 4 * (64 * 1024 + 16 * 1024)
    assert c["flops"] == 64 * 1024 + 16 * 8 * 1024


def test_least_time_takes_the_larger_bound():
    peak = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time({"flops": 50.0, "bytes": 20.0}, peak) == \
        (2.0, "bytes")
    assert counts.least_time({"flops": 500.0, "bytes": 20.0}, peak) == \
        (5.0, "flops")


def test_peaks_table_has_v5e_with_source():
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_bf16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks["cpu"]
