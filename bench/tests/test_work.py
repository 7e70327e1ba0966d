"""The kernels' work counts against hand counts at small shapes."""

import peaks
import harness

roi = harness.load_module(harness.BENCH + "/work/roi_filter.py", "w_roi")
assign = harness.load_module(harness.BENCH + "/work/assign.py", "w_assign")


def test_roi_filter_by_hand():
    # 2 seeds x 3 candidates of width 4: per candidate 4 subtractions,
    # 4 multiplies, 4 adds, a root, a comparison and a select = 15
    flops, hbm = roi.count(batch=2, n=3, d=4)
    assert flops == 2 * 3 * 15
    # per seed: 3 rows of 4 f32 (48 B), 3 int32 flags (12 B), the
    # 4-wide center (16 B), the radius (4 B), 3 distances and 3 scores
    # written (24 B) = 104 B
    assert hbm == 2 * 104


def test_assign_by_hand():
    # 2 queries, 3 clusters of 5 rows of width 4
    flops, hbm = assign.count(m=2, clusters=3, cap=5, d=4)
    cross = 2 * 2 * 3 * 5 * 4           # 240
    norms = 3 * 3 * 5 * 4 + 2 * 2 * 4   # 180 + 16
    rest = 8 * 2 * 3 * 5                # 240
    assert flops == cross + norms + rest
    # supports 3*5*4 f32 (240 B) and weights 3*5 (60 B), queries 2*4 f32
    # (32 B), densities 3 (12 B), labels and scores 2+2 (16 B)
    assert hbm == 240 + 60 + 32 + 12 + 16


def test_assign_at_cell_shape_is_memory_bound():
    flops, hbm = assign.count(m=64, clusters=200, cap=2160, d=128)
    least, bound = peaks.roofline_s(flops, hbm, peaks.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert abs(hbm - 200 * 2160 * 129 * 4) < 1e5
    assert 0.27e-3 < least < 0.28e-3


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
