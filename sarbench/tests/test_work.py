"""The nominal work of one scene against hand arithmetic."""
import pytest

from sarbench import work

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("n, ops, nbytes", [
    # 4 transforms x n lines x 5 n log2 n, plus 3 filters x 6 x n^2
    (4096, 4 * 4096 * 5 * 4096 * 12 + 18 * 4096**2, 16 * 4096**2),
    (8192, 4 * 8192 * 5 * 8192 * 13 + 18 * 8192**2, 16 * 8192**2),
])
def test_scene_work_by_hand(n, ops, nbytes):
    assert work.scene_ops(n, n) == ops
    assert work.scene_bytes(n, n) == nbytes


def test_least_time_is_bytes_bound_at_both_sizes():
    t, bound = work.least_seconds(4096, 4096, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(16 * 4096**2 / 819e9)        # 0.328 ms
    t, bound = work.least_seconds(8192, 8192, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(16 * 8192**2 / 819e9)        # 1.311 ms


def test_rectangular_scene_counts_each_axis():
    # 2 range transforms of na lines of nr, 2 azimuth of nr lines of na
    assert work.scene_ops(1024, 256) == (2 * 1024 * 5 * 256 * 8
                                         + 2 * 256 * 5 * 1024 * 10
                                         + 18 * 1024 * 256)


def test_peaks_table_holds_v5e_and_refuses_unknown_kinds():
    p = work.peaks("TPU v5 lite")
    assert (p["flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        work.peaks("cpu")
