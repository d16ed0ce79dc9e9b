import pytest

from bench import peaks, work


@pytest.mark.parametrize("n, m, flops, nbytes", [
    # s = 1, K = 2, p = 1: factor 2 (7/3 + 3 + 1) + 1/3 = 13,
    # solves 2 x [2 x 2 (3 + 2)] = 40; words 2 (2 + 1) + 1 = 7
    (1, 2, 53.0, 8 * 7 * 4),
    # s = 3, K = 3, p = 1: factor 3 (63 + 27 + 3) + 1/3 = 279 1/3,
    # solves 2 x [3 x 2 (27 + 6)] = 396; words 3 (18 + 3) + 1 = 64
    (2, 3, 279 + 1 / 3 + 396, 8 * 64 * 4),
])
def test_ipm_iteration_hand_count(n, m, flops, nbytes):
    f, b = work.ipm_iteration(n, m)
    assert f == pytest.approx(flops)
    assert b == nbytes


def test_peaks_of_v5e():
    pk = peaks.peaks("TPU v5 lite")
    assert pk == {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
    with pytest.raises(KeyError):
        peaks.roofline_share(1.0, 1.0, 1.0, "TPU v9 imaginary")


def test_roofline_share_takes_the_binding_peak():
    # 819 GB at 819 GB/s is 1 s; 1 GFLOP is far less: bandwidth binds
    share, bound = peaks.roofline_share(1e9, 819e9, 2.0, "TPU v5 lite")
    assert (share, bound) == (pytest.approx(50.0), "bandwidth")
    share, bound = peaks.roofline_share(197e12, 1.0, 4.0, "TPU v5 lite")
    assert (share, bound) == (pytest.approx(25.0), "compute")
