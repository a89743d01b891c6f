"""Operation and byte counts of a Lloyd step from (n, k, f), and the
roofline share they give against the peaks table."""
import pytest

from bench import roofline


@pytest.mark.parametrize("n,k,f", [(1, 1, 1), (100_000, 3, 3), (1024, 6, 3)])
def test_lloyd_counts_follow_the_shapes(n, k, f):
    # per point: k distances of f terms (3f - 1 operations each), k - 1
    # comparisons, f + 1 additions into its center's sum and count
    assert roofline.lloyd_step_flops(n, k, f) == n * (k * (3 * f - 1) + k - 1 + f + 1)
    # 4-byte points and centers read, label and distance written per
    # point, sums and counts written per center
    assert roofline.lloyd_step_bytes(n, k, f) == 4 * (n * f + 2 * n + 2 * k * f + k)


def test_share_names_the_bound():
    pk = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    assert roofline.share(1e6, 1e6, 2e-3, pk) == (pytest.approx(50.0), "memory")
    assert roofline.share(1e10, 1e6, 2e-2, pk) == (pytest.approx(50.0), "compute")


def test_peaks_know_v5e_and_refuse_others():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["flops_bf16"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
