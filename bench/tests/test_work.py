"""Work counts and the peaks table, on shapes alone."""
import pytest

from bench import work


def test_dist_work_counts_pairs_and_bytes():
    w = work.dist_work(n=8192, d=9, n_e=39365)
    assert w["flops"] == 2 * 9 * 8192 * 8191 / 2
    assert w["bytes"] == 4 * 8192 * 9 + 12 * 39365


def test_least_seconds_takes_the_binding_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    compute_bound = work.dist_work(8192, 9, 0)
    assert work.least_seconds(compute_bound, peak) == \
        compute_bound["flops"] / peak["bf16_flops_per_s"]
    memory_bound = {"flops": 1.0, "bytes": 819e9}
    assert work.least_seconds(memory_bound, peak) == 1.0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
