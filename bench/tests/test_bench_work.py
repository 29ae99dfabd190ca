"""Required-work counts and the peak table."""
import pytest

from bench import work


def test_bucket_solve_work_counts_true_degrees():
    # one node of degree 2 (d = 3), n = 10, 4 iterations, no influence:
    # per iteration n (4d + d(d+1) + 8) = 10 * 32 = 320 operations and
    # (deg + 1) n 4 = 120 bytes; the sandwich pass 10 (12 + 24 + 8) = 440
    flops, nbytes = work.bucket_solve_work([2], [4], n=10)
    assert flops == 4 * 320 + 440
    assert nbytes == 4 * 120 + 120


def test_bucket_solve_work_adds_nodes_and_influence():
    f1, b1 = work.bucket_solve_work([2], [4], n=10)
    f2, b2 = work.bucket_solve_work([2, 2], [4, 4], n=10)
    assert (f2, b2) == (2 * f1, 2 * b1)
    fi, bi = work.bucket_solve_work([2], [4], n=10, want_influence=True)
    assert fi == f1 + 2 * 10 * 9
    assert bi == b1 + 10 * 3 * 4


def test_score_work():
    # 4 x 4 lattice: p = 16, m = 24
    assert work.score_work(n=8, p=16, m=24) == (4 * 8 * (16 + 48),
                                                8 * 16 * 4)


def test_roofline_names_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_pct(100.0, 1.0, 2.0, peak) == (50.0, "compute")
    assert work.roofline_pct(1.0, 10.0, 4.0, peak) == (25.0, "memory")


def test_peak_table():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
