"""Algorithmic bytes and operations of one PCG iteration, by hand."""
import pytest

from bench import counts


def test_one_element_four_vertices():
    # 4 int32 ids + 10 float32 of the symmetric element matrix = 56 B;
    # 13 float32 touched per vertex slot = 52 B
    assert counts.pcg_iteration(1, 4) == (56 + 4 * 52, 28 + 4 * 13)


def test_scales_linearly():
    b1, f1 = counts.pcg_iteration(3, 5)
    assert (b1, f1) == (3 * 56 + 5 * 52, 3 * 28 + 5 * 13)
    assert counts.pcg_iteration(6, 10) == (2 * b1, 2 * f1)


def test_roofline_share_takes_the_binding_bound():
    peak = {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12}
    # 1e6 B at 1 GB/s is 1 ms; 1e6 flops at 1 TF/s is 1 us: bytes bind
    assert counts.roofline_share(1e6, 1e6, 2e-3, peak) == pytest.approx(50)
    # 1e10 flops take 10 ms, more than the 1 ms of bytes
    assert counts.roofline_share(1e6, 1e10, 20e-3, peak) == pytest.approx(50)
