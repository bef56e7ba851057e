"""The roofline's work count against a hand count."""
import numpy as np
import pytest

from bench import work


def test_bytes_and_flops_of_a_4x4_matrix_by_hand():
    # 4 x 4 with 6 stored entries, 2 columns served:
    # values + column indices 6 * (4 + 4) = 48, row pointers (4 + 1) * 4
    # = 20, X read 4 rows * 2 * 4 = 32, Y written 4 rows * 2 * 4 = 32
    a = np.array([[1, 0, 2, 0],
                  [0, 0, 0, 0],
                  [3, 4, 0, 5],
                  [0, 0, 0, 6]], float)
    nnz = int(np.count_nonzero(a))
    assert nnz == 6
    assert work.spmm_bytes(4, 4, nnz, 2) == 48 + 20 + 32 + 32
    # one multiply and one add per stored entry and column
    assert work.spmm_flops(nnz, 2) == 24


def test_least_time_takes_the_binding_bound():
    peaks = {"hbm_bytes_per_s": 100.0, "flops_per_s": 10.0}
    # 132 bytes at 100/s = 1.32 s against 24 operations at 10/s = 2.4 s
    assert work.least_time_s(4, 4, 6, 2, peaks) == pytest.approx(2.4)
    peaks["flops_per_s"] = 1e9
    assert work.least_time_s(4, 4, 6, 2, peaks) == pytest.approx(1.32)


def test_the_count_ignores_the_format():
    # whatever pads or reorders the stream, the work needed is the same
    assert work.spmm_bytes(10, 10, 7, 3) == work.spmm_bytes(10, 10, 7, 3)
    assert work.spmm_bytes(10, 10, 7, 3) < work.spmm_bytes(10, 10, 8, 3)
