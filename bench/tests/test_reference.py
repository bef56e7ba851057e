"""The plain reference and its control."""
import numpy as np

from bench import reference


def _random(m, n, nnz, seed):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, nnz).astype(np.int32)
    cols = rng.integers(0, n, nnz).astype(np.int32)
    vals = rng.standard_normal(nnz).astype(np.float32)
    return rows, cols, vals


def test_reference_equals_a_dense_product():
    m, n = 37, 23
    rows, cols, vals = _random(m, n, 200, 0)   # with duplicate entries
    dense = np.zeros((m, n))
    np.add.at(dense, (rows, cols), vals.astype(np.float64))
    x = np.random.default_rng(1).standard_normal((n, 5))
    a = reference.csr(rows, cols, vals, (m, n))
    np.testing.assert_allclose(reference.multiply(a, x), dense @ x,
                               rtol=1e-13, atol=1e-13)


def test_normwise_err_of_zero_and_nan_columns():
    ref = np.array([[0.0, 1.0], [0.0, 0.0]])
    assert list(reference.normwise_err(ref, ref)) == [0.0, 0.0]
    y = ref.copy()
    y[0, 0] = 1e-30
    assert np.isinf(reference.normwise_err(y, ref)[0])
    y = ref.copy()
    y[1, 1] = np.nan
    assert np.isinf(reference.normwise_err(y, ref)[1])


def test_float32_passes_and_the_bfloat16_control_fails_the_limit():
    m = n = 2000
    rows, cols, vals = _random(m, n, 40000, 2)
    x = np.random.default_rng(3).standard_normal((n, 4)).astype(np.float32)
    a = reference.csr(rows, cols, vals, (m, n))
    ref = reference.multiply(a, x)
    a32 = reference.csr(rows, cols, vals, (m, n), np.float32)
    f32 = reference.multiply(a32, x)
    ctl = reference.control_multiply(
        reference.control_csr(rows, cols, vals, (m, n)), x)
    limit = 1e-5
    assert np.max(reference.normwise_err(f32, ref)) < limit / 10
    assert np.min(reference.normwise_err(ctl, ref)) > limit * 10
