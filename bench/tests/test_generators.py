"""The generators: deterministic per seed, and the same matrices in law
as the repo's numpy generators."""
import jax
import numpy as np
import pytest
import scipy.sparse as sp

from bench import harness, spec
from repro.data import matrices

KRON = {"scale": 12, "edges": 16 * 4096, "a": 0.57, "b": 0.19, "c": 0.19}


def _key(seed):
    return harness.seed_key(jax, seed)


def test_seed_key_keeps_bits_past_32():
    k = [jax.random.key_data(_key(s)) for s in (5, 5 + 2 ** 31, 5 + 2 ** 62)]
    assert not np.array_equal(k[0], k[1])
    assert not np.array_equal(k[1], k[2])
    assert np.array_equal(jax.random.key_data(_key(2 ** 40 + 1)),
                          jax.random.key_data(_key(2 ** 40 + 1)))


@pytest.mark.parametrize("name,cfg", [("kron", KRON),
                                      ("stencil2d", {"side": 20})])
def test_generators_are_deterministic_per_seed(name, cfg):
    gen = spec.generator(name)
    a = gen.generate(cfg, _key(3))
    b = gen.generate(cfg, _key(3))
    c = gen.generate(cfg, _key(4))
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.vals, c.vals)
    assert a.rows.dtype == np.int32 and a.vals.dtype == np.float32


def _ks(a, b):
    """Two-sample Kolmogorov-Smirnov statistic."""
    grid = np.union1d(a, b)
    fa = np.searchsorted(np.sort(a), grid, side="right") / a.size
    fb = np.searchsorted(np.sort(b), grid, side="right") / b.size
    return float(np.max(np.abs(fa - fb)))


def _graph500_kernel_1(rows, cols, m):
    """Graph500's kernel 1 on host edges: self-loops removed, made
    undirected, duplicates merged."""
    keep = rows != cols
    r = np.concatenate([rows[keep], cols[keep]]).astype(np.int64)
    c = np.concatenate([cols[keep], rows[keep]]).astype(np.int64)
    key = np.unique(r * m + c)
    return key // m, key % m


def test_kron_row_degrees_match_the_numpy_rmat():
    ours = spec.generator("kron").generate(KRON, _key(7))
    rows, cols, _, shape = matrices.rmat(12, 16, seed=7)
    assert ours.shape == shape
    m = shape[0]
    rows, cols = _graph500_kernel_1(rows, cols, m)
    d_ours = np.bincount(ours.rows, minlength=m)
    d_rmat = np.bincount(rows, minlength=m)
    assert abs(ours.nnz - rows.size) / rows.size < 0.01
    assert abs(np.mean(d_ours == 0) - np.mean(d_rmat == 0)) < 0.02
    # 4096 rows each: a statistic above ~0.043 rejects at 0.1 %
    assert _ks(d_ours, d_rmat) < 0.043
    # sorted, unique, in range, no self-loops, uniform weights in [0, 1)
    key = ours.rows.astype(np.int64) * m + ours.cols
    assert np.all(np.diff(key) > 0) and key[-1] < m * m
    assert not np.any(ours.rows == ours.cols)
    assert 0.0 <= ours.vals.min() and ours.vals.max() < 1.0
    assert abs(float(np.mean(ours.vals)) - 0.5) < 0.01


def test_kron_is_symmetric_with_shuffled_labels():
    ours = spec.generator("kron").generate(KRON, _key(8))
    m = ours.shape[0]
    a = sp.coo_array((ours.vals, (ours.rows, ours.cols)), shape=ours.shape)
    assert abs(a - a.T).max() == 0
    # unshuffled, a label with more 1 bits has fewer edges (a + b = 0.76
    # per bit); the shuffle leaves no such trend
    bits = np.array([bin(i).count("1") for i in range(m)])
    deg = np.bincount(ours.rows, minlength=m)
    assert abs(np.corrcoef(bits, deg)[0, 1]) < 0.1
    rows, cols, _, _ = matrices.rmat(12, 16, seed=8)
    rows, _ = _graph500_kernel_1(rows, cols, m)
    assert np.corrcoef(bits, np.bincount(rows, minlength=m))[0, 1] < -0.3


def test_stencil_is_mesh2d_entry_for_entry():
    ours = spec.generator("stencil2d").generate({"side": 16}, _key(1))
    rows, cols, vals, shape = matrices.mesh2d(16, seed=1)
    assert ours.shape == shape
    np.testing.assert_array_equal(ours.rows, rows)
    np.testing.assert_array_equal(ours.cols, cols)
    assert ours.vals.shape == vals.shape
