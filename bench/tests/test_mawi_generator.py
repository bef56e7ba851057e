"""The MAWI-class generator at CPU sizes: the hub's share and the stored
entries per row of the configuration, symmetry, and one matrix per seed;
and its cell run end to end on the CPU, sound and with the control."""
import jax
import numpy as np
import pytest
import scipy.sparse as sp

from bench import harness, spec
from bench.tests import tiny

CELL = "mawi22.solo"


def _cfg(scale):
    """``bench/configs/mawi22.json`` at ``2**scale`` rows, the Kronecker
    edges scaled with them."""
    cfg = spec.config(spec.load_benchmark(), "mawi22")
    edges = round(cfg["edges"] * 2 ** scale / 2 ** cfg["scale"])
    return dict(cfg, scale=scale, edges=edges)


def _generate(scale, seed):
    return spec.generator("mawi").generate(_cfg(scale),
                                           harness.seed_key(jax, seed))


@pytest.mark.parametrize("scale", [14, 16])
def test_hub_share_and_entries_per_row(scale):
    t = _generate(scale, 2 ** 31 + 7)
    m = t.shape[0]
    assert t.shape == (2 ** scale, 2 ** scale)
    deg = np.bincount(t.rows, minlength=m)
    assert abs(deg.max() / t.nnz - 0.444) <= 0.02
    assert abs(t.nnz / m - 2.10) <= 0.05
    # sorted, unique, in range, no self-loops, weights in [0, 1)
    key = t.rows.astype(np.int64) * m + t.cols
    assert np.all(np.diff(key) > 0) and key[-1] < m * m
    assert not np.any(t.rows == t.cols)
    assert 0.0 <= t.vals.min() and t.vals.max() < 1.0


def test_is_symmetric():
    t = _generate(14, 5)
    a = sp.coo_array((t.vals, (t.rows, t.cols)), shape=t.shape).tocsr()
    assert (a != a.T).nnz == 0


def test_same_triplets_from_the_same_seed():
    a, b, c = _generate(14, 9), _generate(14, 9), _generate(14, 10)
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y)
    assert a.rows.dtype == np.int32 and a.vals.dtype == np.float32
    assert a.nnz != c.nnz or not np.array_equal(a.cols, c.cols)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_the_cell_runs_correct_on_the_cpu(root, monkeypatch):
    tiny.off_chip(monkeypatch)
    bench = spec.load_benchmark(root)
    for trace in (False, True):
        out = harness.run_cell(CELL, 2 ** 33 + 1, 0.3, trace, root=root)
        assert out["correct"] is True and out["failed"] == 0
        assert set(out["metrics"]) <= {
            m["name"] for m in spec.metrics_for(bench, CELL, trace)}


def test_the_control_fails_the_limit_on_three_seeds(root, monkeypatch):
    from bench import control
    tiny.off_chip(monkeypatch)
    control.install(monkeypatch.setattr)
    for seed in (1, 2, 2 ** 33 + 5):
        out = harness.run_cell(CELL, seed, 0.3, False, root=root)
        assert out["correct"] is False, (seed, out["checks"])
