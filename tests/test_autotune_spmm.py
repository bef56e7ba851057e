"""Autotuner (paper §8 future work) + the blocked format's multi-RHS
reference."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import autotune, to_coo
from repro.data import matrices
from repro.kernels import coo_to_tiled
from repro.kernels.ref import bsr_spmm_ref


def test_autotune_returns_consistent_best():
    coo = to_coo(*matrices.uniform(400, 400, 4000, 0))
    best, results = autotune(coo, num_spmvs=20, reps=2,
                             algorithms=("parcrs", "csb", "bcohc"),
                             betas=[64, 128])
    assert best.total_s == min(r.total_s for r in results)
    assert best.total_s == pytest.approx(
        best.convert_s + 20 * best.spmv_s)
    # flat algorithms carry beta=None; blocked ones a real beta
    assert any(r.beta is None for r in results)
    assert any(r.beta in (64, 128) for r in results)


def test_autotune_low_reuse_weights_conversion_only():
    coo = to_coo(*matrices.uniform(3000, 3000, 60000, 0))
    best1, results = autotune(coo, num_spmvs=0, reps=2,
                              algorithms=("parcrs", "bcohch"), betas=[256])
    # with zero reuse, total == conversion cost alone
    for r in results:
        assert r.total_s == pytest.approx(r.convert_s)
    # and the Hilbert sort costs strictly more to build than CSR
    conv = {r.algorithm: r.convert_s for r in results}
    assert conv["bcohch"] > conv["parcrs"]


@pytest.mark.parametrize("R", [1, 8, 33])
@pytest.mark.parametrize("algo", ["csb", "bcohch"])
def test_bsr_spmm_vs_dense(R, algo):
    coo = to_coo(*matrices.powerlaw(300, 260, 2600, seed=1))
    ts = coo_to_tiled(coo, algo, beta=128)
    X = jnp.asarray(np.random.default_rng(2)
                    .standard_normal((260, R)).astype(np.float32))
    Yd = np.asarray(coo.todense()) @ np.asarray(X)
    Yr = bsr_spmm_ref(ts, X)
    np.testing.assert_allclose(np.asarray(Yr), Yd, rtol=2e-4, atol=2e-4)


def test_spmm_columns_match_spmv():
    """Column j of SpMM == SpMV with x_j (consistency across kernels)."""
    coo = to_coo(*matrices.uniform(200, 220, 1800, 3))
    ts = coo_to_tiled(coo, "csb", beta=128)
    X = jnp.asarray(np.random.default_rng(4)
                    .standard_normal((220, 4)).astype(np.float32))
    Y = bsr_spmm_ref(ts, X)
    from repro.kernels.ref import bsr_spmv_ref
    for j in range(4):
        np.testing.assert_allclose(np.asarray(Y[:, j]),
                                   np.asarray(bsr_spmv_ref(ts, X[:, j])),
                                   rtol=1e-5, atol=1e-5)
