"""The XLA paths of the TiledSparse format and of the merge-path plan
against the plain oracles, swept over shapes/dtypes/orderings, and the MoE
grouped-GEMM kernel against its oracle (interpret mode). The SELL-C-σ
kernels are tested in test_spmm.py."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import coo_to_csr, spmv_csr, spmv_dense_oracle, to_coo
from repro.data import matrices
from repro.kernels import coo_to_tiled, merge_plan, ops, ref


def _rand_x(n, dtype=np.float32, seed=7):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(n)
                       .astype(dtype))


SHAPES = [
    ("square", matrices.uniform(256, 256, 2000, 0)),
    ("tall", matrices.uniform(700, 120, 2600, 1)),
    ("wide", matrices.uniform(120, 700, 2600, 2)),
    ("mesh", matrices.mesh2d(17)),
    ("powerlaw", matrices.powerlaw(300, 300, 3000, 1.7, 3)),
    ("mawi", matrices.mawi_like(260, 260, 2200, 0.35, 4)),
    ("tiny", matrices.uniform(8, 128, 30, 5)),
    ("empty", (np.zeros(0, np.int32), np.zeros(0, np.int32),
               np.zeros(0, np.float32), (64, 256))),
]


@pytest.mark.parametrize("name,gen", SHAPES, ids=[s[0] for s in SHAPES])
@pytest.mark.parametrize("algo", ["csb", "csbh", "bcohch", "mergeb"])
def test_bsr_spmv_vs_ref(name, gen, algo):
    coo = to_coo(*gen)
    ts = coo_to_tiled(coo, algo, beta=128)
    x = _rand_x(coo.shape[1])
    np.testing.assert_allclose(np.asarray(ref.bsr_spmv_ref(ts, x)),
                               np.asarray(spmv_dense_oracle(coo, x)),
                               rtol=1e-4, atol=1e-4)


def test_bsr_spmv_bf16_tiles():
    coo = to_coo(*matrices.uniform(256, 256, 2000, 0))
    ts = coo_to_tiled(coo, "csb", beta=128, dtype=jnp.bfloat16)
    x = _rand_x(coo.shape[1])
    y = ref.bsr_spmv_ref(ts, x)
    yo = spmv_dense_oracle(coo, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yo),
                               rtol=5e-2, atol=5e-1)


@pytest.mark.parametrize("name,gen", SHAPES, ids=[s[0] for s in SHAPES])
@pytest.mark.parametrize("spans", [4, 32])
def test_merge_spmv_vs_ref(name, gen, spans):
    """The merge-path plan, multiplied in XLA, equals plain CSR SpMV."""
    coo = to_coo(*gen)
    csr = coo_to_csr(coo)
    x = _rand_x(coo.shape[1])
    plan = merge_plan(csr, spans)
    y = ref.merge_spmv_xla(plan.cols, plan.vals, plan.seg, plan.row_starts,
                           x, r_width=plan.r_width, m=coo.shape[0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(spmv_csr(csr, x)),
                               rtol=1e-4, atol=1e-4)


def test_merge_plan_balance():
    """Every span consumes the same number of merge operations (+-1 step)."""
    coo = to_coo(*matrices.mawi_like(260, 260, 2200, 0.35, 4))
    csr = coo_to_csr(coo)
    P = 16
    plan = merge_plan(csr, P)
    starts = np.asarray(plan.row_starts)
    nnz_counts = np.sum(np.asarray(plan.vals) != 0, axis=1)
    m, nnz = csr.shape[0], csr.nnz
    D = -(-(m + nnz) // P)
    # diag budget: rows closed + nnz consumed <= D per span
    rows_per = np.diff(starts)
    assert np.all(rows_per + nnz_counts <= D + 1)
    assert nnz_counts.sum() == nnz


@pytest.mark.parametrize("sizes", [
    [10, 200, 0, 90], [0, 0, 300, 0], [75, 75, 75, 75], [300, 0, 0, 0]])
def test_moe_group_matmul(sizes):
    rng = np.random.default_rng(0)
    E, K, N = 4, 256, 384
    T = int(np.sum(sizes))
    tokens = jnp.asarray(rng.standard_normal((T, K)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((E, K, N)).astype(np.float32) * .1)
    out = ops.moe_group_matmul(tokens, w, jnp.asarray(sizes, jnp.int32),
                               interpret=True)
    outr = ref.moe_group_matmul_ref(tokens, w, jnp.asarray(sizes, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr),
                               rtol=2e-4, atol=2e-4)


def test_tiled_fill_and_switches():
    """Hilbert ordering must not increase x-window switches vs row order
    on a matrix with 2D locality (the paper's locality claim, TPU proxy)."""
    coo = to_coo(*matrices.mesh2d(40))
    ts_row = coo_to_tiled(coo, "mergeb", beta=256)   # row-major order
    ts_hil = coo_to_tiled(coo, "bcohch", beta=256)   # hilbert both levels
    xr, yr = ts_row.window_switches()
    xh, yh = ts_hil.window_switches()
    assert ts_row.num_tiles == ts_hil.num_tiles
    assert xh + yh <= (xr + yr) * 1.5
    assert 0 < ts_row.fill_ratio <= 1
