"""repro.spmm — multi-RHS engine: SELL-C-σ, kernels, selector-k, batching.

The core property (ISSUE acceptance): for every storage format and
k in {1, 8, 32, 128}, ``spmm(A, X)`` equals k stacked single-vector oracle
calls to fp32 tolerance — including the mawi-style skewed generator.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (MachineSpec, convert, coo_to_csr, matrix_stats,
                        select, select_algorithm, spmv, to_coo)
from repro.core.spmv import spmv_coo
from repro.data import matrices
from repro.kernels.tiling import coo_to_tiled
from repro import spmm as M

RTOL, ATOL = 2e-4, 2e-4


def _matrices():
    return {
        "uniform": to_coo(*matrices.uniform(230, 190, 2200, seed=0)),
        "mawi_like": to_coo(*matrices.mawi_like(260, 240, 2400, 0.3,
                                                seed=1)),
    }


def _make(fmt, coo):
    if fmt == "coo":
        return coo
    if fmt == "csr":
        return coo_to_csr(coo)
    if fmt == "blocked":
        return convert(coo, "bcohc", beta=64)
    if fmt == "tiled":
        return coo_to_tiled(coo, "csb", beta=128)
    if fmt == "sellcs":
        return M.coo_to_sellcs(coo, c=64, sigma=128)
    raise ValueError(fmt)


@pytest.mark.parametrize("k", [1, 8, 32, 128])
@pytest.mark.parametrize("fmt", ["coo", "csr", "blocked", "tiled",
                                 "sellcs"])
def test_spmm_equals_stacked_spmv(fmt, k):
    for name, coo in _matrices().items():
        mat = _make(fmt, coo)
        n = coo.shape[1]
        X = jnp.asarray(np.random.default_rng(k).standard_normal(
            (n, k)).astype(np.float32))
        Y = M.spmm(mat, X)
        stacked = jnp.stack([spmv_coo(coo, X[:, j]) for j in range(k)],
                            axis=1)
        np.testing.assert_allclose(np.asarray(Y), np.asarray(stacked),
                                   rtol=RTOL, atol=ATOL, err_msg=name)


def test_spmm_1d_input_is_spmv():
    coo = _matrices()["uniform"]
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        coo.shape[1]).astype(np.float32))
    y = M.spmm(coo_to_csr(coo), x)
    assert y.ndim == 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(spmv_coo(coo, x)),
                               rtol=RTOL, atol=ATOL)


# --------------------------------------------------------------------------
# SELL-C-σ structure
# --------------------------------------------------------------------------
def test_sellcs_roundtrip_exact():
    for name, coo in _matrices().items():
        for c, sigma in ((8, 8), (64, 128), (128, 10 ** 6)):
            sc = M.coo_to_sellcs(coo, c=c, sigma=sigma)
            rt = sc.to_coo()
            assert rt.nnz == coo.nnz, (name, c, sigma)
            np.testing.assert_allclose(np.asarray(rt.todense()),
                                       np.asarray(coo.todense()),
                                       atol=1e-6, err_msg=name)


def test_sellcs_sigma_sorting_reduces_padding():
    """A global σ sort can only shrink (or keep) the padded footprint vs
    no sorting (σ = C): rows of similar length share slices."""
    coo = to_coo(*matrices.powerlaw(400, 300, 4000, 1.8, seed=2))
    unsorted = M.coo_to_sellcs(coo, c=32, sigma=32)
    glob = M.coo_to_sellcs(coo, c=32, sigma=10 ** 6)
    assert glob.padded_nnz <= unsorted.padded_nnz
    assert glob.fill_ratio >= unsorted.fill_ratio
    # and within each σ-window, slice widths are non-increasing
    widths = np.diff(np.asarray(glob.slice_ptr))
    assert np.all(np.diff(widths) <= 0)


def test_sellcs_convert_registration():
    coo = _matrices()["uniform"]
    sc = convert(coo, "sellcs", c=32, sigma=64)
    assert isinstance(sc, M.SellCS) and sc.chunk == 32
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        coo.shape[1]).astype(np.float32))
    np.testing.assert_allclose(np.asarray(spmv(sc, x)),
                               np.asarray(spmv_coo(coo, x)),
                               rtol=RTOL, atol=ATOL)


# --------------------------------------------------------------------------
# Pallas kernels (interpret mode), k-tiled grids
# --------------------------------------------------------------------------
@pytest.mark.parametrize("k,k_tile", [(8, 4), (5, 2), (8, 8)])
def test_kernels_interpret_match_reference(k, k_tile):
    coo = _matrices()["mawi_like"]
    n = coo.shape[1]
    X = jnp.asarray(np.random.default_rng(7).standard_normal(
        (n, k)).astype(np.float32))
    dense = np.asarray(coo.todense()) @ np.asarray(X)

    ts = coo_to_tiled(coo, "csb", beta=128)
    np.testing.assert_allclose(
        np.asarray(M.tiled_spmm(ts, X, k_tile=k_tile, interpret=True)),
        dense, rtol=RTOL, atol=ATOL)
    csr = coo_to_csr(coo)
    np.testing.assert_allclose(
        np.asarray(M.csr_spmm(csr, X, k_tile=k_tile, interpret=True)),
        dense, rtol=RTOL, atol=ATOL)
    sc = M.coo_to_sellcs(coo, c=64, sigma=128)
    np.testing.assert_allclose(
        np.asarray(M.sellcs_spmm(sc, X, k_tile=k_tile, interpret=True)),
        dense, rtol=RTOL, atol=ATOL)


def test_sellcs_slots_gather_kernel_edge_streams():
    """The DMA-gather kernel against its jnp twin on streams the σ-sorted
    converter never makes but shards and padding do: slice ids that
    revisit earlier slices (each finished slice is added into Y, never
    overwritten), a width-row count that is no multiple of the grid
    step, stored zeros mid-row, and (interpret mode only) a k-tile below
    one lane, with X padded to a multiple of it."""
    from repro.spmm.kernels import _pad_k, sellcs_slots
    from repro.spmm.reference import sellcs_slots_ref
    rng = np.random.default_rng(5)
    W, C, S, n = 37, 8, 5, 20
    data = rng.standard_normal((W, C)).astype(np.float32)
    data[rng.random((W, C)) < 0.3] = 0.0
    cols = rng.integers(0, n, (W, C)).astype(np.int32)
    slice_of = rng.integers(0, S, W).astype(np.int32)
    for k, kt in ((3, 3), (5, 2)):
        x = jnp.asarray(rng.standard_normal((n, k)).astype(np.float32))
        y = sellcs_slots(jnp.asarray(data), jnp.asarray(cols),
                         jnp.asarray(slice_of), _pad_k(x, kt),
                         num_slices=S, chunk=C, k_tile=kt, interpret=True)
        ref = sellcs_slots_ref(jnp.asarray(data), jnp.asarray(cols),
                               jnp.asarray(slice_of), x, num_slices=S,
                               chunk=C)
        assert y.shape[0] == ref.shape[0] and y.shape[1] % kt == 0
        np.testing.assert_allclose(np.asarray(y[:, :k]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_sellcs_slots_rejects_unaligned_widths():
    """``choose_k_tile`` owns the width: ``sellcs_slots`` pads nothing, so
    an X narrower than a whole number of k-tiles, or (for Mosaic) a k-tile
    that is not a lane multiple, is refused before anything lowers."""
    from repro.spmm.kernels import sellcs_slots
    W, C, S, n = 8, 8, 2, 16
    data = jnp.ones((W, C), jnp.float32)
    cols = jnp.zeros((W, C), jnp.int32)
    slice_of = jnp.zeros((W,), jnp.int32)
    kw = dict(num_slices=S, chunk=C)
    with pytest.raises(ValueError, match="multiple of k_tile"):
        sellcs_slots(data, cols, slice_of, jnp.ones((n, 5), jnp.float32),
                     k_tile=2, interpret=True, **kw)
    with pytest.raises(ValueError, match="Mosaic"):
        sellcs_slots(data, cols, slice_of, jnp.ones((n, 64), jnp.float32),
                     k_tile=64, **kw)


def test_auto_impl_resolves_per_format(monkeypatch):
    """impl="auto" names the path that runs: on a TPU backend the Pallas
    kernel only where one lowers (SELL-C-σ forward, general storage), the
    XLA reference for every other format and op — and the reference
    everywhere off the TPU. Operator plans record it in impl and label."""
    import jax
    from repro.spmm.kernels import resolve_impl
    from repro.spmm.operator import SparseOperator
    from repro.core import PlanSpec
    coo = _matrices()["uniform"]
    sc = M.coo_to_sellcs(coo, c=64, sigma=128)
    sq = to_coo(*matrices.mesh2d(12))
    sym = to_coo(np.concatenate([np.asarray(sq.rows), np.asarray(sq.cols)]),
                 np.concatenate([np.asarray(sq.cols), np.asarray(sq.rows)]),
                 np.concatenate([np.asarray(sq.data)] * 2), sq.shape)
    ssym = M.coo_to_sellcs(sym, structure="symmetric")
    others = (coo, coo_to_csr(coo), coo_to_tiled(coo, "csb", beta=128))
    assert resolve_impl("auto", sc) == "ref"          # CPU backend
    op = SparseOperator.from_coo(coo, PlanSpec(algorithm="parcrs"))
    assert op.plan.impl == "ref" and op.plan.label == "parcrs[ref]"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_impl("auto", sc) == "pallas"
    assert resolve_impl("auto", sc, "T") == "ref"
    assert resolve_impl("auto", ssym) == "ref"
    assert all(resolve_impl("auto", m) == "ref" for m in others)
    assert resolve_impl("pallas", coo_to_csr(coo)) == "pallas"
    op = SparseOperator.from_coo(coo, PlanSpec(algorithm="bcohc"))
    assert op.plan.impl == "ref" and op.plan.label == "bcohc[ref]"
    op = SparseOperator.from_coo(coo, PlanSpec(algorithm="sellcs"))
    assert op.plan.impl == "pallas" and op.plan.label == "sellcs[pallas]"


def test_off_path_kernels_refuse_mosaic():
    """Kernels that do not lower on Mosaic raise for interpret=False
    instead of running something else; interpret mode still runs them."""
    from repro.kernels import ops as kops
    coo = _matrices()["uniform"]
    X = jnp.ones((coo.shape[1], 4), jnp.float32)
    ts = coo_to_tiled(coo, "csb", beta=128)
    csr = coo_to_csr(coo)
    for call in (lambda: M.tiled_spmm(ts, X),
                 lambda: M.csr_spmm(csr, X),
                 lambda: kops.bsr_spmv(ts, X[:, 0]),
                 lambda: kops.merge_spmv(csr, X[:, 0]),
                 lambda: M.spmm(csr, X, impl="pallas")):
        with pytest.raises(NotImplementedError, match="Mosaic"):
            call()


def test_choose_k_tile_roofline():
    from repro.spmm.kernels import sellcs_vmem_bytes
    from repro.core.convert import VMEM_BUDGET_BYTES
    # one lane at least: HBM rows are DMA'd whole lanes, so a narrower
    # tile would cost the same VMEM and the same DMAs
    for k in (1, 7, 32, 128):
        assert M.choose_k_tile(k) == 128
    # the chosen tile fits the budget, is a lane multiple and never wider
    # than k rounded up to a lane
    for k in (1, 32, 200, 256, 1000, 1024, 4096):
        kt = M.choose_k_tile(k)
        assert kt % 128 == 0 and kt <= -(-k // 128) * 128
        assert sellcs_vmem_bytes(kt) <= VMEM_BUDGET_BYTES
    # X and Y stay in HBM, so only the budget (never n) sizes the tile
    wide = M.choose_k_tile(1024, vmem_budget=4 * VMEM_BUDGET_BYTES)
    assert wide > M.choose_k_tile(1024)
    assert sellcs_vmem_bytes(wide) <= 4 * VMEM_BUDGET_BYTES
    assert M.choose_k_tile(200, vmem_budget=4 * VMEM_BUDGET_BYTES) == 256


def test_arithmetic_intensity_monotone_in_k():
    from repro.roofline import ridge_intensity, spmm_arithmetic_intensity
    ais = [spmm_arithmetic_intensity(10 ** 6, 10 ** 5, 10 ** 5, k)
           for k in (1, 2, 4, 8, 16, 32, 64, 128, 256)]
    assert all(b > a for a, b in zip(ais, ais[1:]))
    assert ridge_intensity() > 0


# --------------------------------------------------------------------------
# selector / autotune k-integration
# --------------------------------------------------------------------------
def test_select_k1_unchanged():
    for name, coo in _matrices().items():
        s = matrix_stats(coo)
        for nd in (1, 256):
            mach = MachineSpec(num_devices=nd)
            for num in (1, 500, 50_000):
                assert select(s, mach, num, k=1) == \
                    select_algorithm(s, mach, num), (name, nd, num)


def test_select_k_accepts_and_returns_candidate():
    s = matrix_stats(_matrices()["mawi_like"])
    assert s.has_dense_row
    pick = select(s, MachineSpec(num_devices=1), 5000, k=64)
    from repro.core.selector import ROW_SPLITTING
    assert pick in ROW_SPLITTING + ("sellcs",)


def test_spmm_cost_scale_sublinear():
    from repro.core import spmm_cost_scale
    s = matrix_stats(_matrices()["uniform"])
    c1 = spmm_cost_scale("parcrs", s, 1)
    c64 = spmm_cost_scale("parcrs", s, 64)
    assert c1 == pytest.approx(1.0)
    assert 1.0 < c64 < 64.0          # the whole point of batching


def test_autotune_k_smoke():
    from repro.core import autotune
    coo = to_coo(*matrices.uniform(150, 150, 1500, seed=4))
    best, results = autotune(coo, num_spmvs=3, reps=1, k=8,
                             algorithms=("parcrs", "sellcs"))
    assert best.k == 8 and best.k_tile is not None and best.k_tile >= 1
    assert {r.algorithm for r in results} == {"parcrs", "sellcs"}


# --------------------------------------------------------------------------
# distributed (format x schedule x k) scoring
# --------------------------------------------------------------------------
def test_spmm_distributed_collective_s_chunked_overlap():
    """ISSUE 3 acceptance: the chunked merge model's exposed collective
    seconds are strictly below the monolithic model for k >= 8 on >= 2
    devices (the psum hides under the slice stream)."""
    from repro.roofline import (spmm_distributed_collective_s,
                                spmm_distributed_time)
    m = n = 100_000
    nnz = 10_000_000
    for k in (8, 64, 256):
        for P in (2, 8):
            mono = spmm_distributed_collective_s(m, n, k, P, "merge",
                                                 nnz=nnz, num_chunks=1)
            assert mono > 0.0
            for c in (2, 4, 8):
                over = spmm_distributed_collective_s(m, n, k, P, "merge",
                                                     nnz=nnz, num_chunks=c)
                assert 0.0 < over < mono, (k, P, c)
            # the time model inherits the same strict ordering
            assert spmm_distributed_time(m, n, k, P, "merge", nnz=nnz,
                                         num_chunks=4) < \
                spmm_distributed_time(m, n, k, P, "merge", nnz=nnz,
                                      num_chunks=1)
    # "row" has no collective to chunk; single device has no wire at all
    assert spmm_distributed_collective_s(m, n, 8, 8, "row", nnz=nnz,
                                         num_chunks=4) == 0.0
    assert spmm_distributed_collective_s(m, n, 8, 1, "merge", nnz=nnz,
                                         num_chunks=4) == 0.0
    # per-psum launch cost keeps the optimum finite: absurd depths lose
    tiny = spmm_distributed_collective_s(500, 500, 8, 8, "merge", nnz=4000,
                                         num_chunks=1)
    assert spmm_distributed_collective_s(500, 500, 8, 8, "merge", nnz=4000,
                                         num_chunks=10_000) > tiny
    import pytest as _pytest
    with _pytest.raises(ValueError):
        spmm_distributed_collective_s(m, n, 8, 8, "merge", nnz=nnz,
                                      num_chunks=0)


def test_spmm_distributed_traffic_model_properties():
    from repro.roofline import (spmm_distributed_time,
                                spmm_distributed_traffic)
    m = n = 100_000
    nnz = 10_000_000
    # merge is the only schedule with collective bytes, and they grow in k
    hbm_r, coll_r = spmm_distributed_traffic(m, n, 8, 8, "row", nnz=nnz)
    hbm_m, coll_m = spmm_distributed_traffic(m, n, 8, 8, "merge", nnz=nnz)
    assert coll_r == 0.0 and coll_m > 0.0
    _, coll_m64 = spmm_distributed_traffic(m, n, 64, 8, "merge", nnz=nnz)
    assert coll_m64 > coll_m
    # a dominant dense row bounds the row schedule's critical shard below
    hot = nnz // 2
    hbm_hot, _ = spmm_distributed_traffic(m, n, 8, 8, "row", nnz=nnz,
                                          max_row_nnz=hot)
    assert hbm_hot > hbm_r
    # one device degrades both schedules to the same single-device stream
    t1r = spmm_distributed_time(m, n, 8, 1, "row", nnz=nnz)
    t1m = spmm_distributed_time(m, n, 8, 1, "merge", nnz=nnz)
    assert t1r == pytest.approx(t1m)
    with pytest.raises(ValueError):
        spmm_distributed_traffic(m, n, 8, 8, "diagonal", nnz=nnz)


def test_select_distributed_schedule_tracks_skew_and_k():
    """The joint grid: heavy skew -> merge at small k (psum is cheap),
    row at large k (psum bytes scale with k); uniform -> always row. The
    chunking axis does not flip either crossover: even fully pipelined,
    the last chunk's psum drain keeps merge above row at large k."""
    from repro.core import select_distributed
    from repro.core.selector import MatrixStats
    mawi = MatrixStats(m=230_000, n=230_000, nnz=270_000_000,
                       max_row_nnz=120_000_000, row_var=1e9)
    uni = MatrixStats(m=230_000, n=230_000, nnz=270_000_000,
                      max_row_nnz=2_000, row_var=10.0)
    assert select_distributed(mawi, k=1, num_devices=8)[1] == "merge"
    assert select_distributed(mawi, k=64, num_devices=8)[1] == "row"
    for k in (1, 8, 64):
        assert select_distributed(uni, k=k, num_devices=8)[1] == "row"
    with pytest.raises(ValueError):
        select_distributed(uni, k=0, num_devices=8)
    with pytest.raises(ValueError):
        select_distributed(uni, k=1, num_devices=0)


def test_select_distributed_records_num_chunks():
    """The grid gained a chunking axis: the choice is a named 3-tuple, the
    row schedule always reports 1, and a merge-winning matrix with real
    psum bytes picks a pipelined depth > 1."""
    from repro.core import CHUNK_CANDIDATES, select_distributed
    from repro.core.selector import DistributedChoice, MatrixStats
    mawi = MatrixStats(m=230_000, n=230_000, nnz=270_000_000,
                       max_row_nnz=120_000_000, row_var=1e9)
    uni = MatrixStats(m=230_000, n=230_000, nnz=270_000_000,
                      max_row_nnz=2_000, row_var=10.0)
    choice = select_distributed(mawi, k=1, num_devices=8)
    assert isinstance(choice, DistributedChoice)
    assert choice.schedule == "merge" and choice.num_chunks in \
        CHUNK_CANDIDATES and choice.num_chunks > 1
    algo, sched, nc, mesh, cx, st, gx = choice   # unpacks like a tuple
    assert (algo, sched, nc, mesh, cx, st, gx) == tuple(choice)
    assert st == "general"                    # nothing symmetric here
    assert gx in ("upfront", "overlap")
    assert mesh[0] * mesh[1] == 8
    assert select_distributed(uni, k=8, num_devices=8).num_chunks == 1


def test_select_num_devices_keyword():
    """select(num_devices=P>1) routes through the joint grid and still
    returns a plain format name; num_devices=None keeps the old path."""
    from repro.core.selector import DISTRIBUTED_ALGOS
    for name, coo in _matrices().items():
        s = matrix_stats(coo)
        pick = select(s, num_spmvs=1000, k=64, num_devices=8)
        assert pick in DISTRIBUTED_ALGOS, (name, pick)
        assert select(s, MachineSpec(1), 1000, k=1) == \
            select_algorithm(s, MachineSpec(1), 1000)


def test_select_num_devices_threads_throughput_through():
    """Regression: select(num_devices>1) used to silently drop the
    caller's measured throughput table — the one path users tune. A table
    that makes one distributed-capable format overwhelmingly faster must
    flip the pick both ways, and omitting the table keeps the pure-model
    choice."""
    from repro.core import select_distributed
    s = matrix_stats(_matrices()["uniform"])
    fast_parcrs = {"parcrs": 100.0, "sellcs": 1.0}
    fast_sellcs = {"parcrs": 1.0, "sellcs": 100.0}
    assert select(s, num_spmvs=1000, k=64, num_devices=8,
                  throughput=fast_parcrs) == "parcrs"
    assert select(s, num_spmvs=1000, k=64, num_devices=8,
                  throughput=fast_sellcs) == "sellcs"
    # the DistributedChoice path accepts it too, and a missing sellcs
    # entry is defaulted from the csb prior like the 1-device selector
    c = select_distributed(s, k=64, num_devices=8,
                           throughput={"parcrs": 1.0, "csb": 100.0})
    assert c.algorithm == "sellcs"
    # no table -> unchanged pure-model scoring
    assert select(s, num_spmvs=1000, k=64, num_devices=8) == \
        select_distributed(s, k=64, num_devices=8).algorithm


def test_sellcs_storage_bytes_counts_every_array():
    """ISSUE 4 satellite: storage_bytes claimed "faithful SELL-C-σ cost"
    while omitting the slice_of and row_len int32 arrays; it must equal
    the summed nbytes of every member array exactly."""
    for coo in _matrices().values():
        sc = M.coo_to_sellcs(coo)
        actual = (sc.data.nbytes + sc.cols.nbytes + sc.slice_ptr.nbytes
                  + sc.slice_of.nbytes + sc.row_perm.nbytes
                  + sc.row_len.nbytes)
        assert sc.storage_bytes() == actual
    # empty matrix: the fixed-size arrays still count
    from repro.core import to_coo
    z = np.zeros(0, np.int32)
    se = M.coo_to_sellcs(to_coo(z, z, np.zeros(0, np.float32), (6, 4)), c=2)
    actual = (se.data.nbytes + se.cols.nbytes + se.slice_ptr.nbytes
              + se.slice_of.nbytes + se.row_perm.nbytes + se.row_len.nbytes)
    assert se.storage_bytes() == actual


def test_spmm_distributed_traffic_compact_x():
    """ISSUE 5 satellite: the compact_x X term is exactly nnz-proportional
    (min(nnz/P, n) rows via spmm_touched_fraction), never exceeds the
    replicated figure, honors a measured per-shard n_touched, and leaves
    the collective bytes alone (compaction shrinks reads, not the psum)."""
    from repro.roofline import (spmm_distributed_traffic,
                                spmm_touched_fraction)
    m = n = 100_000
    dt = 4
    P = 8
    mat_bytes = 1e6          # pinned so only the X term varies with nnz
    for sched in ("row", "merge"):
        hbm_rep, coll_rep = spmm_distributed_traffic(
            m, n, 64, P, sched, matrix_bytes=mat_bytes, nnz=80_000)
        prev = None
        for nnz in (0, 8_000, 80_000, 160_000):
            hbm_c, coll_c = spmm_distributed_traffic(
                m, n, 64, P, sched, matrix_bytes=mat_bytes, nnz=nnz,
                compact_x=True)
            # X term == min(nnz/P, n) * k * dt exactly — nnz-proportional
            expect = min(nnz / P, n) * 64 * dt
            base = hbm_c - expect
            if prev is None:
                prev = base
            assert base == pytest.approx(prev), (sched, nnz)
            assert hbm_c <= hbm_rep + 1e-9, (sched, nnz)
            assert coll_c == coll_rep, (sched, nnz)
        # saturated columns: nnz/P >= n caps at the replicated figure
        hbm_sat, _ = spmm_distributed_traffic(
            m, n, 64, P, sched, matrix_bytes=mat_bytes,
            nnz=100 * n * P, compact_x=True)
        assert hbm_sat == pytest.approx(hbm_rep)
    # measured n_touched overrides the nnz bound (and still caps at n)
    hbm_meas, _ = spmm_distributed_traffic(
        m, n, 64, P, "row", matrix_bytes=mat_bytes, nnz=80_000,
        compact_x=True, n_touched=500.0)
    hbm_model, _ = spmm_distributed_traffic(
        m, n, 64, P, "row", matrix_bytes=mat_bytes, nnz=80_000,
        compact_x=True)
    assert hbm_model - hbm_meas == pytest.approx(
        (80_000 / P - 500.0) * 64 * dt)
    assert spmm_touched_fraction(n, 80_000, P) == pytest.approx(
        80_000 / P / n)
    assert spmm_touched_fraction(n, 10**12, P) == 1.0
    assert spmm_touched_fraction(0, 10, P) == 0.0
    # the 2-D mesh composes: the compact X term divides by P_model too
    hbm1, _ = spmm_distributed_traffic(
        m, n, 64, P, "merge", matrix_bytes=mat_bytes, nnz=8_000,
        compact_x=True)
    hbm2, _ = spmm_distributed_traffic(
        m, n, 64, P, "merge", matrix_bytes=mat_bytes, nnz=8_000,
        compact_x=True, model_devices=2)
    x_and_y = (8_000 / P + m) * 64 * dt        # k-proportional terms
    assert hbm1 - hbm2 == pytest.approx(x_and_y / 2)


def test_select_distributed_compact_x_flip():
    """ISSUE 5 satellite: the selector flips to compaction on a
    highly-sparse-columns case (a shard touches far fewer than n columns)
    and refuses it on a dense-columns case (nnz/P >= n makes the gather a
    modelled wash — the tie keeps replication)."""
    from repro.core import select_distributed
    from repro.core.selector import MatrixStats
    # sparse columns: 8 shards x 50k nnz each touch <= 50k of 2M columns
    sparse = MatrixStats(m=2_000_000, n=2_000_000, nnz=400_000,
                         max_row_nnz=20, row_var=1.0)
    pick = select_distributed(sparse, k=64, num_devices=8)
    assert pick.algorithm == "sellcs" and pick.compact_x is True
    # dense columns: nnz/P >> n — compaction cannot shrink the X term
    dense = MatrixStats(m=230_000, n=230_000, nnz=270_000_000,
                        max_row_nnz=2_000, row_var=10.0)
    assert select_distributed(dense, k=64, num_devices=8).compact_x is False
    # single device keeps the degenerate default
    assert select_distributed(dense, k=1, num_devices=1).compact_x is False


def test_sharded_sellcs_storage_bytes_counts_col_map():
    """ISSUE 5 satellite: ShardedSellCS.storage_bytes must equal the
    summed nbytes of every member array — including the compact_x col_map
    / n_touched and any baked chunk plan — so the paper's "472
    multiplications to amortize" convert-cost comparisons stay honest."""
    from repro.spmm import partition_sellcs_nnz, partition_sellcs_rows

    def expected(sh):
        total = (sh.data.nbytes + sh.cols.nbytes + sh.slice_of.nbytes
                 + sh.slice_offset.nbytes + sh.row_perm.nbytes)
        for opt in (sh.row_counts, sh.col_map, sh.n_touched):
            if opt is not None:
                total += opt.nbytes
        if sh.chunk_plan is not None:
            for sp in sh.chunk_plan[1]:
                total += (sp.data.nbytes + sp.cols.nbytes
                          + sp.slice_of.nbytes)
                for opt in (sp.sub, sp.col_map, sp.n_touched):
                    if opt is not None:
                        total += opt.nbytes
            for opt in sh.chunk_plan[2:]:
                if opt is not None:
                    total += opt.nbytes
        return total

    for coo in _matrices().values():
        sc = M.coo_to_sellcs(coo, c=16, sigma=64)
        for cf in (False, True):
            for sh in (partition_sellcs_rows(sc, 4, compact_x=cf),
                       partition_sellcs_nnz(sc, 4, compact_x=cf),
                       partition_sellcs_nnz(sc, 4, num_chunks=3,
                                            compact_x=cf)):
                assert sh.storage_bytes() == expected(sh), cf
        # the col_map is real storage: compaction must cost more bytes
        assert partition_sellcs_rows(sc, 4, compact_x=True).storage_bytes() \
            > partition_sellcs_rows(sc, 4).storage_bytes()


def test_autotune_num_devices_records_schedule():
    from repro.core import CHUNK_CANDIDATES, autotune
    coo = to_coo(*matrices.uniform(150, 150, 1500, seed=4))
    best, results = autotune(coo, num_spmvs=3, reps=1, k=8, num_devices=8,
                             algorithms=("parcrs", "sellcs"))
    assert best.num_devices == 8
    assert all(r.schedule in ("row", "merge") for r in results)
    assert all(r.dist_model_s is not None and r.dist_model_s > 0
               for r in results)
    # ISSUE 3 acceptance: the tuner records a num_chunks choice — 1 for
    # the collective-free row schedule, a CHUNK_CANDIDATES entry for merge
    assert all(r.num_chunks == 1 for r in results if r.schedule == "row")
    assert all(r.num_chunks in CHUNK_CANDIDATES for r in results
               if r.schedule == "merge")
    assert best.num_chunks is not None and best.num_chunks >= 1
    # ISSUE 5: the tuner records the compact-gather choice; only sellcs
    # can execute it, so every other format must record False
    assert all(r.compact_x in (False, True) for r in results)
    assert all(r.compact_x is False for r in results
               if r.algorithm != "sellcs")


# --------------------------------------------------------------------------
# request batching (serve path)
# --------------------------------------------------------------------------
def test_batch_spmv_matches_individual():
    coo = _matrices()["mawi_like"]
    csr = coo_to_csr(coo)
    rng = np.random.default_rng(9)
    xs = [jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float32))
          for _ in range(6)]
    ys = M.batch_spmv(csr, xs)
    for x, y in zip(xs, ys):
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(spmv_coo(coo, x)),
                                   rtol=RTOL, atol=ATOL)


def test_request_batcher_flush_and_padding():
    coo = _matrices()["uniform"]
    sc = M.coo_to_sellcs(coo, c=32, sigma=64)
    b = M.RequestBatcher(sc, max_batch=8)
    rng = np.random.default_rng(11)
    xs = [jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float32))
          for _ in range(11)]
    rids = [b.submit(x) for x in xs]
    assert b.pending == 11
    out = b.drain()
    assert b.pending == 0 and b.flushes == 2 and b.served == 11
    assert sorted(out) == sorted(rids)
    for rid, x in zip(rids, xs):
        np.testing.assert_allclose(np.asarray(out[rid]),
                                   np.asarray(spmv_coo(coo, x)),
                                   rtol=RTOL, atol=ATOL)


def test_batcher_rejects_bad_shape():
    coo = _matrices()["uniform"]
    with pytest.raises(ValueError):
        M.batch_spmv(coo_to_csr(coo),
                     [jnp.zeros((coo.shape[1] + 1,), jnp.float32)])
    # submit() checks shape up front so a bad request can never corrupt a
    # flush batch that was already popped from the queue
    b = M.RequestBatcher(coo_to_csr(coo), max_batch=4)
    with pytest.raises(ValueError):
        b.submit(jnp.zeros((coo.shape[1] + 1,), jnp.float32))
    assert b.pending == 0


def test_batcher_partial_flush_and_interleaving():
    """A flush below max_batch serves exactly the queued requests; requests
    submitted after a flush land in the next one, in order."""
    coo = _matrices()["uniform"]
    csr = coo_to_csr(coo)
    b = M.RequestBatcher(csr, max_batch=8)
    rng = np.random.default_rng(21)
    xs = [jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float32))
          for _ in range(5)]
    rids = [b.submit(x) for x in xs[:3]]
    out1 = b.flush()                      # partial: 3 of max 8
    assert sorted(out1) == sorted(rids) and b.pending == 0
    assert b.flushes == 1 and b.served == 3
    rids2 = [b.submit(x) for x in xs[3:]]
    out2 = b.flush()
    assert sorted(out2) == sorted(rids2) and b.served == 5
    for rid, x in zip(rids + rids2, xs):
        np.testing.assert_allclose(np.asarray((out1 | out2)[rid]),
                                   np.asarray(spmv_coo(coo, x)),
                                   rtol=RTOL, atol=ATOL)
    assert b.flush() == {}                # empty queue is a no-op


def test_batcher_scatter_order_is_per_ticket_not_fifo():
    """Result columns scatter back by ticket even when consumed out of
    submission order."""
    coo = _matrices()["mawi_like"]
    sc = M.coo_to_sellcs(coo, c=32, sigma=64)
    b = M.RequestBatcher(sc, max_batch=16)
    rng = np.random.default_rng(23)
    xs = [jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float32))
          for _ in range(7)]
    rids = [b.submit(x) for x in xs]
    out = b.drain()
    for rid, x in sorted(zip(rids, xs), key=lambda t: -t[0]):  # reversed
        np.testing.assert_allclose(np.asarray(out[rid]),
                                   np.asarray(spmv_coo(coo, x)),
                                   rtol=RTOL, atol=ATOL)


def test_batcher_pad_pow2_off_uses_exact_k():
    coo = _matrices()["uniform"]
    seen = []

    def probe(_mat, X):
        seen.append(X.shape[1])
        return M.spmm_ref(_mat, X)

    b = M.RequestBatcher(coo_to_csr(coo), max_batch=8, pad_pow2=False,
                         spmm_fn=probe)
    rng = np.random.default_rng(29)
    xs = [jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float32))
          for _ in range(3)]
    rids = [b.submit(x) for x in xs]
    out = b.drain()
    assert seen == [3]                    # exact k, no pow2 padding
    for rid, x in zip(rids, xs):
        np.testing.assert_allclose(np.asarray(out[rid]),
                                   np.asarray(spmv_coo(coo, x)),
                                   rtol=RTOL, atol=ATOL)


def test_batcher_mixed_dtype_queue_promotes():
    """Regression: flush() used to build X with batch[0]'s dtype, silently
    downcasting every later request — a float16 head request truncated its
    float32 neighbours. The batch dtype is now the promotion over the whole
    queue (and batch_spmv mirrors it)."""
    coo = _matrices()["uniform"]
    csr = coo_to_csr(coo)
    seen = []

    def probe(mat, X):
        seen.append(X.dtype)
        return M.spmm_ref(mat, X)

    rng = np.random.default_rng(37)
    x16 = jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float16))
    x32 = jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float32))
    b = M.RequestBatcher(csr, max_batch=8, spmm_fn=probe)
    r16, r32 = b.submit(x16), b.submit(x32)      # low-precision head
    out = b.flush()
    assert seen == [jnp.float32]
    # the f32 request keeps full precision (f16 truncation would miss)
    np.testing.assert_allclose(np.asarray(out[r32]),
                               np.asarray(spmv_coo(coo, x32)),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(out[r16]),
                               np.asarray(spmv_coo(coo, x16.astype(
                                   jnp.float32))),
                               rtol=1e-2, atol=1e-2)
    # batch_spmv takes the same promotion path
    seen.clear()
    ys = M.batch_spmv(csr, [x16, x32], spmm_fn=probe)
    assert seen == [jnp.float32]
    np.testing.assert_allclose(np.asarray(ys[1]),
                               np.asarray(spmv_coo(coo, x32)),
                               rtol=RTOL, atol=ATOL)


def test_batch_spmv_spmm_fn_override():
    """batch_spmv routes through a custom spmm_fn (the distributed serve
    path's hook) and still returns per-request results in input order."""
    coo = _matrices()["uniform"]
    csr = coo_to_csr(coo)
    calls = []

    def spmm_fn(mat, X):
        calls.append(X.shape)
        return M.spmm_ref(mat, X)

    rng = np.random.default_rng(31)
    xs = [jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float32))
          for _ in range(4)]
    ys = M.batch_spmv(csr, xs, spmm_fn=spmm_fn)
    assert calls == [(coo.shape[1], 4)]
    for x, y in zip(xs, ys):
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(spmv_coo(coo, x)),
                                   rtol=RTOL, atol=ATOL)
