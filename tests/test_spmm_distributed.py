"""Distributed multi-RHS SpMM (repro.spmm.distributed) on 8 host-platform
devices, plus the degenerate-input guards of both partitioner families.

Device-backed tests run in SUBPROCESSES (the device-count flag must be set
before jax initializes; the rest of the suite keeps seeing 1 device).
Partitioner guard tests are pure host code and run in-process.
"""
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_sub(code: str, devices: int = 8, env=None) -> str:
    env = dict(os.environ, **(env or {}))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_spmm_distributed_matches_oracle_k_1_8_64():
    """ISSUE acceptance: both schedules match the spmm.reference oracle on
    8 devices for k in {1, 8, 64}, including the mawi skewed case."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.data import matrices
from repro.launch.mesh import make_mesh
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                        partition_sellcs_rows, spmm_coo,
                        spmm_merge_distributed, spmm_row_distributed)
mesh = make_mesh((8,), ("data",))
for name, gen in [("uniform", matrices.uniform(500, 430, 4000, 0)),
                  ("mawi_like", matrices.mawi_like(400, 400, 3000, 0.4, 1))]:
    coo = to_coo(*gen)
    sc = coo_to_sellcs(coo, c=16, sigma=64)
    row = partition_sellcs_rows(sc, 8)
    mrg = partition_sellcs_nnz(sc, 8)
    for k in (1, 8, 64):
        X = jnp.asarray(np.random.default_rng(k).standard_normal(
            (coo.shape[1], k)).astype(np.float32))
        yo = np.asarray(spmm_coo(coo, X))
        yr = np.asarray(spmm_row_distributed(row, X, mesh))
        ym = np.asarray(spmm_merge_distributed(mrg, X, mesh))
        np.testing.assert_allclose(yr, yo, rtol=1e-5, atol=1e-4,
                                   err_msg=f"{name} row k={k}")
        np.testing.assert_allclose(ym, yo, rtol=1e-5, atol=1e-4,
                                   err_msg=f"{name} merge k={k}")
    # SpMV rides along as the 1-D k=1 special case
    x = jnp.asarray(np.random.default_rng(9).standard_normal(
        coo.shape[1]).astype(np.float32))
    y = spmm_row_distributed(row, x, mesh)
    assert y.ndim == 1
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(spmm_coo(coo, x)),
                               rtol=1e-5, atol=1e-4)
print("distributed spmm oracle OK")
"""))


def test_spmm_distributed_pallas_interpret_kernel_body():
    """The shard_map bodies reuse the PR-1 k-tiled Pallas kernel
    (interpret mode off-TPU)."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.data import matrices
from repro.launch.mesh import make_mesh
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                        partition_sellcs_rows, spmm_coo,
                        spmm_merge_distributed, spmm_row_distributed)
mesh = make_mesh((8,), ("data",))
coo = to_coo(*matrices.mawi_like(300, 280, 2400, 0.4, 3))
sc = coo_to_sellcs(coo, c=16, sigma=64)
X = jnp.asarray(np.random.default_rng(5).standard_normal(
    (coo.shape[1], 8)).astype(np.float32))
yo = np.asarray(spmm_coo(coo, X))
yr = np.asarray(spmm_row_distributed(
    partition_sellcs_rows(sc, 8), X, mesh, impl="pallas_interpret",
    k_tile=4))
ym = np.asarray(spmm_merge_distributed(
    partition_sellcs_nnz(sc, 8), X, mesh, impl="pallas_interpret",
    k_tile=4))
np.testing.assert_allclose(yr, yo, rtol=1e-5, atol=1e-4)
np.testing.assert_allclose(ym, yo, rtol=1e-5, atol=1e-4)
print("distributed pallas kernel body OK")
"""))


def test_spmm_merge_chunked_matches_monolithic():
    """ISSUE 3 acceptance: the chunked/pipelined merge schedule is
    summation-equivalent (within fp tolerance) to the monolithic one for
    num_chunks in {1, 2, 8}, k in {1, 8, 64}, including the mawi dense-row
    case and the num_chunks > S degenerate setting."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.data import matrices
from repro.launch.mesh import make_mesh
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz, spmm_coo,
                        spmm_merge_distributed)
mesh = make_mesh((8,), ("data",))
for name, gen in [("uniform", matrices.uniform(500, 430, 4000, 0)),
                  ("mawi_like", matrices.mawi_like(400, 400, 3000, 0.4, 1))]:
    coo = to_coo(*gen)
    sc = coo_to_sellcs(coo, c=16, sigma=64)
    mrg = partition_sellcs_nnz(sc, 8)
    S = sc.num_slices
    for k in (1, 8, 64):
        X = jnp.asarray(np.random.default_rng(k).standard_normal(
            (coo.shape[1], k)).astype(np.float32))
        yo = np.asarray(spmm_coo(coo, X))
        y1 = np.asarray(spmm_merge_distributed(mrg, X, mesh, num_chunks=1))
        np.testing.assert_allclose(y1, yo, rtol=1e-5, atol=1e-4,
                                   err_msg=f"{name} k={k} monolithic")
        for c in (2, 8, S + 5):        # S + 5 > S: empty tail chunks
            yc = np.asarray(spmm_merge_distributed(mrg, X, mesh,
                                                   num_chunks=c))
            np.testing.assert_allclose(yc, y1, rtol=1e-6, atol=1e-5,
                                       err_msg=f"{name} k={k} chunks={c}")
    # the Pallas kernel body chunks identically (interpret mode off-TPU)
    X = jnp.asarray(np.random.default_rng(3).standard_normal(
        (coo.shape[1], 8)).astype(np.float32))
    yc = np.asarray(spmm_merge_distributed(
        mrg, X, mesh, impl="pallas_interpret", k_tile=4, num_chunks=3))
    np.testing.assert_allclose(yc, np.asarray(spmm_coo(coo, X)),
                               rtol=1e-5, atol=1e-4)
    # partition-time span plan (the serve path) gives the same answer
    baked = partition_sellcs_nnz(sc, 8, num_chunks=2)
    assert baked.chunk_plan is not None and baked.chunk_plan[0] == 2
    yb = np.asarray(spmm_merge_distributed(baked, X, mesh, num_chunks=2))
    np.testing.assert_allclose(yb, np.asarray(spmm_coo(coo, X)),
                               rtol=1e-5, atol=1e-4, err_msg=name)
import pytest
with pytest.raises(ValueError):
    spmm_merge_distributed(mrg, X, mesh, num_chunks=0)
print("chunked merge equivalence OK")
"""))


def test_mesh_plan_jits_stream_as_arguments():
    """Regression: the mesh plan's jitted multiply closed over the
    partitioned stream, so every compile embedded it as constants (2.36 GB
    at rmat scale 21 on 4 chips, and a compile that outran its time
    limit). Both directions of both schedules must take it as arguments
    and still answer like the COO oracle."""
    out = run_sub("""
import warnings
import numpy as np, jax.numpy as jnp
from repro.core import PlanSpec, to_coo
from repro.data import matrices
from repro.spmm import SparseOperator, spmm_coo, spmm_coo_t
coo = to_coo(*matrices.uniform(3000, 3000, 60000, 0))
X = jnp.asarray(np.random.default_rng(0).standard_normal(
    (3000, 8)).astype(np.float32))
for sched, nc in (("row", 1), ("merge", 2)):
    op = SparseOperator.from_coo(coo, PlanSpec(
        num_devices=4, mesh_shape=(4, 1), schedule=sched, num_chunks=nc,
        compact_x=False, algorithm="sellcs"), impl="ref")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        y, yt = op.matmul(X), op.rmatmul(X)
        op.plan.multiply.lower(X)
    assert not [w for w in caught if "constants" in str(w.message)], sched
    np.testing.assert_allclose(np.asarray(y), np.asarray(spmm_coo(coo, X)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(yt),
                               np.asarray(spmm_coo_t(coo, X)),
                               rtol=1e-4, atol=1e-4)
print("stream passed as arguments")
""", devices=4, env={"JAX_CAPTURED_CONSTANTS_WARN_BYTES": "100000"})
    assert "stream passed as arguments" in out


def test_spmm_distributed_dtype_follows_kernel():
    """Regression: the nnz == 0 early-returns used to hardcode float32;
    they must produce the dtype the nonzero kernel path would — the
    (data, X) promotion on the ref path, which is also what the spmm_coo
    oracle reports. An empty matrix (data stored float32) multiplied by a
    complex64 X must come out complex64, not float32."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.launch.mesh import make_mesh
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                        partition_sellcs_rows, spmm_coo,
                        spmm_merge_distributed, spmm_row_distributed)
mesh = make_mesh((8,), ("data",))
z = np.zeros(0, np.int32)
empty = to_coo(z, z, np.zeros(0, np.float32), (6, 4))
tiny = to_coo(np.array([0, 1, 2], np.int32), np.array([0, 1, 2], np.int32),
              np.ones(3, np.float32), (6, 4))
X16 = jnp.ones((4, 3), jnp.float16)
Xc = jnp.ones((4, 3), jnp.complex64)
se = coo_to_sellcs(empty, c=2, sigma=4)
st = coo_to_sellcs(tiny, c=2, sigma=4)
for part, fn in [(partition_sellcs_rows, spmm_row_distributed),
                 (partition_sellcs_nnz, spmm_merge_distributed)]:
    # the nonzero path and the oracle agree on the (data, X) promotion
    y16 = fn(part(st, 8), X16, mesh)
    assert y16.dtype == spmm_coo(tiny, X16).dtype, (fn.__name__, y16.dtype)
    # nnz == 0 must take the same promotion, not hardcoded float32: with a
    # complex64 X the nonzero path yields complex64, and so must this
    ye = fn(part(se, 8), Xc, mesh)
    assert ye.dtype == spmm_coo(empty, Xc).dtype == jnp.complex64, \\
        (fn.__name__, ye.dtype)
    assert np.abs(np.asarray(ye)).max() == 0
# chunked merge keeps the same dtype contract as the monolithic schedule
yc = spmm_merge_distributed(partition_sellcs_nnz(st, 8), X16, mesh,
                            num_chunks=2)
assert yc.dtype == spmm_merge_distributed(
    partition_sellcs_nnz(st, 8), X16, mesh).dtype
print("distributed dtype contract OK")
"""))


def test_sharded_coo_multi_rhs_and_batcher_distributed():
    """core.distributed accepts [n, k] X; RequestBatcher drives a
    distributed spmm_fn closure (partial last flush included)."""
    print(run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import to_coo
from repro.core.distributed import (partition_nnz, partition_rows,
                                    spmv_merge_distributed,
                                    spmv_row_distributed)
from repro.data import matrices
from repro.launch.mesh import make_mesh
from repro.spmm import (RequestBatcher, coo_to_sellcs,
                        partition_sellcs_rows, spmm_coo,
                        spmm_row_distributed)
mesh = make_mesh((8,), ("data",))
coo = to_coo(*matrices.mawi_like(260, 240, 2400, 0.3, 1))
for k in (1, 8, 64):
    X = jnp.asarray(np.random.default_rng(k).standard_normal(
        (coo.shape[1], k)).astype(np.float32))
    yo = np.asarray(spmm_coo(coo, X))
    y1 = np.asarray(spmv_row_distributed(partition_rows(coo, 8), X, mesh))
    y2 = np.asarray(spmv_merge_distributed(partition_nnz(coo, 8), X, mesh))
    np.testing.assert_allclose(y1, yo, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(y2, yo, rtol=1e-5, atol=1e-4)

# batcher over the mesh: 11 requests, max_batch 8 -> one full + one
# partial flush, every ticket answered from the right column
sc = coo_to_sellcs(coo, c=16, sigma=64)
sharded = partition_sellcs_rows(sc, 8)
calls = []
def spmm_fn(_mat, X):
    calls.append(X.shape[1])
    return spmm_row_distributed(sharded, X, mesh)
b = RequestBatcher(sc, max_batch=8, spmm_fn=spmm_fn)
rng = np.random.default_rng(11)
xs = [jnp.asarray(rng.standard_normal(coo.shape[1]).astype(np.float32))
      for _ in range(11)]
rids = [b.submit(x) for x in xs]
out = b.drain()
assert b.flushes == 2 and b.served == 11 and sorted(out) == sorted(rids)
assert calls == [8, 4], calls   # pow2-padded partial flush
for rid, x in zip(rids, xs):
    np.testing.assert_allclose(np.asarray(out[rid]),
                               np.asarray(spmm_coo(coo, x)),
                               rtol=1e-5, atol=1e-4)
print("sharded COO k + distributed batcher OK")
"""))


def test_spmm_distributed_degenerate_on_mesh():
    """Empty matrices and meshes wider than the matrix stay correct."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.core.distributed import (partition_nnz, partition_rows,
                                    spmv_merge_distributed,
                                    spmv_row_distributed)
from repro.launch.mesh import make_mesh
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                        partition_sellcs_rows, spmm_merge_distributed,
                        spmm_row_distributed)
mesh = make_mesh((8,), ("data",))
z = np.zeros(0, np.int32)
empty = to_coo(z, z, np.zeros(0, np.float32), (5, 4))
tiny = to_coo(np.array([0, 1, 2], np.int32), np.array([0, 1, 2], np.int32),
              np.ones(3, np.float32), (3, 3))
X4 = jnp.ones((4, 3), jnp.float32)
I3 = jnp.eye(3, dtype=jnp.float32)
# SELL-C-σ schedules
se = coo_to_sellcs(empty, c=2, sigma=4)
assert np.abs(np.asarray(spmm_row_distributed(
    partition_sellcs_rows(se, 8), X4, mesh))).max() == 0
assert np.abs(np.asarray(spmm_merge_distributed(
    partition_sellcs_nnz(se, 8), X4, mesh))).max() == 0
st = coo_to_sellcs(tiny, c=2, sigma=2)    # more devices than slices
np.testing.assert_allclose(np.asarray(spmm_row_distributed(
    partition_sellcs_rows(st, 8), I3, mesh)), np.eye(3), atol=1e-6)
np.testing.assert_allclose(np.asarray(spmm_merge_distributed(
    partition_sellcs_nnz(st, 8), I3, mesh)), np.eye(3), atol=1e-6)
# COO schedules: num_devices > m and nnz == 0
assert np.abs(np.asarray(spmv_row_distributed(
    partition_rows(empty, 8), X4, mesh))).max() == 0
assert np.abs(np.asarray(spmv_merge_distributed(
    partition_nnz(empty, 8), X4, mesh))).max() == 0
np.testing.assert_allclose(np.asarray(spmv_row_distributed(
    partition_rows(tiny, 8), I3, mesh)), np.eye(3), atol=1e-6)
print("degenerate mesh cases OK")
"""))


# --------------------------------------------------------------------------
# Partitioner guards — host-side, no devices needed
# --------------------------------------------------------------------------
def _empty_coo(m=5, n=4):
    from repro.core import to_coo
    z = np.zeros(0, np.int32)
    return to_coo(z, z, np.zeros(0, np.float32), (m, n))


def test_partition_guards_reject_bad_device_count():
    import pytest
    from repro.core.distributed import partition_nnz, partition_rows
    from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                            partition_sellcs_rows)
    coo = _empty_coo()
    sc = coo_to_sellcs(coo, c=2)
    for fn, arg in [(partition_rows, coo), (partition_nnz, coo),
                    (partition_sellcs_rows, sc), (partition_sellcs_nnz, sc)]:
        with pytest.raises(ValueError):
            fn(arg, 0)
        with pytest.raises(ValueError):
            fn(arg, -3)


def test_partition_rows_empty_matrix_keeps_sane_shard_shapes():
    """Regression: a zero-nnz matrix used to put every row in the last
    band, inflating rows_per_shard to m; now bands split evenly."""
    from repro.core.distributed import partition_nnz, partition_rows
    coo = _empty_coo(m=64, n=16)
    s = partition_rows(coo, 8)
    assert s.rows.shape == (8, 1)
    assert s.rows_per_shard == 8            # == m / P, not m
    assert np.asarray(s.row_offset).tolist() == list(range(0, 64, 8))
    s2 = partition_nnz(coo, 8)
    assert s2.rows.shape == (8, 1) and s2.rows_per_shard == 1


def test_partition_more_devices_than_rows():
    from repro.core import to_coo
    from repro.core.distributed import partition_nnz, partition_rows
    coo = to_coo(np.array([0, 1, 2], np.int32),
                 np.array([0, 1, 2], np.int32),
                 np.ones(3, np.float32), (3, 3))
    for part in (partition_rows, partition_nnz):
        s = part(coo, 8)
        assert s.rows.shape[0] == 8
        assert s.rows_per_shard >= 1
        # local row ids stay inside the shard buffer
        assert int(np.asarray(s.rows).max()) < s.rows_per_shard
        # every shard offset is a valid global row (or 0 for empty shards)
        offs = np.asarray(s.row_offset)
        assert offs.min() >= 0 and offs.max() < 3


def test_partition_sellcs_roundtrip_covers_all_nnz():
    """Both SELL-C-σ partitioners must conserve the nonzero payload."""
    from repro.core import to_coo
    from repro.data import matrices
    from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                            partition_sellcs_rows)
    coo = to_coo(*matrices.mawi_like(200, 180, 1500, 0.3, 2))
    sc = coo_to_sellcs(coo, c=8, sigma=32)
    total = float(np.abs(np.asarray(sc.data)).sum())
    for part in (partition_sellcs_rows, partition_sellcs_nnz):
        for P in (1, 3, 8, 64):
            sh = part(sc, P)
            got = float(np.abs(np.asarray(sh.data)).sum())
            assert abs(got - total) < 1e-3, (part.__name__, P)
            assert sh.data.shape[0] == P


def _explicit_zero_coo():
    """m=16, c=4: row 0 stores 3 entries, two of them EXPLICIT ZEROS at
    cols 2 and 3; rows 1..15 store one entry each. After the σ-sort, slice
    0 has width 3 and its width-rows j=1,2 carry ONLY row 0's explicit
    zeros — all-zero data with real column indices, exactly what
    SellCS.to_coo round-trips and a value-based padding mask destroys."""
    from repro.core import to_coo
    rows = np.array([0, 0, 0] + list(range(1, 16)), np.int32)
    cols = np.array([0, 2, 3] + [r % 4 for r in range(1, 16)], np.int32)
    vals = np.array([1.0, 0.0, 0.0] + [float(r) for r in range(1, 16)],
                    np.float32)
    return to_coo(rows, cols, vals, (16, 4))


def test_chunk_plan_preserves_explicit_zero_width_rows():
    """Regression for the ``np.any(data != 0)`` padding mask in
    ``_chunk_substreams``: the span plan must rebuild the stream from the
    partitioner's recorded real-row counts, so (a) the slice spans equal
    ``balanced_row_bands`` over the TRUE per-slice widths and (b) the
    explicit-zero width-rows survive into the spans with their column
    payload. The old mask dropped them, shifting both."""
    from repro.core import balanced_row_bands
    from repro.spmm import coo_to_sellcs, partition_sellcs_nnz
    sc = coo_to_sellcs(_explicit_zero_coo(), c=4, sigma=16)
    widths = np.diff(np.asarray(sc.slice_ptr, np.int64))
    assert widths.tolist() == [3, 1, 1, 1]       # slice 0 holds the zeros
    sharded = partition_sellcs_nnz(sc, 3, num_chunks=2)
    assert np.asarray(sharded.row_counts).sum() == sc.data.shape[0]
    spans = sharded.chunk_plan[1]
    # (a) spans tile [0, S) at the band bounds of the TRUE widths — the
    # old mask saw widths [1, 1, 1, 1] and cut the stream elsewhere
    bounds = balanced_row_bands(np.asarray(sc.slice_ptr, np.int64), 2)
    expect = [(int(a), int(b - a)) for a, b in zip(bounds, bounds[1:])
              if b > a]
    assert [(sp.slice_start, sp.num_slices) for sp in spans] == expect
    # (b) the two explicit-zero width-rows (all-zero values, nonzero cols)
    # crossed into the spans — the old mask left zero of them
    zero_rows = sum(
        int((np.all(np.asarray(sp.data) == 0, axis=-1)
             & np.any(np.asarray(sp.cols) != 0, axis=-1)).sum())
        for sp in spans)
    assert zero_rows == 2


def test_chunked_merge_equivalence_with_explicit_zeros():
    """ISSUE 4 satellite: chunked-vs-monolithic merge equivalence on a COO
    matrix containing explicit-zero entries, on a real 8-device mesh."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz, spmm_coo,
                        spmm_merge_distributed)
from repro.launch.mesh import make_mesh
rows = np.array([0, 0, 0] + list(range(1, 16)), np.int32)
cols = np.array([0, 2, 3] + [r % 4 for r in range(1, 16)], np.int32)
vals = np.array([1.0, 0.0, 0.0] + [float(r) for r in range(1, 16)],
                np.float32)
coo = to_coo(rows, cols, vals, (16, 4))
mesh = make_mesh((8,), ("data",))
sc = coo_to_sellcs(coo, c=4, sigma=16)
mrg = partition_sellcs_nnz(sc, 8)
X = jnp.asarray(np.random.default_rng(0).standard_normal(
    (4, 8)).astype(np.float32))
yo = np.asarray(spmm_coo(coo, X))
y1 = np.asarray(spmm_merge_distributed(mrg, X, mesh, num_chunks=1))
np.testing.assert_allclose(y1, yo, rtol=1e-5, atol=1e-5)
for c in (2, 3, 9):
    yc = np.asarray(spmm_merge_distributed(mrg, X, mesh, num_chunks=c))
    np.testing.assert_allclose(yc, y1, rtol=1e-6, atol=1e-6,
                               err_msg=f"chunks={c}")
print("explicit-zero chunked merge OK")
"""))


def test_partitioners_record_row_counts():
    """Both partitioners record per-device real width-row counts (the only
    trustworthy padding mask — see _chunk_substreams)."""
    from repro.core import to_coo
    from repro.data import matrices
    from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                            partition_sellcs_rows)
    coo = to_coo(*matrices.mawi_like(200, 180, 1500, 0.3, 2))
    sc = coo_to_sellcs(coo, c=8, sigma=32)
    W = sc.data.shape[0]
    for part in (partition_sellcs_rows, partition_sellcs_nnz):
        for P in (1, 3, 8):
            sh = part(sc, P)
            counts = np.asarray(sh.row_counts)
            assert counts.shape == (P,) and counts.sum() == W
            assert counts.min() >= 0
            assert counts.max() <= sh.data.shape[1]


def test_distributed_schedule_mismatch_raises():
    import pytest
    import jax
    from repro.launch.mesh import make_mesh
    from repro.spmm import (coo_to_sellcs, partition_sellcs_rows,
                            spmm_merge_distributed)
    if len(jax.devices()) != 1:
        return                       # in-process guard only needs 1 device
    mesh = make_mesh((1,), ("data",))
    sc = coo_to_sellcs(_empty_coo(), c=2)
    sharded = partition_sellcs_rows(sc, 1)
    with pytest.raises(ValueError, match="schedule"):
        spmm_merge_distributed(sharded, np.ones((4, 2), np.float32), mesh)


def test_rechunk_sellcs_equals_partition_time_plan():
    """rechunk_sellcs (the SparseOperator swap path's partition reuse) must
    bake exactly the chunk plan partition_sellcs_nnz would have baked at
    partition time, for every depth — and reject non-merge partitions."""
    import pytest
    from repro.core import to_coo
    from repro.data import matrices
    from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                            partition_sellcs_rows, rechunk_sellcs)
    coo = to_coo(*matrices.mawi_like(300, 280, 2500, 0.3, 1))
    sc = coo_to_sellcs(coo, c=8, sigma=32)
    for compact in (False, True):
        base = partition_sellcs_nnz(sc, 4, compact_x=compact)
        assert base.chunk_plan is None
        for nc in (2, 4):
            re = rechunk_sellcs(base, nc)
            fresh = partition_sellcs_nnz(sc, 4, num_chunks=nc,
                                         compact_x=compact)
            assert re.chunk_plan is not None
            assert re.chunk_plan[0] == fresh.chunk_plan[0] == nc
            # span count may clamp below nc when slices run out; the two
            # paths must clamp identically. col_map/n_touched are arrays
            # when compact, None otherwise
            assert len(re.chunk_plan[1]) == len(fresh.chunk_plan[1])
            for got, want in zip(re.chunk_plan[1], fresh.chunk_plan[1]):
                # _ChunkSpan fields mix ints and arrays — compare each
                for g, w in zip(got, want):
                    np.testing.assert_array_equal(np.asarray(g),
                                                  np.asarray(w))
            for got, want in zip(re.chunk_plan[2:], fresh.chunk_plan[2:]):
                assert (got is None) == (want is None)
                if got is not None:
                    np.testing.assert_array_equal(np.asarray(got),
                                                  np.asarray(want))
        # idempotence: same depth returns the same object, depth 1 strips
        re4 = rechunk_sellcs(base, 4)
        assert rechunk_sellcs(re4, 4) is re4
        assert rechunk_sellcs(re4, 1).chunk_plan is None
    with pytest.raises(ValueError, match="merge"):
        rechunk_sellcs(partition_sellcs_rows(sc, 4), 2)
    with pytest.raises(ValueError):
        rechunk_sellcs(partition_sellcs_nnz(sc, 4), 0)
