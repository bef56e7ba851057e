"""Hiding the compact-X gather under the chunked slice stream (ISSUE 10):
``gather="overlap"`` rebuilds the gathered slab per span inside the mesh
body so XLA can run span i+1's gather under span i's kernel/psum. It must
be BITWISE identical to the up-front gather — it moves the same bytes at
a different time, in the same fp summation order —
across schedules x chunks {1,2,4} x meshes (8,1)/(4,2) x op N/T x
uniform/mawi, under the jnp reference body and the Pallas kernel body in
interpret mode, plus the degenerates (nnz==0 shard, a shard touching all
n columns, n_touched < LANE).

Also locked down here: the exposed-gather roofline term's ordering
(overlap <= upfront, zero off the compact path), the selector's
gather axis (PlanSpec pin, validation), the baked per-span touched-column
split's invariants (LANE-padded col_map, the row-0 padding pair), and the
``_symmetric_combine`` mixed-dtype regression (a wider stored diagonal
must not promote the output dtype).

Device-backed tests run in SUBPROCESSES (the device-count flag must be
set before jax initializes; the rest of the suite keeps seeing 1 device).
Model/selector/plan invariants are pure host code and run in-process.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_gather_modes_bitwise_equal_and_oracle():
    """The overlapped gather answers BITWISE identically to the up-front
    gather (and both match the
    ``SellCS.to_coo`` oracle) across meshes (8,1)/(4,2), both schedules,
    num_chunks in {1, 2, 4}, op N/T, uniform + mawi."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.data import matrices
from repro.launch.mesh import make_spmm_mesh
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                        partition_sellcs_rows, spmm_coo,
                        spmm_merge_distributed, spmm_row_distributed)
for name, gen in [("uniform", matrices.uniform(500, 430, 4000, 0)),
                  ("mawi_like", matrices.mawi_like(400, 400, 3000, 0.4, 1))]:
    coo = to_coo(*gen)
    sc = coo_to_sellcs(coo, c=16, sigma=64)
    for pd, pm in [(8, 1), (4, 2)]:
        mesh = make_spmm_mesh((pd, pm))
        row = partition_sellcs_rows(sc, pd, compact_x=True)
        mrgs = {c: partition_sellcs_nnz(sc, pd, num_chunks=c,
                                        compact_x=True)
                for c in (1, 2, 4)}
        for k in (1, 8):
            X = jnp.asarray(np.random.default_rng(k).standard_normal(
                (coo.shape[1], k)).astype(np.float32))
            yo = np.asarray(spmm_coo(sc.to_coo(), X))
            y_up = np.asarray(spmm_row_distributed(row, X, mesh,
                                                   gather="upfront"))
            np.testing.assert_allclose(y_up, yo, rtol=1e-5, atol=1e-4,
                                       err_msg=f"{name} row {pd}x{pm}")
            np.testing.assert_array_equal(
                np.asarray(spmm_row_distributed(row, X, mesh,
                                                gather="overlap")),
                y_up, err_msg=f"{name} row {pd}x{pm} k={k}")
            for c, mrg in mrgs.items():
                y_up = np.asarray(spmm_merge_distributed(
                    mrg, X, mesh, num_chunks=c, gather="upfront"))
                np.testing.assert_allclose(
                    y_up, yo, rtol=1e-5, atol=1e-4,
                    err_msg=f"{name} merge/c{c} {pd}x{pm}")
                np.testing.assert_array_equal(
                    np.asarray(spmm_merge_distributed(
                        mrg, X, mesh, num_chunks=c, gather="overlap")),
                    y_up, err_msg=f"{name} merge/c{c} {pd}x{pm} k={k}")
            # op=T has no compact-X gather (X is read dense in slot
            # space) — gather= is accepted and ignored, bitwise
            XT = jnp.asarray(np.random.default_rng(k + 7).standard_normal(
                (coo.shape[0], k)).astype(np.float32))
            yt = np.asarray(spmm_merge_distributed(mrgs[2], XT, mesh,
                                                   num_chunks=2, op="T"))
            np.testing.assert_array_equal(
                np.asarray(spmm_merge_distributed(
                    mrgs[2], XT, mesh, num_chunks=2, op="T",
                    gather="overlap")),
                yt, err_msg=f"{name} op=T {pd}x{pm} k={k}")
    print(name, "gather modes OK")
"""))


def test_gather_modes_pallas_interpret():
    """The gather modes under the Pallas kernel body (interpret mode
    off-TPU): the overlapped result stays bitwise equal to up-front, the
    replicated-X stream (no compaction) answers the same, and all match
    the oracle."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.data import matrices
from repro.launch.mesh import make_spmm_mesh
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                        partition_sellcs_rows, spmm_coo,
                        spmm_merge_distributed, spmm_row_distributed)
coo = to_coo(*matrices.mawi_like(300, 280, 2400, 0.4, 3))
sc = coo_to_sellcs(coo, c=16, sigma=64)
for pd, pm in [(8, 1), (4, 2)]:
    mesh = make_spmm_mesh((pd, pm))
    row = partition_sellcs_rows(sc, pd, compact_x=True)
    plain = partition_sellcs_rows(sc, pd)
    mrg = partition_sellcs_nnz(sc, pd, num_chunks=4, compact_x=True)
    for k in (1, 8):
        X = jnp.asarray(np.random.default_rng(k).standard_normal(
            (coo.shape[1], k)).astype(np.float32))
        yo = np.asarray(spmm_coo(sc.to_coo(), X))
        y_up = np.asarray(spmm_row_distributed(
            row, X, mesh, impl="pallas_interpret", k_tile=4,
            gather="upfront"))
        np.testing.assert_allclose(y_up, yo, rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(
            np.asarray(spmm_row_distributed(
                row, X, mesh, impl="pallas_interpret", k_tile=4,
                gather="overlap")),
            y_up, err_msg=f"row overlap {pd}x{pm} k={k}")
        np.testing.assert_allclose(
            np.asarray(spmm_row_distributed(
                plain, X, mesh, impl="pallas_interpret", k_tile=4)),
            yo, rtol=1e-5, atol=1e-4, err_msg=f"row plain {pd}x{pm} k={k}")
        m_up = np.asarray(spmm_merge_distributed(
            mrg, X, mesh, impl="pallas_interpret", k_tile=4,
            num_chunks=4, gather="upfront"))
        np.testing.assert_allclose(m_up, yo, rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(
            np.asarray(spmm_merge_distributed(
                mrg, X, mesh, impl="pallas_interpret", k_tile=4,
                num_chunks=4, gather="overlap")),
            m_up, err_msg=f"merge overlap {pd}x{pm} k={k}")
    print(pd, pm, "gather interpret OK")
"""))


def test_gather_degenerate_cases_on_mesh():
    """Degenerates under every gather mode: an nnz==0 matrix (empty
    shards), a shard touching ALL n columns (col_map == identity), and
    n_touched < LANE (the slab pad dominates the map)."""
    print(run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import to_coo
from repro.data import matrices
from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                        partition_sellcs_rows, spmm_coo,
                        spmm_merge_distributed, spmm_row_distributed)
from repro.launch.mesh import make_spmm_mesh
mesh = make_spmm_mesh((8, 1))
z = np.zeros(0, np.int32)

# 1. nnz == 0: every shard is empty, every gather mode answers zero
empty = to_coo(z, z, np.zeros(0, np.float32), (6, 4))
se = coo_to_sellcs(empty, c=2, sigma=4)
X4 = jnp.ones((4, 3), jnp.float32)
for g in ("upfront", "overlap"):
    assert np.abs(np.asarray(spmm_row_distributed(
        partition_sellcs_rows(se, 8, compact_x=True), X4, mesh,
        gather=g))).max() == 0, g
    assert np.abs(np.asarray(spmm_merge_distributed(
        partition_sellcs_nnz(se, 8, num_chunks=2, compact_x=True), X4,
        mesh, num_chunks=2, gather=g))).max() == 0, g

# 2. a shard touching ALL n columns: identity map, answer must not move
coo = to_coo(*matrices.mawi_like(64, 8, 512, 0.5, 5))
sc = coo_to_sellcs(coo, c=8, sigma=16)
mrg = partition_sellcs_nnz(sc, 8, num_chunks=4, compact_x=True)
assert int(np.asarray(mrg.chunk_plan[3]).max()) == 8
X = jnp.asarray(np.random.default_rng(0).standard_normal(
    (8, 8)).astype(np.float32))
yo = np.asarray(spmm_coo(sc.to_coo(), X))
y_up = np.asarray(spmm_merge_distributed(mrg, X, mesh, num_chunks=4,
                                         gather="upfront"))
np.testing.assert_allclose(y_up, yo, rtol=1e-5, atol=1e-4)
np.testing.assert_array_equal(
    np.asarray(spmm_merge_distributed(mrg, X, mesh, num_chunks=4,
                                      gather="overlap")), y_up)

# 3. n_touched < LANE everywhere (4 distinct columns): the slab is pure
# pad beyond row 4 and every mode must read only the real rows
coo = to_coo(*matrices.uniform(100, 4, 300, 11))
sc = coo_to_sellcs(coo, c=16, sigma=32)
row = partition_sellcs_rows(sc, 8, compact_x=True)
assert int(np.asarray(row.n_touched).max()) <= 4
mrg = partition_sellcs_nnz(sc, 8, num_chunks=4, compact_x=True)
X = jnp.asarray(np.random.default_rng(1).standard_normal(
    (4, 8)).astype(np.float32))
yo = np.asarray(spmm_coo(sc.to_coo(), X))
for g in ("upfront", "overlap"):
    np.testing.assert_allclose(
        np.asarray(spmm_row_distributed(row, X, mesh, gather=g)),
        yo, rtol=1e-5, atol=1e-4, err_msg=g)
    np.testing.assert_allclose(
        np.asarray(spmm_merge_distributed(mrg, X, mesh, num_chunks=4,
                                          gather=g)),
        yo, rtol=1e-5, atol=1e-4, err_msg=g)
    np.testing.assert_allclose(
        np.asarray(spmm_row_distributed(row, X, mesh,
                                        impl="pallas_interpret",
                                        k_tile=4, gather=g)),
        yo, rtol=1e-5, atol=1e-4, err_msg=g)
print("gather degenerates OK")
"""))


# --------------------------------------------------------------------------
# Host-side: knob validation, baked span maps, model term, selector axis
# --------------------------------------------------------------------------
def _mawi_sellcs(c=8, sigma=32):
    from repro.core import to_coo
    from repro.data import matrices
    from repro.spmm import coo_to_sellcs
    coo = to_coo(*matrices.mawi_like(200, 180, 1500, 0.3, 2))
    return coo_to_sellcs(coo, c=c, sigma=sigma)


def test_gather_knob_validation():
    """overlap needs a compact partition (a replicated-X stream has
    no X gather to hide); an unknown mode is a ValueError naming the
    choices."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.spmm import (partition_sellcs_nnz, partition_sellcs_rows,
                            spmm_merge_distributed, spmm_row_distributed)
    if len(jax.devices()) != 1:
        return                       # in-process guard only needs 1 device
    sc = _mawi_sellcs()
    mesh = make_mesh((1,), ("data",))
    X = np.ones((180, 2), np.float32)
    plain = partition_sellcs_rows(sc, 1)
    comp = partition_sellcs_rows(sc, 1, compact_x=True)
    with pytest.raises(ValueError, match="compact"):
        spmm_row_distributed(plain, X, mesh, gather="overlap")
    with pytest.raises(ValueError, match="compact"):
        spmm_merge_distributed(partition_sellcs_nnz(sc, 1), X, mesh,
                               gather="overlap")
    for g in ("bogus", "fused"):
        with pytest.raises(ValueError, match="gather"):
            spmm_row_distributed(comp, X, mesh, gather=g)
    # on one device every mode is the same single gather — bitwise
    y_up = np.asarray(spmm_row_distributed(comp, X, mesh))
    np.testing.assert_array_equal(
        np.asarray(spmm_row_distributed(comp, X, mesh, gather="overlap")),
        y_up)


def test_span_maps_lane_padded_and_row0_invariant():
    """The baked per-span touched split: every span of a compact chunked
    plan carries (sub, col_map, n_touched); the plan-level col_map is
    LANE-padded (the hot path is a single ``x_pad[col_map]``, no
    per-multiply concatenate) with all-zero padding beyond the touched
    prefix; span padding entries carry the consistent pair
    (sub == 0, col_map == plan col_map[:, 0]) so duplicate scatter writes
    agree."""
    from repro.spmm import partition_sellcs_nnz
    from repro.spmm.kernels import LANE
    sc = _mawi_sellcs()
    sh = partition_sellcs_nnz(sc, 8, num_chunks=3, compact_x=True)
    nc, spans, plan_cm, plan_nt = sh.chunk_plan
    assert nc == 3 and plan_cm is not None and plan_nt is not None
    cm = np.asarray(plan_cm)
    nt = np.asarray(plan_nt)
    assert cm.shape[1] % LANE == 0          # baked pad, not a hot-path one
    for p in range(cm.shape[0]):
        assert not cm[p, int(nt[p]):].any()  # padding is all row 0
    assert len(spans) == 3
    for sp in spans:
        assert sp.sub is not None and sp.col_map is not None \
            and sp.n_touched is not None
        sub = np.asarray(sp.sub)
        scm = np.asarray(sp.col_map)
        snt = np.asarray(sp.n_touched)
        for p in range(cm.shape[0]):
            t = int(snt[p])
            # real entries: plan-space positions resolving to the same
            # global columns the span recorded
            np.testing.assert_array_equal(cm[p][sub[p, :t]], scm[p, :t])
            # padding entries: the consistent (0, plan col_map[p, 0]) pair
            assert not sub[p, t:].any()
            assert (scm[p, t:] == cm[p, 0]).all()


def test_exposed_gather_roofline_term():
    """overlap <= upfront always; overlap strictly wins only
    where there are spans to hide behind (merge, num_chunks > 1); the
    term is zero off the compact path and for op=T."""
    from repro.roofline import spmm_distributed_gather_s
    kw = dict(nnz=40_000, max_row_nnz=64, model_devices=1,
              compact_x=True, n_touched=900.0)
    up = spmm_distributed_gather_s(5000, 4000, 32, 8, "merge",
                                   num_chunks=4, gather="upfront", **kw)
    ov = spmm_distributed_gather_s(5000, 4000, 32, 8, "merge",
                                   num_chunks=4, gather="overlap", **kw)
    assert 0.0 < ov < up
    with pytest.raises(ValueError, match="gather"):
        spmm_distributed_gather_s(5000, 4000, 32, 8, "merge",
                                  num_chunks=4, gather="fused", **kw)
    # no spans to hide behind: overlap degenerates to up-front
    for sched, nc in (("row", 1), ("merge", 1)):
        u = spmm_distributed_gather_s(5000, 4000, 32, 8, sched,
                                      num_chunks=nc, gather="upfront",
                                      **kw)
        o = spmm_distributed_gather_s(5000, 4000, 32, 8, sched,
                                      num_chunks=nc, gather="overlap",
                                      **kw)
        assert u == o > 0.0
    # nothing to gather: replicated X, or the transpose's dense read
    assert spmm_distributed_gather_s(5000, 4000, 32, 8, "merge",
                                     num_chunks=4, nnz=40_000) == 0.0
    assert spmm_distributed_gather_s(5000, 4000, 32, 8, "merge",
                                     num_chunks=4, gather="overlap",
                                     op="T", **kw) == 0.0
    with pytest.raises(ValueError, match="gather"):
        spmm_distributed_gather_s(5000, 4000, 32, 8, "merge",
                                  gather="bogus", **kw)


def test_selector_gather_axis_and_spec_pin():
    """select_distributed scores the gather axis on compact sellcs
    candidates, respects a PlanSpec.gather pin, and rejects a pin without
    compact_x (a replicated-X plan has no gather to schedule)."""
    from repro.core import (GATHER_CANDIDATES, MatrixStats, PlanSpec,
                            select_distributed)
    assert GATHER_CANDIDATES == ("upfront", "overlap")
    stats = MatrixStats(m=20000, n=20000, nnz=300000, max_row_nnz=64,
                        row_var=0.4, symmetric=False)
    ch = select_distributed(stats, k=64, num_devices=8)
    assert ch.gather in GATHER_CANDIDATES
    if not ch.compact_x:
        assert ch.gather == "upfront"
    pinned = select_distributed(
        stats, k=64, num_devices=8,
        spec=PlanSpec(num_devices=8, algorithm="sellcs", compact_x=True,
                      gather="overlap"))
    assert pinned.compact_x and pinned.gather == "overlap"
    with pytest.raises(ValueError, match="gather"):
        PlanSpec(num_devices=8, gather="bogus").canonical()
    with pytest.raises(ValueError, match="compact"):
        PlanSpec(num_devices=8, compact_x=False,
                 gather="overlap").canonical()
    with pytest.raises(ValueError, match="gather"):
        PlanSpec(num_devices=8, compact_x=True, gather="fused").canonical()


def test_symmetric_combine_mixed_dtype_regression():
    """A wider stored diagonal must not promote the symmetric combine's
    output dtype: with a bf16 stream and a f32 diag, the one-triangle
    answer keeps the kernel-path dtype and matches the general-storage
    answer."""
    import jax
    import jax.numpy as jnp
    from repro.core import to_coo
    from repro.launch.mesh import make_mesh
    from repro.spmm import (coo_to_sellcs, partition_sellcs_rows,
                            spmm_row_distributed)
    if len(jax.devices()) != 1:
        return                       # in-process guard only needs 1 device
    rng = np.random.default_rng(3)
    b = np.zeros((12, 12), np.float32)
    idx = rng.integers(0, 12, size=(40, 2))
    b[idx[:, 0], idx[:, 1]] = rng.standard_normal(40).astype(np.float32)
    a = b + b.T + np.diag(np.arange(1.0, 13.0, dtype=np.float32))
    r, c = np.nonzero(a)
    coo = to_coo(r.astype(np.int32), c.astype(np.int32),
                 a[r, c].astype(np.float32), (12, 12))
    mesh = make_mesh((1,), ("data",))
    X = jnp.asarray(rng.standard_normal((12, 4)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    sym = partition_sellcs_rows(
        coo_to_sellcs(coo, c=4, sigma=8, structure="symmetric"), 1)
    sym = sym._replace(data=sym.data.astype(jnp.bfloat16),
                       diag=sym.diag.astype(jnp.float32))
    gen = partition_sellcs_rows(coo_to_sellcs(coo, c=4, sigma=8), 1)
    gen = gen._replace(data=gen.data.astype(jnp.bfloat16))
    y_gen = spmm_row_distributed(gen, X, mesh, impl="ref")
    y_sym = spmm_row_distributed(sym, X, mesh, impl="ref")
    assert y_sym.dtype == y_gen.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y_sym, dtype=np.float32),
        np.asarray(y_gen, dtype=np.float32), rtol=0.1, atol=0.3)
