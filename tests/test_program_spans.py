"""The program's own spans and counters: registration (``convert/*``), the
single-chip flush (``spmm/*`` spans, ``sellcs/*`` counters), and the rule
that nothing is recorded while JAX is tracing."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.convert import to_coo
from repro.core.selector import PlanSpec
from repro.data.matrices import rmat
from repro.obs import MetricRegistry
from repro.spmm import SparseOperator, coo_to_sellcs
from repro.spmm.kernels import sellcs_spmm

REGISTRATION = ("convert/canonicalize", "convert/put", "convert/pull",
                "convert/stats", "convert/sellcs_order",
                "convert/sellcs_split", "convert/sellcs_fill")
FLUSH = ("spmm/pad_x", "spmm/kernel", "spmm/fixup")
COUNTERS = ("sellcs/width_rows", "sellcs/slots", "sellcs/lanes_live",
            "sellcs/split_slots", "sellcs/split_lanes")


@pytest.fixture(autouse=True)
def _no_registry():
    obs.uninstall()
    yield
    obs.uninstall()


@pytest.fixture(scope="module")
def small():
    r, c, v, shape = rmat(8, 4, seed=0)
    return coo_to_sellcs(to_coo(r, c, v, shape))


@pytest.fixture(scope="module")
def small_split():
    """The same matrix as the one-device plan streams it: its hubs split
    into virtual rows."""
    r, c, v, shape = rmat(8, 4, seed=0)
    sc = coo_to_sellcs(to_coo(r, c, v, shape), sigma=shape[0],
                       split_long_rows=True)
    assert sc.split_lanes > 0
    return sc


def _register(r, c, v, shape):
    """One registration as the serve path makes it; its wall seconds and
    the registry that recorded it."""
    reg = obs.install(MetricRegistry())
    t = time.perf_counter()
    op = SparseOperator.from_coo(to_coo(r, c, v, shape),
                                 PlanSpec(num_devices=1, algorithm="sellcs"))
    jax.block_until_ready(jax.tree_util.tree_leaves(op.plan.matrix))
    wall = time.perf_counter() - t
    obs.uninstall()
    return wall, reg


def test_registration_spans_cover_to_coo_and_from_coo():
    r, c, v, shape = rmat(12, 16, seed=0)
    _register(r, c, v, shape)                     # imports, first dispatch
    shares = []
    for _ in range(3):
        wall, reg = _register(r, c, v, shape)
        hists = {name: reg.histogram(name) for name in REGISTRATION}
        # put: the COO's upload and the stream's; pull: matrix_stats's and
        # coo_to_sellcs's reads of the COO; the graph's hubs are split
        assert {n: h.count for n, h in hists.items()} == {
            "convert/canonicalize": 1, "convert/put": 2, "convert/pull": 2,
            "convert/stats": 1, "convert/sellcs_order": 1,
            "convert/sellcs_split": 1, "convert/sellcs_fill": 1}
        shares.append(sum(h.total for h in hists.values()) / wall)
    # the spans are siblings, so their totals cannot pass the wall time;
    # the best of three leaves out a scheduler's pause between two spans
    assert all(s <= 1.0 for s in shares)
    assert max(shares) >= 0.9, shares


@pytest.mark.parametrize("split", [False, True], ids=["whole", "split"])
@pytest.mark.parametrize("k,k_tile,nk", [(1, None, 1), (5, 4, 2)],
                         ids=["k1", "two_k_tiles"])
def test_flush_counters_follow_the_stream(request, k, k_tile, nk, split):
    sc = request.getfixturevalue("small_split" if split else "small")
    x = jnp.asarray(np.random.default_rng(k).standard_normal(
        (sc.shape[1], k)).astype(np.float32))
    reg = obs.install(MetricRegistry())
    y = sellcs_spmm(sc, x, k_tile=k_tile, interpret=True)
    W, C = sc.data.shape
    counts = {n: reg.counter(n).value for n in COUNTERS}
    assert counts == {"sellcs/width_rows": W * nk,
                      "sellcs/slots": W * C * nk,
                      "sellcs/lanes_live": sc.nnz * nk,
                      "sellcs/split_slots": sc.split_slots * nk,
                      "sellcs/split_lanes": sc.split_lanes * nk}
    assert counts["sellcs/lanes_live"] / counts["sellcs/slots"] == \
        pytest.approx(sc.fill_ratio, rel=1e-12)
    # a split row owns several slots; its slices and its entries, counted
    # from the stream's arrays
    perm = np.asarray(sc.row_perm)
    m = sc.shape[0]
    owned = np.bincount(perm, minlength=m + 1)[:m]
    virtual = np.flatnonzero(np.isin(perm, np.flatnonzero(owned > 1)))
    widths = np.diff(np.asarray(sc.slice_ptr))
    assert counts["sellcs/split_slots"] == nk * C * int(
        widths[np.unique(virtual // C)].sum())
    assert counts["sellcs/split_lanes"] == nk * int(
        np.asarray(sc.row_len)[virtual].sum())
    assert (counts["sellcs/split_lanes"] > 0) == split
    assert {n: reg.histogram(n).count for n in FLUSH} == dict.fromkeys(
        FLUSH, 1)
    dense = np.asarray(sc.to_coo().todense())
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("call", ["lower", "jit"])
def test_nothing_is_recorded_while_tracing(small, call):
    """A jitted flush is traced once: its spans and counters would count
    a multiply that never ran (``jax.jit(...).lower``) or time the
    tracing of one that did."""
    sc = small
    x = jnp.ones((sc.shape[1], 3), jnp.float32)
    f = jax.jit(lambda X: sellcs_spmm(sc, X, interpret=True))
    reg = obs.install(MetricRegistry())
    if call == "lower":
        text = f.lower(x).as_text(debug_info=True)
        # the names still ride into the program
        assert all(name in text for name in FLUSH)
    else:
        jax.block_until_ready(f(x))
    assert not reg.histograms()
    assert all(reg.counter(n).value == 0 for n in COUNTERS)
    with obs.span("eager/after"):           # recording resumes eagerly
        pass
    assert reg.histogram("eager/after").count == 1
