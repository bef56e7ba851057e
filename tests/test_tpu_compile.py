"""Compiles of the main path for a described TPU v5e, at the shapes
``chip_smoke.py`` serves (``kron_like`` at scale 128: rmat scale 21).

Nothing runs: each test lowers and compiles for a ``v5e:2x2`` topology the
TPU compiler describes without a chip, and asserts that the Mosaic kernel
is in the compiled program (``tpu_custom_call``). This is what interpret
mode cannot show — block shapes, SMEM/VMEM limits and layouts the chip's
compiler refuses. The topology is described inside a module fixture (only
the worker that runs this file loads the TPU library), and the persistent
compile cache is off while it is held: such a compile cannot be read back
without a chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.spmm import kernels as K
from repro.spmm.distributed import (ShardedSellCS, spmm_merge_distributed,
                                    spmm_row_distributed)
from repro.spmm.sellcs import SellCS

# chip_smoke.py's matrix: n = m = 2**21 rows; slice height C = 128; the
# width-row count W of one coo_to_sellcs of kron_like at scale 128
N = 2 ** 21
C = 128
S = N // C
W = 2_219_301


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:                # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [1, 32])
def test_sellcs_slots_compiles_at_smoke_shape(one_chip, k):
    """The forward kernel alone: X and Y stay in HBM, so it compiles at
    n = 2**21 (the VMEM-resident design was refused here). X is padded
    to the lane-multiple k-tile, as every caller does."""
    kt = K.choose_k_tile(k)
    f = jax.jit(lambda d, c, so, x: K.sellcs_slots(
        d, c, so, K._pad_k(x, kt), num_slices=S, chunk=C, k_tile=kt))
    _assert_kernel(f.lower(_sds((W, C), jnp.float32, one_chip),
                           _sds((W, C), jnp.int32, one_chip),
                           _sds((W,), jnp.int32, one_chip),
                           _sds((N, k), jnp.float32, one_chip)).compile())


def test_sellcs_spmm_flush_compiles(one_chip):
    """The one-device flush multiply: pad, kernel and σ-unpermute."""
    s = lambda shape, dt: _sds(shape, dt, one_chip)
    sc = SellCS(data=s((W, C), jnp.float32), cols=s((W, C), jnp.int32),
                slice_ptr=s((S + 1,), jnp.int32), slice_of=s((W,), jnp.int32),
                row_perm=s((S * C,), jnp.int32),
                row_len=s((S * C,), jnp.int32), diag=None, shape=(N, N),
                chunk=C, sigma=16 * C, nnz=48_097_363)
    f = jax.jit(lambda m, x: K.sellcs_spmm(m, x))
    _assert_kernel(f.lower(sc, s((N, 32), jnp.float32)).compile())


# the one-chip cells with the most rows: poisson2d_2048 and mawi22
N_CELL = 2 ** 22


def test_sellcs_slots_resident_compiles_at_cell_shape(one_chip):
    """The resident-X kernel at k = 1: 16 MiB of packed X held in VMEM
    under the kernel's own scoped limit, Y lane-dense."""
    s = lambda shape, dt: _sds(shape, dt, one_chip)
    f = jax.jit(lambda d, c, so, x: K.sellcs_slots_resident(
        d, c, so, K.pack_x(x), num_slices=N_CELL // C, chunk=C))
    _assert_kernel(f.lower(s((W, C), jnp.float32), s((W, C), jnp.int32),
                           s((W,), jnp.int32),
                           s((N_CELL, 1), jnp.float32)).compile())


def test_sellcs_spmm_resident_flush_compiles(one_chip, monkeypatch):
    """The k = 1 flush multiply as a v5e (128 MiB of VMEM) takes it:
    packing, the resident kernel and the σ-unpermute."""
    monkeypatch.setattr(K, "vmem_capacity_bytes", lambda: 128 * 2 ** 20)
    s = lambda shape, dt: _sds(shape, dt, one_chip)
    sc = SellCS(data=s((W, C), jnp.float32), cols=s((W, C), jnp.int32),
                slice_ptr=s((S + 1,), jnp.int32), slice_of=s((W,), jnp.int32),
                row_perm=s((S * C,), jnp.int32),
                row_len=s((S * C,), jnp.int32), diag=None, shape=(N, N),
                chunk=C, sigma=N, nnz=48_097_363)
    calls = []
    real = K.sellcs_slots_resident
    monkeypatch.setattr(K, "sellcs_slots_resident",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    f = jax.jit(lambda m, x: K.sellcs_spmm(m, x))
    _assert_kernel(f.lower(sc, s((N, 1), jnp.float32)).compile())
    assert calls


def _mesh4(topo):
    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), ("data",))
    return mesh, lambda *spec: NamedSharding(mesh, P(*spec))


def test_row_distributed_body_compiles_on_4_chips(topo):
    """The row schedule's shard_map body over a 4-device mesh: one slice
    band per chip, X replicated, no collective in the body."""
    mesh, dev = _mesh4(topo)
    wp = -(-W // 4)

    def flush(data, cols, so, offs, row_perm, x):
        sh = ShardedSellCS(data, cols, so, offs, row_perm, (N, N), C, S,
                           S // 4, 48_097_363, "row")
        return spmm_row_distributed(sh, x, mesh, impl="pallas")

    compiled = jax.jit(flush).lower(
        _sds((4, wp, C), jnp.float32, dev("data")),
        _sds((4, wp, C), jnp.int32, dev("data")),
        _sds((4, wp), jnp.int32, dev("data")),
        _sds((4,), jnp.int32, dev("data")),
        _sds((S * C,), jnp.int32, dev()),
        _sds((N, 32), jnp.float32, dev())).compile()
    _assert_kernel(compiled)


def test_merge_distributed_body_compiles_on_4_chips(topo):
    """The chunked merge schedule's shard_map body over a 4-device mesh:
    a quarter of the stream per chip, the kernel per span, the psums."""
    mesh, dev = _mesh4(topo)
    wc = -(-W // 8)                       # 2 spans x 4 devices
    span = lambda: (_sds((4, wc, C), jnp.float32, dev("data")),
                    _sds((4, wc, C), jnp.int32, dev("data")),
                    _sds((4, wc), jnp.int32, dev("data")))
    half = S // 2

    def flush(spans, row_perm, x):
        from repro.spmm.distributed import _ChunkSpan
        plan = tuple(_ChunkSpan(i * half, half, *sp)
                     for i, sp in enumerate(spans))
        sh = ShardedSellCS(spans[0][0], spans[0][1], spans[0][2],
                           jnp.zeros((4,), jnp.int32), row_perm, (N, N), C,
                           S, S, 48_097_363, "merge",
                           chunk_plan=(2, plan, None, None))
        return spmm_merge_distributed(sh, x, mesh, impl="pallas",
                                      num_chunks=2)

    compiled = jax.jit(flush).lower(
        (span(), span()), _sds((S * C,), jnp.int32, dev()),
        _sds((N, 32), jnp.float32, dev())).compile()
    _assert_kernel(compiled)
    assert "all-reduce" in compiled.as_text()
