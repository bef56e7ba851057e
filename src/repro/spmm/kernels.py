"""The Pallas SpMM kernels: one SELL-C-σ family, each kernel with a
column-block (k-tile) grid dimension. SpMV is its k = 1 case.

The SELL-C-σ forward kernel (``sellcs_slots``) serves the TPU path, on
one device and in every distributed schedule. It keeps X and the slot
output in HBM: each grid step DMAs the X rows its width-rows name into
VMEM and adds finished slices into Y, so its VMEM footprint is fixed by
the k-tile and matrices far larger than VMEM run. The k-tile is the
*leading, parallel* grid dimension: k-tiles touch disjoint X/Y columns,
while the matrix stream stays "arbitrary" (sequential accumulate). Its
k = 1 variant on one device, ``sellcs_slots_resident``, walks the same
stream with X copied into VMEM once per pass, where X fits
(``x_resident``): a gather is then a vector load, not a DMA.

The transpose kernel (``sellcs_slots_t``) keeps whole ``[n, KT]`` slabs
VMEM-resident; beyond a VMEM-sized n Mosaic refuses it, so there it runs
in interpret mode only and raises for ``interpret=False``.

``choose_k_tile`` picks the widest lane-multiple KT whose VMEM working set
fits the budget: every extra k-tile re-streams the matrix and re-issues its
gathers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.convert import VMEM_BUDGET_BYTES
from repro.obs import live_registry, span
from .sellcs import SellCS

LANE = 128
W_TILE = 8          # width-rows per grid step of the transpose kernel
GATHER_W_TILE = 32  # width-rows per grid step of the forward gather kernel
SO_BLOCK = 1024     # slice ids per SMEM block (XLA's int32 vector tile)
_LANE_BITS = LANE.bit_length() - 1


def _lanes(kt: int) -> int:
    """VMEM lanes a row of ``kt`` 32-bit values occupies (lane padding)."""
    return -(-kt // LANE) * LANE


def sellcs_vmem_bytes(k_tile: int, chunk: int = 128) -> int:
    """VMEM working set of ``sellcs_slots`` at a lane-multiple ``k_tile``:
    the two gather slots, the slice accumulator and its read-back buffer,
    and the double-buffered data block (its rows lane-padded)."""
    kl = k_tile * 4
    return (2 * GATHER_W_TILE * chunk * kl + 2 * chunk * kl
            + 2 * GATHER_W_TILE * _lanes(chunk) * 4)


def choose_k_tile(k: int, *, chunk: int = 128,
                  vmem_budget: int = VMEM_BUDGET_BYTES) -> int:
    """The k-tile of ``sellcs_slots`` for a k-column multiply: the widest
    lane multiple, up to k rounded up to a lane, whose working set fits
    ``vmem_budget``; never below one lane (HBM rows are DMA'd lane-aligned,
    so a narrower tile costs the same). Independent of the matrix shape —
    X and Y stay in HBM. Callers pad X to a multiple of it."""
    kt = _lanes(max(int(k), 1))
    while kt > LANE and sellcs_vmem_bytes(kt, chunk) > vmem_budget:
        kt -= LANE
    return kt


def resolve_impl(impl: str, mat, op: str = "N") -> str:
    """The implementation that runs for ``impl`` on ``mat``: ``"auto"``
    is the Mosaic kernel on a TPU backend where one lowers — the SELL-C-σ
    forward pass of a general matrix — and the XLA reference everywhere
    else. Explicit impls pass through (a format with no kernel, or a
    kernel without a Mosaic lowering, then raises instead of running
    something else)."""
    if impl != "auto":
        return impl
    if (jax.default_backend() == "tpu" and isinstance(mat, SellCS)
            and mat.structure == "general" and op == "N"):
        return "pallas"
    return "ref"


def _pad_k(x: jax.Array, kt: int) -> jax.Array:
    k = x.shape[1]
    kp = -(-k // kt) * kt
    if kp != k:
        x = jnp.pad(x, ((0, 0), (0, kp - k)))
    return x


# --------------------------------------------------------------------------
# SELL-C-σ SpMM, k-tiled grid, X gathered from HBM by DMA
# --------------------------------------------------------------------------
def _stream(data: jax.Array, cols: jax.Array, slice_of: jax.Array,
            interpret: bool):
    """The width-row stream as both SELL-C-σ forward kernels read it.

    XLA tiles an int32 vector in HBM by 1024, and a 1-D SMEM block must
    match: one block of slice ids serves ``so_block // GATHER_W_TILE``
    steps (interpret mode has no tiling; a lane keeps its CPU runs short).
    The last block may run past W — the kernels mask rows >= W — so only
    a stream shorter than one block is padded, with width-rows of
    ``data == 0`` aimed harmlessly at slice 0. Returns ``(data, cols,
    slice_of, so_block, steps)``, ``steps`` the grid steps of one pass.
    """
    W, C = data.shape
    so_block = LANE if interpret else SO_BLOCK
    if W < so_block:
        pad = so_block - W
        data = jnp.concatenate([data, jnp.zeros((pad, C), data.dtype)])
        cols = jnp.concatenate([cols, jnp.zeros((pad, C), cols.dtype)])
        slice_of = jnp.concatenate(
            [slice_of, jnp.zeros((pad,), slice_of.dtype)])
    steps = -(-max(W, so_block) // GATHER_W_TILE)
    return (data, cols.astype(jnp.int32), slice_of.astype(jnp.int32),
            so_block, steps)


def _add_slices(so_ref, cur, acc, tmp, ysem, prods, dst, *, g, last,
                w_tile: int, so_block: int, rows: int):
    """Add one grid step's width-row products into Y, slice by slice.

    ``prods(w)`` is width-row ``w``'s products, zero in padding lanes.
    They accumulate per slice in ``acc``; when the slice id changes the
    finished slice is added into its block of Y in HBM, ``dst(s)``
    (read-modify-write through ``tmp``), so only one slice of Y is ever
    resident and a stream that revisits a slice (padding width-rows aimed
    at slice 0) stays exact. The last step flushes the slice still open;
    ``cur[0]`` is the open slice, -1 before the first.
    """
    def flush(s):
        block = dst(s)
        cp = pltpu.make_async_copy(block, tmp, ysem)
        cp.start()
        cp.wait()
        tmp[...] += acc[...]
        cp = pltpu.make_async_copy(tmp, block, ysem)
        cp.start()
        cp.wait()

    so_base = (g % (so_block // w_tile)) * w_tile

    def accumulate(w, c):
        s = so_ref[so_base + w]

        @pl.when(jnp.logical_and(s != cur[0], g * w_tile + w < rows))
        def _switch():
            @pl.when(cur[0] >= 0)
            def _():
                flush(cur[0])
            acc[...] = jnp.zeros_like(acc)
            cur[0] = s

        acc[...] += prods(w)
        return c

    jax.lax.fori_loop(0, w_tile, accumulate, 0)

    @pl.when(jnp.logical_and(g == last, cur[0] >= 0))
    def _last():
        flush(cur[0])


def _sellcs_kernel(so_ref, nl_ref, nln_ref,            # SMEM per-row ids
                   cols0_ref, colsn_ref,               # SMEM (WT, C)
                   data_ref,                           # VMEM (WT, C)
                   x_hbm, y_init, y_hbm,               # HBM (ANY)
                   xbuf, acc, tmp, cur, cnt, gsem, ysem,
                   *, w_tile: int, chunk: int, k_tile: int, so_block: int,
                   rows: int):
    """One grid step = ``w_tile`` width-rows of one k-tile.

    The X rows a step names are DMA'd from HBM into ``xbuf[slot]``, one
    row per lane up to the width-row's last stored lane (``nl``); lanes
    with ``data == 0`` contribute exact zeros whatever their buffer holds.
    Step ``g`` issues step ``g+1``'s gathers (``colsn`` is the next block,
    in SMEM) before it waits for its own, so the DMA latency hides under
    the next issue loop. ``_add_slices`` then adds the products into Y,
    one ``(C, KT)`` block per finished slice.
    """
    j = pl.program_id(0)
    g = pl.program_id(1)
    last = pl.num_programs(1) - 1
    slot = g % 2
    c0 = j * k_tile

    per = so_block // w_tile                  # steps per SMEM id block

    def issue(cols_ref, lens_ref, sl, step):
        base = (step % per) * w_tile

        def row(w, n):
            width = jnp.where(step * w_tile + w < rows, lens_ref[base + w], 0)

            def lane(l, c):
                pltpu.make_async_copy(
                    x_hbm.at[pl.ds(cols_ref[w, l], 1), pl.ds(c0, k_tile)],
                    xbuf.at[sl, w, pl.ds(l, 1)], gsem.at[sl]).start()
                return c
            jax.lax.fori_loop(0, width, lane, 0)
            return n + width
        cnt[sl] = jax.lax.fori_loop(0, w_tile, row, jnp.int32(0))

    @pl.when(g == 0)
    def _first():
        cur[0] = -1
        issue(cols0_ref, nl_ref, 0, 0)

    @pl.when(g < last)
    def _prefetch():
        issue(colsn_ref, nln_ref, 1 - slot, g + 1)

    def wait(_, c):
        pltpu.make_async_copy(x_hbm.at[pl.ds(0, 1), pl.ds(c0, k_tile)],
                              xbuf.at[slot, 0, pl.ds(0, 1)],
                              gsem.at[slot]).wait()
        return c

    jax.lax.fori_loop(0, cnt[slot], wait, 0)

    d_t = data_ref[...].astype(jnp.float32).T                # (C, WT)
    for w in range(w_tile):
        d = d_t[:, w:w + 1]                                   # (C, 1)
        live = jnp.logical_and(d != 0, g * w_tile + w < rows)
        xbuf[slot, w] = jnp.where(live, d * xbuf[slot, w], 0.0)

    _add_slices(so_ref, cur, acc, tmp, ysem, lambda w: xbuf[slot, w],
                lambda s: y_hbm.at[pl.ds(s * chunk, chunk),
                                   pl.ds(c0, k_tile)],
                g=g, last=last, w_tile=w_tile, so_block=so_block, rows=rows)


@functools.partial(jax.jit, static_argnames=("num_slices", "chunk",
                                             "k_tile", "interpret"))
def sellcs_slots(data: jax.Array, cols: jax.Array, slice_of: jax.Array,
                 x_pad: jax.Array, *, num_slices: int, chunk: int,
                 k_tile: int, interpret: bool = False) -> jax.Array:
    """Raw-array slot-space SpMM over a SELL-C-σ width-row stream.

    Accumulates into row slots ``[num_slices * chunk, Kp]`` (float32)
    without applying any row permutation. This is the shard-local compute
    of the distributed schedules (``repro.spmm.distributed``): a shard's
    slice stream is just a shorter width-row stream with its own
    ``slice_of``/``num_slices``, so the same kernel serves one device or a
    mesh body.

    X and Y stay in HBM: the kernel gathers the X rows each width-row names
    by DMA and adds each finished slice into Y, so its VMEM use depends on
    ``k_tile`` alone, never on n or m. ``Kp`` (the width of ``x_pad``) is a
    multiple of ``k_tile``, which on Mosaic is a lane multiple
    (``choose_k_tile``): HBM rows are DMA'd whole lanes at a time.
    """
    Kp = x_pad.shape[1]
    if Kp % k_tile:
        raise ValueError(f"x_pad width {Kp} is not a multiple of k_tile "
                         f"{k_tile}; pad it (choose_k_tile, _pad_k)")
    if not interpret and k_tile % LANE:
        raise ValueError(f"k_tile {k_tile} is not a multiple of {LANE}: "
                         "Mosaic DMAs HBM rows whole lanes at a time")
    C = chunk
    WT = GATHER_W_TILE
    W = data.shape[0]
    data, cols, slice_of, so_block, G = _stream(data, cols, slice_of,
                                                interpret)
    # each width-row's last stored lane plus one: σ-sorted rows put the
    # padding at the end of each width-row, so the walk stops there
    lane = jnp.arange(1, C + 1, dtype=jnp.int32)
    nlive = jnp.max(jnp.where(data != 0, lane, 0), axis=1)
    x_pad = x_pad.astype(jnp.float32)
    nk = Kp // k_tile
    per = so_block // WT
    n_blocks = -(-max(W, so_block) // so_block)
    smem = pltpu.MemorySpace.SMEM
    nxt = lambda j, g: (jnp.minimum(g + 1, G - 1), 0)
    ids = lambda j, g: (g // per,)
    ids_nxt = lambda j, g: (jnp.minimum((g + 1) // per, n_blocks - 1),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nk, G),
        in_specs=[
            pl.BlockSpec((so_block,), ids, memory_space=smem),
            pl.BlockSpec((so_block,), ids, memory_space=smem),
            pl.BlockSpec((so_block,), ids_nxt, memory_space=smem),
            pl.BlockSpec((WT, C), lambda j, g: (0, 0), memory_space=smem),
            pl.BlockSpec((WT, C), nxt, memory_space=smem),
            pl.BlockSpec((WT, C), lambda j, g: (g, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, WT, C, k_tile), jnp.float32),     # gathered X
            pltpu.VMEM((C, k_tile), jnp.float32),            # slice acc
            pltpu.VMEM((C, k_tile), jnp.float32),            # Y read-back
            pltpu.SMEM((1,), jnp.int32),                     # open slice
            pltpu.SMEM((2,), jnp.int32),                     # DMAs in flight
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    y0 = jnp.zeros((num_slices * C, Kp), jnp.float32)
    return pl.pallas_call(
        functools.partial(_sellcs_kernel, w_tile=WT, chunk=C,
                          k_tile=k_tile, so_block=so_block, rows=W),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_slices * C, Kp), jnp.float32),
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(slice_of, nlive, nlive, cols[:WT], cols, data, x_pad, y0)


# --------------------------------------------------------------------------
# SELL-C-σ SpMV (k = 1) with X resident in VMEM
# --------------------------------------------------------------------------
RESIDENT_SLACK = 8 * 2 ** 20   # scoped VMEM left to Mosaic's own scratch


def _x_rows(n: int) -> int:
    """Rows of an n-vector packed lane-dense, in whole (8, 128) tiles."""
    return -(-max(n, 1) // (8 * LANE)) * 8


def sellcs_resident_vmem_bytes(n: int, chunk: int = 128) -> int:
    """Scoped VMEM ``sellcs_slots_resident`` asks for with an n-vector X:
    packed X, one width-row's gathered lane groups, the step's products
    and the slice accumulator with its read-back (a ``(1, C)`` row takes a
    whole tile), the double-buffered data and column blocks, and
    ``RESIDENT_SLACK``."""
    row = 8 * _lanes(chunk) * 4
    return (_x_rows(n) * LANE * 4 + chunk * LANE * 4
            + (GATHER_W_TILE + 2) * row
            + 2 * 2 * GATHER_W_TILE * _lanes(chunk) * 4 + RESIDENT_SLACK)


def x_resident(n: int, k: int, vmem_capacity: int, *,
               chunk: int = 128) -> bool:
    """Whether a k-column multiply by an n-column matrix of slice height
    ``chunk`` keeps X resident in VMEM (``sellcs_slots_resident``): at
    k = 1, with lane-dense slices, when packed X and the kernel's working
    set fit ``vmem_capacity``. Every other multiply, and every one off a
    TPU (capacity 0), gathers X from HBM by DMA."""
    return (k == 1 and chunk % LANE == 0
            and sellcs_resident_vmem_bytes(n, chunk) <= vmem_capacity)


def vmem_capacity_bytes() -> int:
    """VMEM of one TensorCore of the default device; 0 where it is no
    TPU this JAX knows."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return 0


def pack_x(x: jax.Array) -> jax.Array:
    """An ``(n, 1)`` X packed lane-dense for ``sellcs_slots_resident``:
    ``X[c]`` at row ``c // 128``, lane ``c % 128``, zero-padded to whole
    tiles."""
    n = x.shape[0]
    flat = jnp.pad(x[:, 0].astype(jnp.float32), (0, _x_rows(n) * LANE - n))
    return flat.reshape(-1, LANE)


def _sellcs_resident_kernel(so_ref, cols_ref,            # SMEM
                            colv_ref, data_ref,            # VMEM (WT, C)
                            x_hbm, y_init, y_hbm,          # HBM (ANY)
                            xv, gbuf, prod, acc, tmp, cur, xsem, ysem,
                            *, w_tile: int, chunk: int, so_block: int,
                            rows: int):
    """One grid step = ``w_tile`` width-rows, X resident in VMEM.

    Step 0 copies packed X from HBM into ``xv`` with one DMA; it stays for
    the pass. For each width-row, the 128-lane group of X that holds lane
    ``l``'s column is loaded at its dynamic row into row ``l`` of ``gbuf``,
    all C lanes unrolled (padding lanes name column 0; they and the lanes
    with ``data == 0`` contribute exact zeros). The transposed groups then
    give each lane its value by a one-hot compare against ``cols % 128``
    and a sum over sublanes, exact in float32 since every other term is
    zero. ``_add_slices`` adds the products into Y, lane-dense: slice
    ``s`` is row ``s``.
    """
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _load_x():
        cur[0] = -1
        cp = pltpu.make_async_copy(x_hbm, xv, xsem)
        cp.start()
        cp.wait()

    sub = jax.lax.broadcasted_iota(jnp.int32, (LANE, chunk), 0)

    def width_row(w, c):
        valid = g * w_tile + w < rows

        @pl.when(valid)
        def _gather():
            for l in range(chunk):
                group = cols_ref[w, l] >> _LANE_BITS
                gbuf[l:l + 1, :] = xv[pl.ds(group, 1), :]

        pick = sub == (colv_ref[pl.ds(w, 1), :] & (LANE - 1))   # (128, C)
        xs = jnp.sum(jnp.where(pick, gbuf[...].T, 0.0), axis=0,
                     keepdims=True)                              # (1, C)
        d = data_ref[pl.ds(w, 1), :].astype(jnp.float32)
        prod[w] = jnp.where(jnp.logical_and(d != 0, valid), d * xs, 0.0)
        return c

    jax.lax.fori_loop(0, w_tile, width_row, 0)
    _add_slices(so_ref, cur, acc, tmp, ysem, lambda w: prod[w],
                lambda s: y_hbm.at[pl.ds(s, 1)],
                g=g, last=pl.num_programs(0) - 1, w_tile=w_tile,
                so_block=so_block, rows=rows)


@functools.partial(jax.jit, static_argnames=("num_slices", "chunk",
                                             "interpret"))
def sellcs_slots_resident(data: jax.Array, cols: jax.Array,
                          slice_of: jax.Array, x_packed: jax.Array, *,
                          num_slices: int, chunk: int,
                          interpret: bool = False) -> jax.Array:
    """``sellcs_slots`` at k = 1 with X resident in VMEM.

    ``x_packed`` is X packed lane-dense (``pack_x``); the kernel copies it
    into VMEM once and gathers each lane's value with a vector load at a
    scalar address, where ``sellcs_slots`` issues one HBM DMA per stored
    nonzero. The output is lane-dense, ``[num_slices, chunk]``: slot
    ``s * chunk + l`` is row ``s``, lane ``l``. The same float32 products
    are added in the same width-row order, so the slots equal
    ``sellcs_slots``'s to the bit, but for the sign of a zero.
    """
    if not interpret and chunk % LANE:
        raise ValueError(f"chunk {chunk} is not a multiple of {LANE}: "
                         "Mosaic moves Y's slices as whole lane rows")
    C = chunk
    WT = GATHER_W_TILE
    W = data.shape[0]
    data, cols, slice_of, so_block, G = _stream(data, cols, slice_of,
                                                interpret)
    R = x_packed.shape[0]
    per = so_block // WT
    smem = pltpu.MemorySpace.SMEM
    block = lambda g: (g, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((so_block,), lambda g: (g // per,),
                         memory_space=smem),
            pl.BlockSpec((WT, C), block, memory_space=smem),
            pl.BlockSpec((WT, C), block),
            pl.BlockSpec((WT, C), block),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((R, LANE), jnp.float32),              # resident X
            pltpu.VMEM((C, LANE), jnp.float32),              # lane groups
            pltpu.VMEM((WT, 1, C), jnp.float32),             # products
            pltpu.VMEM((1, C), jnp.float32),                 # slice acc
            pltpu.VMEM((1, C), jnp.float32),                 # Y read-back
            pltpu.SMEM((1,), jnp.int32),                     # open slice
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    y0 = jnp.zeros((num_slices, C), jnp.float32)
    return pl.pallas_call(
        functools.partial(_sellcs_resident_kernel, w_tile=WT, chunk=C,
                          so_block=so_block, rows=W),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_slices, C), jnp.float32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=sellcs_resident_vmem_bytes(R * LANE, C)),
        interpret=interpret,
    )(slice_of, cols, cols, data, x_packed.astype(jnp.float32), y0)


def sellcs_slots_chunk(data: jax.Array, cols: jax.Array,
                       slice_of: jax.Array, x_pad: jax.Array, *,
                       slice_start: int, num_slices: int, chunk: int,
                       k_tile: int, interpret: bool = False) -> jax.Array:
    """``sellcs_slots`` over one *chunk sub-stream* of the slice stream.

    The chunked distributed merge schedule (``repro.spmm.distributed``)
    splits the σ-sorted stream into spans of ``num_slices`` consecutive
    slices so each span's psum can overlap the next span's compute.
    ``slice_of`` stays GLOBAL in the sub-stream; this entry point rebases it
    to the chunk-local slot space ``[num_slices * chunk, Kp]`` starting at
    global slice ``slice_start``. Padding width-rows (zero data) may carry
    any slice id — they are clipped into range and contribute nothing.
    """
    local = jnp.clip(slice_of.astype(jnp.int32) - slice_start, 0,
                     max(num_slices - 1, 0))
    return sellcs_slots(data, cols, local, x_pad, num_slices=num_slices,
                        chunk=chunk, k_tile=k_tile, interpret=interpret)


def _sellcs_spmm_slots(sc: SellCS, x_pad: jax.Array, *, k_tile: int,
                       interpret: bool = False) -> jax.Array:
    """Accumulate into σ-sorted row slots [S*C, Kp]; the caller undoes the
    permutation."""
    return sellcs_slots(sc.data, sc.cols, sc.slice_of, x_pad,
                        num_slices=sc.num_slices, chunk=sc.chunk,
                        k_tile=k_tile, interpret=interpret)


# --------------------------------------------------------------------------
# SELL-C-σ transpose SpMM (Y = A^T X), k-tiled grid
# --------------------------------------------------------------------------
def _sellcs_t_kernel(slice_of_ref,                # scalar prefetch (SMEM)
                     data_ref, cols_ref, xs_ref,  # VMEM in
                     y_ref,                       # VMEM out (revisited)
                     *, w_tile: int, chunk: int, n_pad: int):
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(w, _):
        s = slice_of_ref[g * w_tile + w]
        # the slot-permuted X makes the read side structured: one
        # contiguous C-block per width-row, no gather
        xb = xs_ref[pl.ds(s * chunk, chunk), :]            # (C, KT)
        prod = (data_ref[w].astype(jnp.float32)[:, None]
                * xb.astype(jnp.float32))                  # (C, KT)
        # scatter to columns via one-hot contraction (MXU-friendly)
        onehot = (cols_ref[w][:, None] ==
                  jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)
                  ).astype(jnp.float32)                    # (C, n_pad)
        y_ref[...] += jax.lax.dot_general(
            onehot, prod, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (n_pad, KT)
        return _

    jax.lax.fori_loop(0, w_tile, body, None)


def interpret_only(kernel: str, interpret: bool) -> None:
    """Refuse a Pallas kernel at a size where it does not lower on Mosaic
    (a VMEM-resident ``[n, ·]`` slab past the VMEM budget): it runs in
    interpret mode only, and the TPU path raises here instead of silently
    running something else."""
    if not interpret:
        raise NotImplementedError(
            f"{kernel} does not lower on Mosaic (TPU); run it with "
            "interpret=True (impl='pallas_interpret') or use impl='ref'")


@functools.partial(jax.jit, static_argnames=("n_out", "chunk", "k_tile",
                                             "interpret"))
def sellcs_slots_t(data: jax.Array, cols: jax.Array, slice_of: jax.Array,
                   x_slots: jax.Array, *, n_out: int, chunk: int,
                   k_tile: int, interpret: bool = False) -> jax.Array:
    """Raw-array transpose pass over a SELL-C-σ width-row stream.

    ``x_slots`` is X permuted into slot space (``reference.sellcs_slot_x``):
    each width-row then reads a *contiguous* C-block at ``slice_of[w] *
    chunk`` and scatter-accumulates ``data[w] * x`` into its column
    indices. The output ``[n_out, Kp]`` is in natural column order — the
    σ-permutation was consumed by the slot gather, so no unpermute
    follows. ``slice_of`` must index the slot space ``x_slots`` spans;
    globalize shard-local slice ids (add ``slice_offset``) before calling.
    Padding entries carry data == 0, cols == 0 (harmless add into column
    0); padding width-rows may carry any in-range slice id.
    """
    C = chunk
    W = data.shape[0]
    Wp = max(-(-W // W_TILE) * W_TILE, W_TILE)
    if Wp != W:
        pad = Wp - W
        data = jnp.concatenate([data, jnp.zeros((pad, C), data.dtype)])
        cols = jnp.concatenate([cols, jnp.zeros((pad, C), cols.dtype)])
        slice_of = jnp.concatenate(
            [slice_of, jnp.zeros((pad,), slice_of.dtype)])

    n_pad = -(-max(n_out, 1) // LANE) * LANE
    SC, Kp = x_slots.shape
    if 2 * (SC + n_pad) * _lanes(k_tile) * 4 > VMEM_BUDGET_BYTES:
        # both slabs stay VMEM-resident, and the one-hot scatter is
        # (C, n_pad) per width-row
        interpret_only(f"sellcs_slots_t at n={n_out}, {SC} slots", interpret)
    nk = Kp // k_tile
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nk, Wp // W_TILE),
        in_specs=[
            pl.BlockSpec((W_TILE, C), lambda j, g, *_: (g, 0)),
            pl.BlockSpec((W_TILE, C), lambda j, g, *_: (g, 0)),
            pl.BlockSpec((SC, k_tile), lambda j, g, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((n_pad, k_tile), lambda j, g, *_: (0, j)),
    )
    y = pl.pallas_call(
        functools.partial(_sellcs_t_kernel, w_tile=W_TILE, chunk=C,
                          n_pad=n_pad),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, Kp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(slice_of, data, cols, x_slots)
    return y[:n_out]


def _slot_x_pad(row_perm: jax.Array, x: jax.Array, m: int,
                kt: int) -> jax.Array:
    """Slot-space X for the transpose pass, k-padded for the k-tile grid.
    Padding slots (``row_perm == m``) read a zero row."""
    x_pad = jnp.concatenate(
        [x, jnp.zeros((1, x.shape[1]), x.dtype)], axis=0)
    return _pad_k(x_pad[row_perm], kt)


def sellcs_spmm(sc: SellCS, x: jax.Array, *, k_tile: Optional[int] = None,
                interpret: bool = False, op: str = "N") -> jax.Array:
    """SELL-C-σ SpMM: each grid step multiplies GATHER_W_TILE
    width-vectors of the slice stream by the X rows they name — uniform
    work quanta regardless of row-length skew (the σ-sorted answer to the
    paper's mawi pathology), with the x-gather as the only irregular
    access. X is gathered from VMEM where it fits there (``x_resident``:
    a single vector on a TPU), else from HBM by DMA.

    ``op='T'`` computes ``Y = A^T X`` (``X: [m, k]``) via the transpose
    kernel; symmetric one-triangle storage combines both passes over the
    stored triangle (``A X = N(X) + T(X) - diag * X``), for which
    ``op='T'`` and ``op='N'`` coincide.
    """
    if op not in ("N", "T"):
        raise ValueError(f"op must be 'N' or 'T', got {op!r}")
    m, n = sc.shape
    k = x.shape[1]
    kt = k_tile or choose_k_tile(k, chunk=sc.chunk)
    sym = sc.structure == "symmetric"
    if op == "T" and not sym:
        if sc.nnz == 0:
            return jnp.zeros((n, k), jnp.float32)
        xs = _slot_x_pad(sc.row_perm, x, m, kt)
        y = sellcs_slots_t(sc.data, sc.cols, sc.slice_of, xs, n_out=n,
                           chunk=sc.chunk, k_tile=kt, interpret=interpret)
        return y[:, :k]
    # X stays resident in VMEM where it fits; the rule reads shapes only
    resident = x_resident(n, k, vmem_capacity_bytes(), chunk=sc.chunk)
    # the spans are not blocked: they name what the host dispatches, so a
    # device gap that opens under one is named for it
    with span("spmm/pad_x"):
        if resident:
            x_pad = pack_x(x)
        else:
            np_ = -(-max(n, 1) // LANE) * LANE
            x_pad = jnp.zeros((np_, k), x.dtype).at[:n].set(x)
            x_pad = _pad_k(x_pad, kt)
    if sc.nnz == 0:
        return jnp.zeros((m, k), jnp.float32)
    reg = live_registry()
    if reg is not None:
        # from shapes alone: each k-tile pass walks every width-row, and
        # visits each live lane once (rows in a slice are sorted longest
        # first, so the live lanes of the stream number nnz); on the DMA
        # path a live lane is one X-row DMA, on the resident path one
        # VMEM load
        nk = 1 if resident else x_pad.shape[1] // kt
        W = sc.data.shape[0]
        reg.counter("sellcs/width_rows").inc(W * nk)
        reg.counter("sellcs/slots").inc(W * sc.chunk * nk)
        reg.counter("sellcs/lanes_live").inc(sc.nnz * nk)
        reg.counter("sellcs/x_resident_lanes").inc(
            sc.nnz * nk if resident else 0)
        reg.counter("sellcs/split_slots").inc(sc.split_slots * nk)
        reg.counter("sellcs/split_lanes").inc(sc.split_lanes * nk)
    with span("spmm/kernel"):
        if resident:
            y_slots = sellcs_slots_resident(
                sc.data, sc.cols, sc.slice_of, x_pad,
                num_slices=sc.num_slices, chunk=sc.chunk,
                interpret=interpret).reshape(-1, 1)              # (S*C, 1)
        else:
            # the k-tile padding columns stop here: the σ-unpermute moves
            # k only
            y_slots = _sellcs_spmm_slots(sc, x_pad, k_tile=kt,
                                         interpret=interpret)[:, :k]
    with span("spmm/fixup"):
        # row_perm is not injective: the virtual rows of a split row share
        # its id, and the scatter-add sums them into it
        y = jnp.zeros((m + 1, k), jnp.float32).at[sc.row_perm].add(y_slots)
        y = y[:m]
    if sym:
        xs = _slot_x_pad(sc.row_perm, x, m, kt)
        y = (y + sellcs_slots_t(sc.data, sc.cols, sc.slice_of, xs,
                                n_out=n, chunk=sc.chunk, k_tile=kt,
                                interpret=interpret)[:, :k]
             - sc.diag[:, None] * x)
    return y
