"""TiledSparse — the TPU compute format for unstructured SpMV.

Hardware adaptation (DESIGN.md §2): the paper's CPU algorithms do per-nonzero
``y[r] += v * x[c]`` — a scatter/gather pattern with no efficient TPU
lowering (the VPU has no cheap vector scatter; the MXU wants dense tiles).
The TPU dialect of the paper's *blocked* formats is therefore hierarchical:

  level 0  (paper: sparse block, cache-sized)   macro block, beta x beta
  level 1  (new, hardware)                      dense 8 x 128 mini-tiles
                                                 (VREG sublane x lane shape)

Only nonempty mini-tiles are stored (dense, zero-filled). SpMV per mini-tile
is a dense (8,128) @ (128,) matvec — pure MXU/VPU work, no scatter. What
survives of each paper algorithm:

  * blocking       -> beta chooses the x/y slab reuse distance;
  * nonzero order  -> the mini-tile visit order (row / Morton / Hilbert at
                      both macro and in-macro level) controls how often the
                      x- and y-windows move => Pallas elides copies for
                      consecutive same-index windows (the cache-reuse story,
                      measurable as window-switch counts);
  * load balancing -> uniform work quanta (every tile = same FLOPs) plus
                      merge-path spans over tiles; a single dense row is
                      split across many tiles (the mawi fix).

The price is fill-in: ``fill_ratio`` = nnz / (1024 * num_tiles). For very
sparse matrices fill-in makes the XLA gather path cheaper — the paper's
density-dependent algorithm choice, reappearing on TPU (see selector +
EXPERIMENTS).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import COO
from repro.core.convert import ALGORITHM_SPECS, block_size_for
from repro.core.curves import hilbert_key_np
from repro.core.formats import static_field, _pytree_dataclass
from repro.core.mergepath import balanced_row_bands

TILE_R = 8      # sublane dimension
TILE_C = 128    # lane dimension


def interpret_only(kernel: str, interpret: bool) -> None:
    """Refuse a Pallas kernel that does not lower on Mosaic (a
    VMEM-resident ``[n, ·]`` slab, an in-kernel ``jnp.take`` gather, or a
    sub-tile block shape): it runs in interpret mode only, and the TPU path
    raises here instead of silently running something else."""
    if not interpret:
        raise NotImplementedError(
            f"{kernel} does not lower on Mosaic (TPU); run it with "
            "interpret=True (impl='pallas_interpret'), use impl='ref', or "
            "convert to SELL-C-σ (coo_to_sellcs), whose kernel does")


def _morton_key_np(rows, cols, bits):
    r = np.asarray(rows, np.uint64)
    c = np.asarray(cols, np.uint64)
    key = np.zeros(r.shape, np.uint64)
    for b in range(bits):
        key |= ((r >> np.uint64(b)) & np.uint64(1)) << np.uint64(2 * b + 1)
        key |= ((c >> np.uint64(b)) & np.uint64(1)) << np.uint64(2 * b)
    return key.astype(np.int64)


@_pytree_dataclass
class TiledSparse:
    """Dense 8x128 mini-tiles of an unstructured sparse matrix."""
    tiles: jax.Array        # f32[T, 8, 128]
    tile_rows: jax.Array    # int32[T] — global tile-row index (row // 8)
    tile_cols: jax.Array    # int32[T] — global tile-col index (col // 128)
    shape: Tuple[int, int] = static_field()
    beta: int = static_field()           # macro block size used for ordering
    order: str = static_field()          # algorithm preset name
    nnz: int = static_field()            # true nonzeros (before fill-in)

    @property
    def num_tiles(self) -> int:
        return self.tiles.shape[0]

    @property
    def fill_ratio(self) -> float:
        t = self.num_tiles
        return self.nnz / (t * TILE_R * TILE_C) if t else 0.0

    def padded_shape(self) -> Tuple[int, int]:
        m, n = self.shape
        return (-(-m // TILE_R) * TILE_R, -(-n // TILE_C) * TILE_C)

    def window_switches(self) -> Tuple[int, int]:
        """(#x-window moves, #y-window moves) across the tile visit order —
        the TPU proxy for the paper's cache-miss counts."""
        tr = np.asarray(self.tile_rows)
        tc = np.asarray(self.tile_cols)
        if tr.size <= 1:
            return (tr.size, tr.size)
        return (int(np.sum(tc[1:] != tc[:-1]) + 1),
                int(np.sum(tr[1:] != tr[:-1]) + 1))

    def storage_bytes(self) -> int:
        return int(self.tiles.size * self.tiles.dtype.itemsize
                   + 2 * 4 * self.num_tiles)


def coo_to_tiled(coo: COO, algorithm: str = "csb", *,
                 beta: Optional[int] = None, num_bands: int = 0,
                 dtype=jnp.float32,
                 max_bytes: int = 8 * 2 ** 30) -> TiledSparse:
    """Convert COO -> TiledSparse with the visit order of ``algorithm``
    (any blocked ALGORITHM_SPECS key; flat 'merge'/'parcrs' get row order)."""
    spec = ALGORITHM_SPECS[algorithm]
    m, n = coo.shape
    if beta is None:
        beta = block_size_for(coo.shape,
                              in_block_format=spec.in_block_format)
    beta = max(beta, TILE_C)            # a macro block holds >=1 tile column

    rows = np.asarray(coo.rows, np.int64)
    cols = np.asarray(coo.cols, np.int64)
    vals = np.asarray(coo.data)

    tr, tc = rows // TILE_R, cols // TILE_C           # mini-tile coords
    Nt_c = -(-n // TILE_C)
    tile_key = tr * Nt_c + tc                          # tile identity

    # ordering key: (band, macro curve key, in-macro tile curve key)
    mb_r, mb_c = rows // beta, cols // beta
    Mb, Nb = -(-m // beta), -(-n // beta)
    grid_bits = max(int(np.ceil(np.log2(max(Mb, Nb, 2)))), 1)
    # tile coords within macro block
    ltr = tr - mb_r * (beta // TILE_R)
    ltc = tc - mb_c * (beta // TILE_C)
    loc_bits = max(int(np.ceil(np.log2(max(beta // TILE_R,
                                           beta // TILE_C, 2)))), 1)

    border = spec.block_order if spec.blocked else "row"
    iorder = spec.in_block_order if spec.blocked else "row"
    if border == "hilbert":
        mkey = hilbert_key_np(mb_r, mb_c, grid_bits)
    elif border == "morton":
        mkey = _morton_key_np(mb_r, mb_c, grid_bits)
    else:
        mkey = mb_r * Nb + mb_c
    if iorder == "hilbert":
        lkey = hilbert_key_np(ltr, ltc, loc_bits)
    elif iorder == "morton":
        lkey = _morton_key_np(ltr, ltc, loc_bits)
    else:
        lkey = ltr * (beta // TILE_C + 1) + ltc

    if num_bands > 0:
        Mbr = -(-m // beta)
        blk_row_ptr = np.zeros(Mbr + 1, np.int64)
        np.cumsum(np.bincount(mb_r, minlength=Mbr), out=blk_row_ptr[1:])
        bands = balanced_row_bands(blk_row_ptr, num_bands)
        band = np.searchsorted(bands, mb_r, side="right") - 1
    else:
        band = np.zeros(rows.size, np.int64)

    perm = np.lexsort((lkey, mkey, band))
    rows, cols, vals = rows[perm], cols[perm], vals[perm]
    tile_key = tile_key[perm]

    # unique tiles in first-visit order
    first_seen, inv = {}, np.zeros(rows.size, np.int64)
    uniq, first_idx = np.unique(tile_key, return_index=True)
    # order tiles by first occurrence in the sorted stream
    order_of_uniq = np.argsort(first_idx, kind="stable")
    rank = np.empty(uniq.size, np.int64)
    rank[order_of_uniq] = np.arange(uniq.size)
    inv = rank[np.searchsorted(uniq, tile_key)]

    T = uniq.size
    if T * TILE_R * TILE_C * 4 > max_bytes:
        raise MemoryError(
            f"TiledSparse would need {T * TILE_R * TILE_C * 4 / 2**30:.1f} "
            f"GiB (fill ratio {rows.size / max(T * 1024, 1):.2e}); use the "
            "XLA gather path for this density (selector does this).")

    tiles = np.zeros((max(T, 1), TILE_R, TILE_C), np.float32)
    lr = (rows % TILE_R).astype(np.int64)
    lc = (cols % TILE_C).astype(np.int64)
    np.add.at(tiles, (inv, lr, lc), vals.astype(np.float32))

    uniq_in_order = uniq[order_of_uniq]
    tile_rows = (uniq_in_order // Nt_c).astype(np.int32)
    tile_cols = (uniq_in_order % Nt_c).astype(np.int32)
    if T == 0:
        tile_rows = np.zeros(1, np.int32)
        tile_cols = np.zeros(1, np.int32)

    return TiledSparse(
        tiles=jnp.asarray(tiles, dtype), tile_rows=jnp.asarray(tile_rows),
        tile_cols=jnp.asarray(tile_cols), shape=coo.shape, beta=int(beta),
        order=algorithm, nnz=int(rows.size))
