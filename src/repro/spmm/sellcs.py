"""SELL-C-σ storage (sliced ELLPACK with σ-window row sorting).

The paper's row-distributed algorithms break on row-length skew (the mawi
pathology, Table 6.3); the survey literature's standard fix is SELL-C-σ
[Kreutzer et al.; Gao et al., arXiv:2404.06047 §4]: group rows into slices
of height C, pad each slice only to *its own* longest row, and sort rows by
length inside windows of σ rows so that similar-length rows share a slice —
padding collapses and every slice is a uniform work quantum.

TPU mapping: C defaults to the Pallas lane width (128) so one width-step of
a slice is one (C,)-lane vector: the SpMM kernel broadcasts it against a
(C, k) block of X and accumulates into C output rows — VPU work with no
scatter. Row sorting is a *permutation*, recorded in ``row_perm`` and undone
by a single scatter at the end of the multiply.

Row splitting (``split_long_rows=True``, the one-device plan): a slice is
as wide as its longest row, so one hub row of L entries costs C·L slots.
Every row longer than the C-th longest row's length L* becomes ⌈L/L*⌉
*virtual rows* of near-equal length, each a consecutive run of the row's
columns, and the virtual rows are sorted with the others. Each keeps its
row's id in ``row_perm``, which then maps several slots to one row; the
unpermute scatter-add sums them. A matrix whose rows are all ≤ L* gets
the unsplit stream, bit for bit.

Layout (width-major, slice-concatenated):

  ``data[w, l]`` / ``cols[w, l]`` — the ``j``-th nonzero of the row in lane
  ``l`` of slice ``slice_of[w]``, where ``j = w - slice_ptr[slice_of[w]]``.
  Padding entries carry ``data == 0`` and ``cols == 0`` (harmless FMA).

Symmetric one-triangle mode (``structure="symmetric"``): for ``A == A^T``
only the lower triangle (``row >= col``, diagonal included) enters the
slice stream, halving the streamed bytes of the memory-bound multiply. A
dense ``diag`` vector rides along so the multiply can combine the normal
and transpose passes over the one stored triangle:
``A X = N-pass(X) + T-pass(X) - diag * X`` (the diagonal is counted by
both passes, so it is subtracted once). ``to_coo`` mirrors the
off-diagonal entries back out, so the round trip is dense-equivalent to
the full matrix and every oracle keeps working unchanged.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import COO, static_field, _pytree_dataclass
from repro.obs import maybe_block, span

Array = jax.Array

DEFAULT_C = 128          # Pallas lane width
DEFAULT_SIGMA_SLICES = 16   # default σ = 16 slices' worth of rows


@_pytree_dataclass
class SellCS:
    """SELL-C-σ matrix as a JAX pytree (see module docstring for layout)."""
    data: Array            # f32[W, C] — padded values, width-major
    cols: Array            # int32[W, C] — padded column indices
    slice_ptr: Array       # int32[S+1] — width offset of each slice
    slice_of: Array        # int32[W] — owning slice of each width-row
    row_perm: Array        # int32[S*C] — permuted slot -> original row
                           #   (padding slots point at m, dropped on scatter;
                           #   the virtual rows of a split row share its id)
    row_len: Array         # int32[S*C] — true nnz of each permuted slot
    diag: Optional[Array]  # f32[m] dense diagonal (symmetric mode), else None
    shape: Tuple[int, int] = static_field()
    chunk: int = static_field()          # C — slice height
    sigma: int = static_field()          # σ — sorting window (rows)
    nnz: int = static_field()            # stored nonzeros before padding
                                         #   (one triangle in symmetric mode)
    structure: str = static_field(default="general")   # "general"|"symmetric"
    split_slots: int = static_field(default=0)   # W·C of the slices that
                                                 #   hold a virtual row
    split_lanes: int = static_field(default=0)   # entries of virtual rows

    @property
    def num_slices(self) -> int:
        return self.slice_ptr.shape[0] - 1

    @property
    def padded_nnz(self) -> int:
        return int(self.data.shape[0]) * self.chunk

    @property
    def fill_ratio(self) -> float:
        """nnz / padded entries — 1.0 means σ-sorting removed all padding."""
        p = self.padded_nnz
        return self.nnz / p if p else 0.0

    def storage_bytes(self) -> int:
        """Faithful SELL-C-σ cost: every int32/value array the format
        actually stores — padded values + padded column indices + slice
        pointers + per-width-row slice ids + the row permutation + per-slot
        true row lengths. Kept equal to the sum of the member arrays'
        ``nbytes`` (asserted in the tests) so conversion-amortization
        comparisons never flatter this format."""
        W = self.data.shape[0]
        b = int(W * self.chunk * (self.data.dtype.itemsize + 4)
                + self.slice_ptr.shape[0] * 4
                + self.slice_of.shape[0] * 4
                + self.row_perm.shape[0] * 4
                + self.row_len.shape[0] * 4)
        if self.diag is not None:
            b += int(self.diag.shape[0] * self.diag.dtype.itemsize)
        return b

    def to_coo(self) -> COO:
        """Exact round-trip (host-side), including explicit zeros. In
        symmetric mode the stored lower triangle is mirrored back out, so
        the result is dense-equivalent to the full matrix."""
        m, n = self.shape
        C = self.chunk
        data = np.asarray(self.data)
        cols = np.asarray(self.cols)
        slice_ptr = np.asarray(self.slice_ptr, np.int64)
        slice_of = np.asarray(self.slice_of, np.int64)
        row_perm = np.asarray(self.row_perm, np.int64)
        row_len = np.asarray(self.row_len, np.int64)
        W = data.shape[0]
        if W == 0 or self.nnz == 0:
            z = jnp.zeros((0,), jnp.int32)
            return COO(z, z, jnp.zeros((0,), self.data.dtype), self.shape)
        j = np.arange(W, dtype=np.int64) - slice_ptr[slice_of]      # [W]
        slot = slice_of[:, None] * C + np.arange(C, dtype=np.int64)  # [W, C]
        valid = j[:, None] < row_len[slot]
        rows = row_perm[slot][valid]
        vals = data[valid]
        ccols = cols[valid].astype(np.int64)
        if self.structure == "symmetric":
            off = rows != ccols                  # strict lower triangle
            rows, ccols = (np.concatenate([rows, ccols[off]]),
                           np.concatenate([ccols, rows[off]]))
            vals = np.concatenate([vals, vals[off]])
        return COO(jnp.asarray(rows.astype(np.int32)),
                   jnp.asarray(ccols.astype(np.int32)),
                   jnp.asarray(vals), self.shape)


def _dedup_sums(keys: np.ndarray, vals: np.ndarray):
    """Coordinate-summed (key, value) pairs in sorted key order."""
    order = np.argsort(keys, kind="stable")
    k, v = keys[order], vals[order].astype(np.float64)
    uk, start = np.unique(k, return_index=True)
    return uk, np.add.reduceat(v, start) if v.size else v


def _symmetric_lower(rows, cols, vals, shape):
    """Validate ``A == A^T`` (pattern and values, after summing duplicate
    coordinates) on host triplets and return the lower-triangle stream +
    dense diagonal. Raises ``ValueError`` on a non-square or asymmetric
    input."""
    m, n = shape
    if m != n:
        raise ValueError(
            f"structure='symmetric' needs a square matrix, got {m}x{n}")
    ka, va = _dedup_sums(rows * n + cols, vals)
    kb, vb = _dedup_sums(cols * n + rows, vals)
    # pattern must match exactly; summed duplicate values only to fp-sum
    # reassociation tolerance (the two sides add duplicates in different
    # orders)
    scale = float(np.abs(va).max()) if va.size else 1.0
    if ka.shape != kb.shape or not np.array_equal(ka, kb) \
            or not np.allclose(va, vb, rtol=1e-6, atol=1e-9 * max(scale, 1.0)):
        raise ValueError(
            "structure='symmetric' requires A == A^T (pattern and values); "
            "store the full matrix with structure='general' instead")
    keep = rows >= cols                       # one triangle, diagonal kept
    dtype = np.float32 if vals.size == 0 else vals.dtype
    diag = np.zeros(m, dtype)
    on_d = rows == cols
    np.add.at(diag, rows[on_d], vals[on_d])
    return rows[keep], cols[keep], vals[keep], diag


def _split_cap(row_len: np.ndarray, c: int) -> int:
    """L*, the length of the ``c``-th longest row: no row is longer once
    split, and the first slices come out full. 0 where nothing would be
    split: no row is longer, fewer than ``c`` rows are non-empty, or the
    matrix has fewer than ``c`` rows."""
    m = row_len.size
    if m < c:
        return 0
    cap = int(np.partition(row_len, m - c)[m - c])
    return cap if cap and int(row_len.max()) > cap else 0


def _split_long_rows(rows, cols, row_len, cap):
    """Replace every row longer than ``cap`` with ⌈L/cap⌉ virtual rows of
    near-equal length, each a run of the row's entries in column order.
    A row's first piece keeps the row's id; the other pieces are numbered
    from m up. Returns each entry's virtual row, each virtual row's owner
    row and length, and the ids of the long rows."""
    m = row_len.size
    long = np.flatnonzero(row_len > cap)
    L = row_len[long]
    q = -(-L // cap)                       # pieces per long row
    base, rem = L // q, L % q              # the first rem pieces hold base+1
    extra = q - 1
    first = m + np.cumsum(extra) - extra   # id of each long row's piece 1
    is_long = np.zeros(m, bool)
    is_long[long] = True
    sel = np.flatnonzero(is_long[rows])
    sel = sel[np.lexsort((cols[sel], rows[sel]))]   # grouped as ``long``
    which = np.repeat(np.arange(long.size), L)
    j = np.arange(sel.size) - np.repeat(np.cumsum(L) - L, L)
    b, r = base[which], rem[which]
    big = r * (b + 1)
    piece = np.where(j < big, j // (b + 1), r + (j - big) // b)
    vrows = rows.copy()
    vrows[sel] = np.where(piece == 0, long[which], first[which] + piece - 1)
    k = np.repeat(np.arange(long.size), extra)
    pk = np.arange(k.size) - (first - m)[k] + 1     # piece number, 1..q-1
    owner = np.concatenate([np.arange(m, dtype=np.int64), long[k]])
    vlen = np.concatenate([row_len, base[k] + (pk < rem[k])])
    vlen[long] = base + (rem > 0)
    return vrows, owner, vlen, long


def _layout(vlen, owner, m: int, sigma: int, c: int):
    """The σ-window sort of the (virtual) rows and the stream's shape.
    Rows are ordered by (window, -length, id) — stable, so equal-length
    rows keep their relative order (reproducible); a virtual row sorts in
    its owner row's window. ``owner`` None: every row is its own."""
    V = vlen.size
    vid = np.arange(V, dtype=np.int64)
    own = vid if owner is None else owner
    order = np.lexsort((vid, -vlen, own // sigma))     # pos -> virtual row
    S = max(-(-V // c), 1)
    slots = S * c
    row_perm = np.full(slots, m, np.int64)
    row_perm[:V] = own[order]
    row_len = np.zeros(slots, np.int64)
    row_len[:V] = vlen[order]
    widths = row_len.reshape(S, c).max(axis=1)      # per-slice width
    slice_ptr = np.zeros(S + 1, np.int64)
    np.cumsum(widths, out=slice_ptr[1:])
    slice_of = np.repeat(np.arange(S, dtype=np.int64), widths)
    return order, row_perm, row_len, slice_ptr, slice_of


def coo_to_sellcs(coo: COO, *, c: int = DEFAULT_C,
                  sigma: Optional[int] = None,
                  structure: str = "general",
                  split_long_rows: bool = False) -> SellCS:
    """Convert COO -> SELL-C-σ (host-side, like every conversion here).

    ``sigma`` is the row-sorting window in rows; it is rounded up to a
    multiple of ``c``. ``sigma=None`` uses ``DEFAULT_SIGMA_SLICES * c``;
    ``sigma >= m`` gives a single global sort (maximal padding reduction,
    maximal permutation scatter); ``sigma = c`` sorts only within slices.

    ``structure="symmetric"`` stores one triangle (``row >= col``) plus a
    dense diagonal; the input must satisfy ``A == A^T`` exactly (pattern
    and values) or a ``ValueError`` is raised.

    ``split_long_rows=True`` splits every stored row longer than the
    ``c``-th longest one into virtual rows (module docstring). The mesh
    partitioners refuse such a stream: their row bands assume each row
    lives in one slot.
    """
    m, n = coo.shape
    if c < 1:
        raise ValueError(f"slice height C must be >= 1, got {c}")
    if structure not in ("general", "symmetric"):
        raise ValueError(f"structure must be 'general' or 'symmetric', "
                         f"got {structure!r}")
    if sigma is None:
        sigma = DEFAULT_SIGMA_SLICES * c
    sigma = max(-(-sigma // c) * c, c)

    with span("convert/pull"):
        rows = np.asarray(coo.rows, np.int64)
        cols = np.asarray(coo.cols, np.int64)
        vals = np.asarray(coo.data)

    with span("convert/sellcs_order"):
        diag = None
        if structure == "symmetric":
            rows, cols, vals, diag = _symmetric_lower(rows, cols, vals,
                                                      coo.shape)
        row_len_orig = (np.bincount(rows, minlength=m).astype(np.int64)
                        if m else np.zeros(0, np.int64))
        cap = _split_cap(row_len_orig, c) if split_long_rows else 0
        if not cap:
            order, row_perm, row_len, slice_ptr, slice_of = _layout(
                row_len_orig, None, m, sigma, c)

    split_slots = split_lanes = 0
    if cap:
        with span("convert/sellcs_split"):
            rows, owner, vlen, long = _split_long_rows(rows, cols,
                                                       row_len_orig, cap)
            order, row_perm, row_len, slice_ptr, slice_of = _layout(
                vlen, owner, m, sigma, c)
            is_piece = np.zeros(vlen.size, bool)
            is_piece[long] = True
            is_piece[m:] = True
            held = np.unique(np.flatnonzero(is_piece[order]) // c)
            split_slots = int(np.sum(np.diff(slice_ptr)[held])) * c
            split_lanes = int(row_len_orig[long].sum())
    W = int(slice_ptr[-1])
    slots = row_len.size

    with span("convert/sellcs_fill"):
        data = np.zeros((W, c), np.float32 if vals.size == 0 else vals.dtype)
        col_arr = np.zeros((W, c), np.int64)
        if rows.size:
            inv = np.empty(order.size, np.int64)
            inv[order] = np.arange(order.size)
            p = inv[rows]                               # permuted position
            sort2 = np.lexsort((cols, p))
            p, cc, vv = p[sort2], cols[sort2], vals[sort2]
            row_start = np.zeros(slots + 1, np.int64)
            np.cumsum(row_len, out=row_start[1:])
            j = np.arange(p.size, dtype=np.int64) - row_start[p]
            wrow = slice_ptr[p // c] + j
            lane = p % c
            data[wrow, lane] = vv
            col_arr[wrow, lane] = cc

    # the int32 copies stay between the uploads: made before the first
    # upload, they slowed a 1G-slot stream's registration by 4-7 % on a v5e
    with span("convert/put"):
        return maybe_block(SellCS(
            data=jnp.asarray(data),
            cols=jnp.asarray(col_arr.astype(np.int32)),
            slice_ptr=jnp.asarray(slice_ptr.astype(np.int32)),
            slice_of=jnp.asarray(slice_of.astype(np.int32)),
            row_perm=jnp.asarray(row_perm.astype(np.int32)),
            row_len=jnp.asarray(row_len.astype(np.int32)),
            diag=None if diag is None else jnp.asarray(diag),
            shape=coo.shape, chunk=int(c), sigma=int(sigma),
            nnz=int(rows.size), structure=structure,
            split_slots=split_slots, split_lanes=split_lanes))
