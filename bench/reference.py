"""The plain reference that decides ``correct``, and its control.

``y = A @ x`` straight from the generator's triplets, in float64 on the
host (scipy's CSR product), independent of the code under test: it imports
nothing of ``repro`` and takes nothing the program made. The number
compared is the normwise error of each sampled answer,
``||y - A x||_2 / ||A x||_2``.

The control puts the reference in the program's place one precision step
down: the matrix values and the vectors stored in bfloat16, products and
sums in float32 — the step a later change to the float32 path would be
tempted to take. It must read above the limit.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp


def csr(rows, cols, vals, shape, dtype=np.float64) -> sp.csr_array:
    """The matrix as a CSR array; duplicate coordinates are summed."""
    return sp.coo_array((np.asarray(vals, dtype),
                         (np.asarray(rows, np.int64),
                          np.asarray(cols, np.int64))),
                        shape=shape).tocsr()


def multiply(a: sp.csr_array, x: np.ndarray) -> np.ndarray:
    """``A @ X`` for ``X`` of shape ``[n, s]``, in ``a``'s precision."""
    return np.asarray(a @ np.asarray(x, a.dtype))


def _bf16(v: np.ndarray) -> np.ndarray:
    return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def control_csr(rows, cols, vals, shape) -> sp.csr_array:
    """The control's matrix: values rounded to bfloat16, held in float32."""
    return csr(rows, cols, _bf16(vals), shape, np.float32)


def control_multiply(a: sp.csr_array, x: np.ndarray) -> np.ndarray:
    """The control's ``A @ X``: ``a`` from :func:`control_csr`, ``X``
    rounded to bfloat16, float32 arithmetic."""
    return multiply(a, _bf16(x))


def normwise_err(y: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per column ``||y - ref|| / ||ref||``; a zero reference column gives
    0 for a zero answer and infinity for any other."""
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    diff = np.linalg.norm(y - ref, axis=0)
    norm = np.linalg.norm(ref, axis=0)
    with np.errstate(divide="ignore", invalid="ignore"):
        err = np.where(norm > 0, diff / np.where(norm > 0, norm, 1.0),
                       np.where(diff > 0, np.inf, 0.0))
    # a NaN anywhere in an answer is a wrong answer
    return np.where(np.isnan(err), np.inf, err)
