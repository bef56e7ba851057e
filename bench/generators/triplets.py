"""The host triplets every generator returns."""
from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np


class Triplets(NamedTuple):
    rows: np.ndarray        # int32[nnz]
    cols: np.ndarray        # int32[nnz]
    vals: np.ndarray        # float32[nnz]
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.rows.size)
