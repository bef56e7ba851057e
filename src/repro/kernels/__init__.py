"""repro.kernels — the TiledSparse blocked storage format (8x128 dense
mini-tiles, ``tiling``) and the merge-path plan (``merge_spmv``), each with
an XLA reference path (``ref``), and the MoE grouped-GEMM Pallas kernel
(``ops``). The sparse Pallas kernels are one SELL-C-σ family in
``repro.spmm.kernels``."""
from . import ops, ref
from .tiling import TILE_C, TILE_R, TiledSparse, coo_to_tiled
from .merge_spmv import MergePlan, merge_plan

__all__ = ["ops", "ref", "TiledSparse", "coo_to_tiled", "TILE_R", "TILE_C",
           "MergePlan", "merge_plan"]
