"""repro — "Algorithms for Parallel Shared-Memory Sparse Matrix-Vector
Multiplication on Unstructured Matrices", grown into a JAX/Pallas system.

Module map
----------
``repro.core``       the paper's contribution: storage formats (COO/CSR/
                     ICRS/BICRS/BlockedSparse), space-filling-curve
                     orderings, merge-path balancing, conversion pipeline,
                     the §7 algorithm selector (k-aware ``select``) and the
                     §8 autotuner.
``repro.spmm``       the multi-RHS SpMM engine: SELL-C-σ storage
                     (``sellcs``; ``structure="symmetric"`` stores one
                     triangle + diagonal), pure-jnp oracles
                     (``reference``), the one Pallas kernel family —
                     SELL-C-σ forward (HBM-gather and VMEM-resident
                     k = 1) and scatter-accumulate transpose, each with a
                     k-tile grid dimension (``kernels``) — behind the one
                     dispatcher ``spmm``, request batching for the serve
                     path (``batching``), the shard_map mesh schedules —
                     row bands / merge spans over the slice stream, both
                     op-aware (``op="N"|"T"``, ``distributed``) — and
                     ``SparseOperator`` (``operator``): the stable
                     partition-once/multiply-many handle whose atomic plan
                     swap carries the serve path's online format
                     migration, with ``rmatmul``/``.T`` running ``A^T X``
                     over the same stored plan and ``sparse_matmul``
                     making both ends differentiable, and ``Fleet``
                     (``fleet``): the multi-tenant operator registry —
                     fingerprint-keyed plan cache, device-loss re-deal via
                     ``redeal_sellcs``, LRU eviction under a
                     ``max_bytes`` storage budget. SpMV is the k = 1
                     special case.
``repro.kernels``    the TiledSparse 8x128 mini-tile blocked format and
                     the merge-path plan, each with an XLA reference, and
                     the MoE grouped-GEMM Pallas kernel.
``repro.roofline``   roofline terms from compiled HLO + the SpMM intensity
                     model that picks k-tiles.
``repro.data``       synthetic matrix generators matched to the paper's
                     test-set classes (uniform/rmat/powerlaw/mesh2d/
                     ``mawi_like`` skew) and the token pipeline.
``repro.models``     the LM stack (attention/SSM/MoE) whose sparse pieces
                     exercise the kernels at scale.
``repro.configs``    model architecture presets.
``repro.obs``        observability: the process-local ``MetricRegistry``
                     (phase spans, exact percentile histograms), the
                     observed-vs-modeled ``ResidualLedger`` that feeds
                     ``select_distributed``/``autotune(feedback=)``, and
                     the §5.2 ``time_min_of_n`` protocol.
``repro.launch``     meshes, shardings, train/serve/dryrun entry points —
                     ``launch.serve --mode spmv`` drives the SpMM request
                     batcher through one ``SparseOperator`` handle, with
                     ``--migrate auto|force`` running the online
                     break-even format migration behind it;
                     ``--mode fleet`` serves N tenants through a
                     ``Fleet`` + ``FleetBatcher`` front end and survives
                     an injected mid-stream device loss.
``repro.optim``      optimizers.
``repro.checkpoint`` checkpointing.
``repro.runtime``    elasticity + fault tolerance: ``elastic`` rebuilds
                     meshes from the live device set
                     (``largest_feasible_mesh``, the guard-checked
                     ``reshard``) and ``fault_tolerance`` watches step
                     times (``StragglerMonitor``) — both wired into the
                     serve fleet's device-loss path.

Submodules import lazily (nothing heavy happens at ``import repro``).
"""
__version__ = "0.1.0"

__all__ = [
    "core", "spmm", "kernels", "roofline", "data", "models", "configs",
    "obs", "launch", "optim", "checkpoint", "runtime",
]
