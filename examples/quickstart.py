"""Quickstart: the paper's pipeline end-to-end on one unstructured matrix.

  1. generate an unstructured (power-law) sparse matrix;
  2. inspect its stats and let the paper's §7 selector pick an algorithm;
  3. convert (the paper's conversion phase) and multiply (9 algorithms);
  4. validate everything against the dense oracle;
  5. show SELL-C-σ, the format of the Pallas kernel family, through the
     one SpMM dispatcher (interpret mode on CPU).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import (ALGORITHM_SPECS, MachineSpec, convert, matrix_stats,
                        select_algorithm, spmv, spmv_dense_oracle, to_coo)
from repro.data import matrices
from repro.spmm import coo_to_sellcs, spmm

# 1. an unstructured matrix (LiveJournal-like power-law rows)
rows, cols, vals, shape = matrices.powerlaw(4096, 4096, 65536, seed=0)
coo = to_coo(rows, cols, vals, shape)
x = jnp.asarray(np.random.default_rng(1).standard_normal(shape[1])
                .astype(np.float32))
y_ref = spmv_dense_oracle(coo, x)

# 2. stats + algorithm selection (the paper's decision procedure)
stats = matrix_stats(coo)
print(f"matrix: {shape}, nnz={stats.nnz}, density={stats.density:.2e}, "
      f"max_row={stats.max_row_nnz}, var={stats.row_var:.1f}")
pick_numa = select_algorithm(stats, MachineSpec(num_devices=256),
                             num_spmvs=1000)
pick_uma = select_algorithm(stats, MachineSpec(num_devices=1),
                            num_spmvs=1000)
print(f"selector: mesh(256 devices) -> {pick_numa!r}; "
      f"single device -> {pick_uma!r}")

# 3+4. convert + multiply with every algorithm, validate
for algo, spec in ALGORITHM_SPECS.items():
    kw = dict(beta=256) if spec.blocked else {}
    if spec.scheduling == "static_rows":
        kw["num_bands"] = 8
    mat = convert(coo, algo, **kw)
    y = spmv(mat, x, impl="ref")
    err = float(jnp.max(jnp.abs(y - y_ref)))
    extra = f" storage={mat.storage_bytes() / 1e6:.2f}MB" \
        if hasattr(mat, "storage_bytes") else ""
    print(f"  {algo:8s} ok (max err {err:.2e}){extra}  [{spec.note}]")

# 5. SELL-C-σ + its Pallas kernel (interpret mode on CPU); SpMV is SpMM's
#    k = 1 column
sc = coo_to_sellcs(coo)
print(f"sellcs: {sc.num_slices} slices of {sc.chunk} rows, "
      f"{sc.data.shape[0]} width-rows")
y_k = spmm(sc, x, impl="pallas_interpret")
print(f"pallas sellcs spmv max err: "
      f"{float(jnp.max(jnp.abs(y_k - y_ref))):.2e}")
print("quickstart OK")
