"""repro.spmm.operator — the stable partition-once/multiply-many handle.

Every repeated-multiply consumer in this repo (the serve batcher, the
iterative examples) used to re-spell the same dance: convert COO to a
format, maybe partition it over a mesh, close a jitted multiply over the
result, and keep the whole plan in ad-hoc locals — which made "change the
format mid-stream" (the paper's §7 break-even economics, ~472 multiplies
to amortize a conversion) impossible without tearing the caller apart.

:class:`SparseOperator` is that seam. It owns the immutable COO source and
a single *current* :class:`RealizedPlan`; ``op.matmul(X)`` multiplies with
whatever plan is installed, and ``op.swap(new_plan)`` replaces it
atomically — the plan is one immutable object read exactly once per
multiply, so a concurrent flush sees either the old plan or the new one,
never a torn mix. ``op.realize(spec)`` builds a plan *without* installing
it, which is what the serve migration controller runs in its background
thread before swapping between flushes.

Convert-time artifacts are cached per operator (the SELL-C-σ stream and
each (schedule, P_data, compact_x) base partition), so a swap that only
changes the psum pipelining depth reuses the existing partition through
:func:`repro.spmm.distributed.rechunk_sellcs` instead of repartitioning.
"""
from __future__ import annotations

import hashlib
import threading
import time
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.formats import COO
from repro.core.selector import (MachineSpec, MatrixStats, PlanSpec,
                                 _matrix_bytes_est, matrix_stats, select,
                                 select_distributed)


def coo_fingerprint(coo: COO) -> str:
    """Stable content hash of a COO matrix — the fleet plan-cache key.

    The nonzeros are hashed in the canonical ``(rows, cols, values)``
    lexicographic order, so any permutation of the same triplet stream
    (including duplicate (row, col) entries, which SpMM sums — order
    irrelevant) maps to the same fingerprint, while any value or pattern
    change maps elsewhere. Shape and value dtype are part of the hash: a
    float64 copy of a float32 matrix is a different operator."""
    rows = np.asarray(coo.rows, np.int64)
    cols = np.asarray(coo.cols, np.int64)
    vals = np.asarray(coo.data)
    order = np.lexsort((vals, cols, rows))
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((tuple(int(s) for s in coo.shape),
                   str(vals.dtype))).encode())
    h.update(rows[order].tobytes())
    h.update(cols[order].tobytes())
    h.update(vals[order].tobytes())
    return h.hexdigest()


def _pick_chunk(m: int, num_devices: int, default: int = 128) -> int:
    """Largest power-of-two slice height <= default that still gives every
    device at least one slice to own (small demo matrices on big meshes)."""
    c = default
    while c > 8 and -(-m // c) < num_devices:
        c //= 2
    return c


class RealizedPlan(NamedTuple):
    """One executable multiply plan: the resolved :class:`PlanSpec`, the
    execution-side matrix (a partitioned ``ShardedSellCS`` on a mesh, the
    converted single-device format otherwise), the jitted multiply
    closure, and everything observability needs to price it (the roofline
    ``model_s(k)`` closure, the compact-gather ``n_touched``, the measured
    build seconds). Immutable — :meth:`SparseOperator.swap` installs a
    whole plan in one reference assignment."""
    spec: PlanSpec               # fully resolved (no None knobs on a mesh)
    label: str                   # e.g. "sellcs+merge@4x2mesh/chunks=2[pallas]"
    matrix: object               # what the multiply executes against
    local_matrix: object         # single-device form for sequential
                                 #   baselines (the pre-partition stream
                                 #   on a mesh; == matrix off one)
    multiply: Callable           # X -> Y, jitted where distributed
    eager: Optional[Callable]    # un-jitted X -> Y (mesh only) — the
                                 #   phase-profile pass --metrics runs
    impl: str                    # the impl the forward multiply runs
                                 #   ("ref"/"pallas"/"pallas_interpret"),
                                 #   resolved per format; also in label
    n_touched: Optional[float]   # mean touched columns per shard
                                 #   (compact_x plans only)
    model_s: Callable            # k -> roofline seconds for one k-RHS
                                 #   flush under exactly these knobs
    build_s: float               # measured convert+partition seconds —
                                 #   the numerator of the live break-even
    multiply_t: Optional[Callable] = None
                                 # X -> A^T X over the SAME plan artifacts
                                 #   (jitted where distributed); every plan
                                 #   carries one — rmatmul never builds a
                                 #   second partition
    eager_t: Optional[Callable] = None
                                 # un-jitted transpose twin of ``eager``

    def labels(self, **extra) -> Dict[str, str]:
        """Canonical residual-ledger labels for this plan's knobs; the
        single-device case keeps the historical ``schedule=single``
        stamping of the serve metrics pass."""
        from repro.obs.residuals import choice_labels
        sp = self.spec
        if (sp.num_devices or 1) > 1:
            return choice_labels(schedule=sp.schedule,
                                 num_chunks=sp.num_chunks or 1,
                                 mesh_shape=sp.mesh_shape,
                                 compact_x=bool(sp.compact_x),
                                 structure=sp.structure or "general",
                                 gather=((sp.gather or "upfront")
                                         if sp.compact_x else None),
                                 **extra)
        return choice_labels(schedule="single", num_chunks=1,
                             mesh_shape=(1, 1), compact_x=None, **extra)


class OperatorStats:
    """Mutable multiply/swap accounting, updated under the operator lock.
    ``multiplies`` counts SpMV-equivalents (served columns), the unit of
    the paper's "472 multiplications" break-even. The build counters
    (``sellcs_builds``/``partition_builds``: conversions and device deals
    actually paid; ``plan_cache_hits``: artifact-cache reuses) are what
    the fleet tests assert on — a returning tenant's operator must show
    zero builds."""
    __slots__ = ("multiplies", "calls", "swaps", "last_swap_unix_s",
                 "sellcs_builds", "partition_builds", "plan_cache_hits")

    def __init__(self):
        self.multiplies = 0
        self.calls = 0
        self.swaps = 0
        self.last_swap_unix_s: Optional[float] = None
        self.sellcs_builds = 0
        self.partition_builds = 0
        self.plan_cache_hits = 0

    def __repr__(self):
        return (f"OperatorStats(multiplies={self.multiplies}, "
                f"calls={self.calls}, swaps={self.swaps}, "
                f"sellcs_builds={self.sellcs_builds}, "
                f"partition_builds={self.partition_builds}, "
                f"plan_cache_hits={self.plan_cache_hits})")


class _PlanCache:
    """Per-operator convert-time artifact reuse across swaps: the
    SELL-C-σ stream per slice height, and each base partition per
    (schedule, P_data, compact_x) — a chunks-only swap then pays one span
    re-deal (``rechunk_sellcs``), not a repartition."""

    def __init__(self):
        # sellcs keyed by (slice height, structure); partitions by
        # (schedule, P_data, compact_x, structure)
        self.sellcs: Dict[Tuple[int, str], object] = {}
        self.partitions: Dict[Tuple[str, int, bool, str], object] = {}


class SparseOperator:
    """Partition-once / multiply-many handle over one sparse matrix.

    ::

        op = SparseOperator.from_coo(coo, PlanSpec(num_devices=8))
        y = op.matmul(x)          # or: op @ x
        op.swap(PlanSpec(num_devices=8, num_chunks=4))   # atomic
        op.plan, op.spec, op.stats, op.shape

    ``matmul`` reads the current plan exactly once, so a ``swap`` from
    another thread (the serve migration controller's background build)
    can never interleave half-updated state into a flush; pre- and
    post-swap results agree with the oracle bitwise because every plan
    multiplies the same COO nonzeros.
    """

    def __init__(self, coo: COO, plan=None, *,
                 impl: str = "auto", k_hint: int = 32,
                 num_spmvs: int = 1000, feedback=None,
                 cache: Optional[_PlanCache] = None):
        self._coo = coo
        self._mstats = matrix_stats(coo)
        self._impl = impl
        self._k_hint = max(int(k_hint), 1)
        self._num_spmvs = num_spmvs
        self._cache = cache if cache is not None else _PlanCache()
        self._lock = threading.Lock()
        self._build_lock = threading.Lock()
        self.stats = OperatorStats()
        if isinstance(plan, RealizedPlan):
            # fleet plan-cache hit: a returning tenant installs the cached
            # plan directly — no conversion, no partition, no selection
            self._plan = plan
        else:
            self._plan = self.realize(plan or PlanSpec(),
                                      feedback=feedback)

    @classmethod
    def from_coo(cls, coo: COO, plan=None, *,
                 impl: str = "auto", k_hint: int = 32,
                 num_spmvs: int = 1000, feedback=None,
                 cache: Optional[_PlanCache] = None) -> "SparseOperator":
        """Build the handle and realize its initial plan. ``plan`` is a
        :class:`PlanSpec` (None = single-device, format chosen by
        ``core.select`` for ``k_hint`` right-hand sides amortized over
        ``num_spmvs`` multiplies) or an already-built
        :class:`RealizedPlan`, which is installed as-is (the fleet's
        returning-tenant path). ``cache`` shares convert-time artifacts
        (SELL-C-σ stream, base partitions) across operators of the same
        matrix."""
        return cls(coo, plan, impl=impl, k_hint=k_hint,
                   num_spmvs=num_spmvs, feedback=feedback, cache=cache)

    # -- read side ---------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        return self._coo.shape

    @property
    def matrix_stats(self) -> MatrixStats:
        return self._mstats

    @property
    def plan(self) -> RealizedPlan:
        return self._plan

    @property
    def spec(self) -> PlanSpec:
        return self._plan.spec

    def matmul(self, x: jax.Array) -> jax.Array:
        """``Y = A @ X`` under the currently installed plan. The plan
        reference is read once — concurrent swaps are invisible within a
        single multiply."""
        rp = self._plan
        y = rp.multiply(x)
        k = 1 if getattr(x, "ndim", 1) == 1 else int(x.shape[1])
        with self._lock:
            self.stats.calls += 1
            self.stats.multiplies += k
        return y

    __matmul__ = matmul

    def rmatmul(self, x: jax.Array) -> jax.Array:
        """``Y = A^T X`` (``X: [m, k]``, ``Y: [n, k]``) under the SAME
        installed plan: both directions share one set of convert-time
        artifacts — the transpose multiplies the stored stream with the
        roles of the row permutation and the column scatter exchanged, so
        no second partition exists to drift out of sync with the forward
        one. Counts toward the same break-even ``multiplies``."""
        rp = self._plan
        if rp.multiply_t is None:
            raise ValueError(
                f"plan {rp.label!r} carries no transpose multiply; "
                "re-realize it (pre-transpose plans cannot rmatmul)")
        y = rp.multiply_t(x)
        k = 1 if getattr(x, "ndim", 1) == 1 else int(x.shape[1])
        with self._lock:
            self.stats.calls += 1
            self.stats.multiplies += k
        return y

    @property
    def T(self) -> "TransposedOperator":
        """Transpose view: ``op.T @ x`` is ``op.rmatmul(x)``. A view, not
        a copy — it reads the operator's current plan at each multiply, so
        swaps show through and ``op.T.T is op``."""
        return TransposedOperator(self)

    def storage_bytes(self) -> int:
        """Execution-side footprint of the installed plan — what the
        multiply actually keeps resident (the partitioned
        ``ShardedSellCS`` on a mesh, the converted format off one; the
        COO triplet estimate only for formats that report no
        ``storage_bytes``). The fleet's ``max_bytes`` budget sums this."""
        rp = self._plan
        for mat in (rp.matrix, rp.local_matrix):
            fn = getattr(mat, "storage_bytes", None)
            if fn is not None:
                return int(fn())
        coo = self._coo
        return int(8 * np.asarray(coo.rows).size
                   + np.asarray(coo.data).nbytes)

    # -- write side --------------------------------------------------------
    def realize(self, spec: PlanSpec, feedback=None) -> RealizedPlan:
        """Build an executable plan for ``spec`` WITHOUT installing it —
        safe to call from a background thread while ``matmul`` traffic
        runs on the current plan. ``feedback`` (a ``ResidualLedger``)
        reaches ``select_distributed`` so unpinned knobs are chosen with
        ledger-corrected scores."""
        with self._build_lock:
            return _realize_plan(self._coo, self._mstats, spec,
                                 impl=self._impl, k_hint=self._k_hint,
                                 num_spmvs=self._num_spmvs,
                                 feedback=feedback, cache=self._cache,
                                 op_stats=self.stats)

    def swap(self, new_plan, feedback=None) -> RealizedPlan:
        """Atomically install ``new_plan`` (a :class:`RealizedPlan`, or a
        :class:`PlanSpec` realized on the spot) as the current plan; the
        next ``matmul`` call uses it. Returns the installed plan."""
        if isinstance(new_plan, PlanSpec):
            new_plan = self.realize(new_plan, feedback=feedback)
        if not isinstance(new_plan, RealizedPlan):
            raise TypeError("swap takes a RealizedPlan or PlanSpec, got "
                            f"{type(new_plan).__name__}")
        with self._lock:
            self._plan = new_plan
            self.stats.swaps += 1
            self.stats.last_swap_unix_s = time.time()
        return new_plan

    def shrink_to(self, devices: Sequence, *,
                  num_chunks: Optional[int] = None) -> RealizedPlan:
        """Device-loss path: re-deal the current distributed plan's
        width-row stream over ``devices`` (the survivors) and atomically
        install the shrunken plan. The global stream is reconstructed from
        the existing shards (:func:`repro.spmm.distributed.redeal_sellcs`)
        — no σ-sort, no COO→SELL-C-σ conversion — and the mesh is rebuilt
        with the :func:`repro.runtime.elastic.largest_feasible_mesh`
        policy: the model axis keeps its width, the loss is absorbed on
        the data axis. Returns the installed plan."""
        from repro.launch.mesh import make_spmm_mesh
        from repro.roofline import spmm_distributed_time
        from repro.runtime.elastic import largest_feasible_mesh
        from repro.spmm.distributed import redeal_sellcs
        rp = self._plan
        sp = rp.spec
        if (sp.num_devices or 1) <= 1:
            raise ValueError(
                "shrink_to needs a distributed plan; the current plan is "
                f"single-device ({rp.label!r})")
        _, pm = sp.mesh_shape
        pd, pm = largest_feasible_mesh(len(devices), pm)
        nc = int(num_chunks) if num_chunks is not None else (sp.num_chunks
                                                            or 1)
        t0 = time.perf_counter()
        with self._build_lock:
            sharded = redeal_sellcs(rp.matrix, pd, num_chunks=nc)
            mesh = make_spmm_mesh((pd, pm), devices=list(devices)[:pd * pm])
            compact = bool(sp.compact_x)
            # survivors' partition replaces the stale artifact so a later
            # chunks-only swap re-deals from the live device count
            self._cache.partitions[(sp.schedule, pd, compact,
                                    sp.structure or "general")] = sharded
            with self._lock:
                self.stats.partition_builds += 1
            plan = _mesh_plan(sharded, rp.local_matrix, self._mstats, mesh,
                              schedule=sp.schedule, chunks=nc, pd=pd, pm=pm,
                              compact=compact, impl_r=rp.impl,
                              time_fn=spmm_distributed_time, t0=t0,
                              gather=((sp.gather or "upfront") if compact
                                      else "upfront"))
        return self.swap(plan)


class TransposedOperator:
    """Zero-copy transpose view over a :class:`SparseOperator` — the
    ``op.T`` surface. Shares the parent's plan (and therefore its swap
    atomicity and break-even accounting); only the multiply direction and
    the reported shape flip."""

    def __init__(self, base: SparseOperator):
        self._base = base

    @property
    def shape(self) -> Tuple[int, int]:
        m, n = self._base.shape
        return n, m

    @property
    def plan(self) -> RealizedPlan:
        return self._base.plan

    @property
    def T(self) -> SparseOperator:
        return self._base

    def matmul(self, x: jax.Array) -> jax.Array:
        return self._base.rmatmul(x)

    __matmul__ = matmul

    def rmatmul(self, x: jax.Array) -> jax.Array:
        return self._base.matmul(x)


def sparse_matmul(op: SparseOperator, x: jax.Array) -> jax.Array:
    """Differentiable ``Y = op @ x``: the forward multiply runs through the
    operator's realized plan and the backward cotangent through the SAME
    plan's transpose multiply (``d loss/d x = op.rmatmul(g)``, i.e.
    ``A^T g`` over the one stored stream). This is the training-surface
    entry point — drop a fixed sparse mixing matrix inside a loss and
    ``jax.grad`` flows through both ops of the operator."""

    @jax.custom_vjp
    def f(x):
        return op.matmul(x)

    def fwd(x):
        return op.matmul(x), None

    def bwd(_, g):
        return (op.rmatmul(g),)

    f.defvjp(fwd, bwd)
    return f(x)


class _JitOver:
    """``X -> fn(matrix, X)``, jitted with the matrix's arrays passed as
    arguments. Closed over, they would be captured as constants: at
    deployment size gigabytes embedded in every compiled flush (and a
    compile that takes minutes)."""

    def __init__(self, fn, matrix):
        leaves, treedef = jax.tree_util.tree_flatten(matrix)
        where = [i for i, leaf in enumerate(leaves)
                 if isinstance(leaf, jax.Array)]

        def call(arrays, X):
            full = list(leaves)
            for i, a in zip(where, arrays):
                full[i] = a
            return fn(jax.tree_util.tree_unflatten(treedef, full), X)

        self._jitted = jax.jit(call)
        self._arrays = [leaves[i] for i in where]

    def __call__(self, X):
        return self._jitted(self._arrays, X)

    def lower(self, X):
        return self._jitted.lower(self._arrays, X)


def _realize_plan(coo: COO, stats: MatrixStats, spec: PlanSpec, *,
                  impl: str, k_hint: int, num_spmvs: int, feedback=None,
                  cache: Optional[_PlanCache] = None,
                  op_stats: Optional[OperatorStats] = None) -> RealizedPlan:
    from repro.roofline import spmm_distributed_time
    spec = spec.canonical()
    cache = cache or _PlanCache()
    t0 = time.perf_counter()
    if spec.num_devices == 1:
        return _realize_single(coo, stats, spec, impl=impl, k_hint=k_hint,
                               num_spmvs=num_spmvs, t0=t0,
                               time_fn=spmm_distributed_time)
    return _realize_mesh(coo, stats, spec, impl=impl, k_hint=k_hint,
                         num_spmvs=num_spmvs, feedback=feedback,
                         cache=cache, t0=t0,
                         time_fn=spmm_distributed_time,
                         op_stats=op_stats)


def _realize_single(coo, stats, spec, *, impl, k_hint, num_spmvs, t0,
                    time_fn):
    from repro.core.convert import convert
    import dataclasses
    algo = spec.algorithm or select(stats, MachineSpec(1),
                                    num_spmvs=num_spmvs, k=k_hint)
    structure = spec.structure or "general"
    if structure == "symmetric" and algo != "sellcs":
        raise ValueError(
            "structure='symmetric' (one-triangle storage) is executable "
            f"only on the SELL-C-σ stream, not {algo!r}")
    if algo == "sellcs":
        from repro.spmm import coo_to_sellcs
        # σ = m: the DMA-gather kernel draws no locality from σ; least
        # padding. Split rows: a hub row would pad its slice C-fold
        mat = coo_to_sellcs(coo, sigma=coo.shape[0], structure=structure,
                            split_long_rows=True)
    else:
        mat = convert(coo, algo)
    mat_bytes = _matrix_bytes_est(algo, stats)
    from repro.spmm.kernels import resolve_impl
    impl_r = resolve_impl(impl, mat)

    def multiply(X):
        from repro.spmm import spmm
        return spmm(mat, X, impl=impl_r)

    from repro.spmm.sellcs import SellCS as _SellCS
    if isinstance(mat, (_SellCS, COO)):
        impl_t = resolve_impl(impl, mat, "T")

        def multiply_t(X):
            from repro.spmm import spmm
            return spmm(mat, X, impl=impl_t, op="T")
    else:
        # formats without a transpose path fall back to the immutable COO
        # source the operator already owns — correct, just unamortized
        def multiply_t(X):
            from repro.spmm.reference import spmm_ref
            return spmm_ref(coo, X, op="T")

    def model_s(k):
        # the distributed model at P=1 degenerates to the plain
        # streaming-bytes roofline for this format
        return time_fn(stats.m, stats.n, k, 1, "row",
                       matrix_bytes=mat_bytes,
                       max_row_nnz=stats.max_row_nnz, nnz=stats.nnz,
                       structure=structure)

    resolved = dataclasses.replace(spec, algorithm=algo,
                                   structure=structure)
    return RealizedPlan(resolved, f"{algo}[{impl_r}]", mat, mat, multiply,
                        None, impl_r, None, model_s,
                        time.perf_counter() - t0,
                        multiply_t=multiply_t)


def _realize_mesh(coo, stats, spec, *, impl, k_hint, num_spmvs, feedback,
                  cache, t0, time_fn, op_stats=None):
    import dataclasses
    from repro.launch.mesh import make_spmm_mesh
    from repro.spmm import coo_to_sellcs
    from repro.spmm.distributed import (partition_sellcs_nnz,
                                        partition_sellcs_rows,
                                        rechunk_sellcs)
    total = spec.num_devices
    ndev = len(jax.devices())
    if ndev < total:
        raise RuntimeError(
            f"the mesh needs {total} devices but jax sees only {ndev}; on "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{total} before launching")
    if spec.algorithm not in (None, "sellcs"):
        raise ValueError(
            f"algorithm {spec.algorithm!r} cannot run on a mesh: the "
            "distributed multiply executes the SELL-C-σ slice stream "
            "(repro.spmm.distributed)")
    # joint (schedule × chunks × mesh × gather) choice under the spec's
    # pins; conversion cost is shared by every candidate so it drops out
    # of the argmin — the old serve grid-min, through one entry point
    choice = select_distributed(
        stats, k=k_hint, num_spmvs=num_spmvs,
        spec=dataclasses.replace(spec, algorithm="sellcs"),
        feedback=feedback)
    schedule, chunks = choice.schedule, choice.num_chunks
    (pd, pm), compact = choice.mesh_shape, choice.compact_x
    structure = choice.structure
    gather = choice.gather if compact else "upfront"
    mesh = make_spmm_mesh((pd, pm))
    c = _pick_chunk(stats.m, pd)
    skey = (c, structure)
    sc = cache.sellcs.get(skey)
    if sc is None:
        sc = cache.sellcs.setdefault(
            skey, coo_to_sellcs(coo, c=c, structure=structure))
        if op_stats is not None:
            op_stats.sellcs_builds += 1
    elif op_stats is not None:
        op_stats.plan_cache_hits += 1
    from repro.spmm.kernels import resolve_impl
    impl_r = resolve_impl(impl, sc)
    key = (schedule, pd, compact, structure)
    base = cache.partitions.get(key)
    if base is None:
        part = (partition_sellcs_rows if schedule == "row"
                else partition_sellcs_nnz)
        base = cache.partitions.setdefault(
            key, part(sc, pd, compact_x=compact))
        if op_stats is not None:
            op_stats.partition_builds += 1
    elif op_stats is not None:
        op_stats.plan_cache_hits += 1
    if schedule == "row":
        sharded = base
    else:
        # partition reuse across swaps: only the span plan is re-baked
        sharded = rechunk_sellcs(base, chunks)
    return _mesh_plan(sharded, sc, stats, mesh, schedule=schedule,
                      chunks=chunks, pd=pd, pm=pm, compact=compact,
                      impl_r=impl_r, time_fn=time_fn, t0=t0, gather=gather)


def _mesh_plan(sharded, sc, stats, mesh, *, schedule, chunks, pd, pm,
               compact, impl_r, time_fn, t0, gather="upfront"):
    """Close a :class:`RealizedPlan` over an already-partitioned stream —
    the shared tail of the convert-time realize and the device-loss
    ``shrink_to`` re-deal (which brings its own survivors' mesh)."""
    from repro.spmm.distributed import (spmm_merge_distributed,
                                        spmm_row_distributed)
    structure = getattr(sharded, "structure", "general")
    gx = gather if compact else None
    if schedule == "row":
        mult = lambda sh, X, op: spmm_row_distributed(
            sh, X, mesh, impl=impl_r, op=op, gather=gx)
    else:
        mult = lambda sh, X, op: spmm_merge_distributed(
            sh, X, mesh, impl=impl_r, num_chunks=chunks, op=op, gather=gx)
    eager = lambda X: mult(sharded, X, "N")
    eager_t = lambda X: mult(sharded, X, "T")
    # jitted so repeated flushes of one batch shape do not retrace the
    # shard_map body
    jitted = _JitOver(lambda sh, X: mult(sh, X, "N"), sharded)
    jitted_t = _JitOver(lambda sh, X: mult(sh, X, "T"), sharded)
    mesh_tag = f"{pd}x{pm}mesh" if pm > 1 else f"{pd}dev"
    cx_tag = "/cx=on" if compact else ""
    gx_tag = f"/gx={gather}" if compact and gather != "upfront" else ""
    sym_tag = "/sym" if structure == "symmetric" else ""
    if schedule == "row":
        label = f"sellcs+row@{mesh_tag}{cx_tag}{gx_tag}{sym_tag}"
    else:
        label = (f"sellcs+merge@{mesh_tag}/chunks={chunks}"
                 f"{cx_tag}{gx_tag}{sym_tag}")
    label += f"[{impl_r}]"
    # price the gather with the map the multiply EXECUTES: the chunked
    # merge gathers through the chunk plan's re-dealt map, not the base
    # partition's
    n_touched = None
    if compact:
        nt_src = (sharded.chunk_plan[3]
                  if sharded.chunk_plan is not None else sharded.n_touched)
        n_touched = float(np.mean(np.asarray(nt_src)))
    sellcs_bytes = _matrix_bytes_est("sellcs", stats)

    def model_s(k):
        return time_fn(stats.m, stats.n, k, pd, schedule,
                       matrix_bytes=sellcs_bytes,
                       max_row_nnz=stats.max_row_nnz, num_chunks=chunks,
                       model_devices=pm, compact_x=compact,
                       n_touched=n_touched, nnz=stats.nnz,
                       structure=structure,
                       gather=gather if compact else "upfront")

    resolved = PlanSpec(num_devices=pd * pm, mesh_shape=(pd, pm),
                        num_chunks=chunks, compact_x=compact,
                        schedule=schedule, algorithm="sellcs",
                        structure=structure,
                        gather=gather if compact else None)
    return RealizedPlan(resolved, label, sharded, sc, jitted, eager,
                        impl_r, n_touched, model_s,
                        time.perf_counter() - t0,
                        multiply_t=jitted_t, eager_t=eager_t)


__all__ = ["SparseOperator", "TransposedOperator", "RealizedPlan",
           "OperatorStats", "PlanSpec", "coo_fingerprint", "sparse_matmul"]
