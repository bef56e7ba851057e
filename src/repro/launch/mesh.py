"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Production target: TPU v5e pods. Single pod = 256 chips as (data=16,
model=16); multi-pod adds a leading pure-DP "pod" axis crossing DCI.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[Sequence] = None) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, devices=devices)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    import numpy as np
    need = int(np.prod(shape))
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < need:
        raise ValueError(
            f"mesh {shape} needs {need} devices, found {len(devs)} "
            "(the dry-run must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import)")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_spmm_mesh(mesh_shape: Tuple[int, int],
                   devices: Optional[Sequence] = None) -> Mesh:
    """Mesh for the distributed SpMM schedules from a (P_data, P_model)
    factorization: 1-D ``("data",)`` when the model axis is trivial (the
    pre-2-D layout every existing call site uses), 2-D ``("data", "model")``
    otherwise — ``repro.spmm.distributed`` auto-adopts the ``model`` axis
    and shards the X/Y k-slabs across it."""
    pd, pm = int(mesh_shape[0]), int(mesh_shape[1])
    if pd < 1 or pm < 1:
        raise ValueError(f"mesh_shape must be positive, got {mesh_shape}")
    if pm == 1:
        return make_mesh((pd,), ("data",), devices=devices)
    return make_mesh((pd, pm), ("data", "model"), devices=devices)


def parse_mesh_shape(spec: str) -> Tuple[int, int]:
    """Parse a ``"Pd,Pm"`` (or ``"PdxPm"``) CLI mesh argument."""
    parts = spec.replace("x", ",").split(",")
    try:
        pd, pm = (int(p) for p in parts)
    except ValueError:
        raise SystemExit(f"--mesh must be Pd,Pm (two ints), got {spec!r}")
    if pd < 1 or pm < 1:
        raise SystemExit(f"--mesh entries must be >= 1, got {spec!r}")
    return pd, pm


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes carrying the batch dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: Mesh) -> str:
    return "model"
