"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state.  Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2 pods x
256 = 512 chips (pod, data, model) — the "pod" axis is pure DP across the
inter-pod DCN/ICI boundary.
"""
from __future__ import annotations

from typing import Dict

import jax


def _auto_axes(n_axes: int) -> Dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU tests (requires forced host device count)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         **_auto_axes(2))


def make_spmm_mesh(n_shards: int = 0, axis_name: str = "data"):
    """1-D data-parallel mesh for the sharded SpMM executor.

    ``n_shards=0`` takes every visible device.  On CPU hosts, more devices
    are forced with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (before jax initializes) — the simulated-mesh tests and the sharded
    benchmark collector both run that way.
    """
    avail = len(jax.devices())
    n = n_shards or avail
    if n > avail:
        raise ValueError(
            f"requested {n} shards but only {avail} device(s) are visible; "
            "on CPU, force more with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}"
        )
    return jax.make_mesh((n,), (axis_name,), **_auto_axes(1))
