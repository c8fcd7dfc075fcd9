"""The 1-D mesh and PartitionSpec helpers of the sharded SpMM executor.

``core/spmm.prepare_sharded`` lays per-shard plan leaves along a leading
mesh axis and ``exec/pipeline.py`` shards RHS columns along a trailing one;
both build their specs here.  ``make_spmm_mesh`` is a function (not a
module constant) so importing never touches jax device state.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P


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
    return jax.make_mesh((n,), (axis_name,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the sharded SpMM
    bodies wrap pallas_call, which has no replication rule."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_spec(rank: int, pos: int, axis: Optional[str]) -> P:
    """Rank-``rank`` PartitionSpec with ``axis`` at dimension ``pos``."""
    dims: list = [None] * rank
    dims[pos] = axis
    return P(*dims)


def leading_axis_spec(rank: int, axis: Optional[str]) -> P:
    return axis_spec(rank, 0, axis)


def trailing_axis_spec(rank: int, axis: Optional[str]) -> P:
    return axis_spec(rank, rank - 1, axis)


def replicated_spec(rank: int) -> P:
    return P(*([None] * rank))
