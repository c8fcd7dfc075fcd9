"""Sharded-executor scaling: execute vs execute_sharded across mesh sizes.

Emits one CSV row per (dataset, n_shards) with the sharded us_per_call and
the ratio to single-device ``execute``.  On a CPU host the mesh devices are
XLA-forced host "devices", so the ratio measures coordination + dispatch
overhead rather than real scaling — run with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python -m benchmarks.run bench_sharded

(without the flag only 1-way meshes are benched).  Real-accelerator meshes
need no flag.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spmm
from repro.distributed.sharding import make_spmm_mesh

from .common import emit, load_dataset, time_fn

PANEL = ["cora", "F1", "reddit"]
N = 128


def run():
    rng = np.random.RandomState(0)
    n_dev = len(jax.devices())
    shard_counts = [n for n in (1, 2, 4, 8) if n <= n_dev]
    for name in PANEL:
        rows, cols, vals, shape = load_dataset(name, max_dim=512)
        b = jnp.asarray(rng.randn(shape[1], N).astype(np.float32))
        cfg = spmm.SpmmConfig(impl="xla")
        plan = spmm.prepare(rows, cols, vals, shape, cfg)
        single_us = time_fn(lambda: spmm.execute(plan, b))
        emit(f"{name}/single", single_us, "ratio=1.00")
        for nsh in shard_counts:
            splan = spmm.prepare_sharded(
                rows, cols, vals, shape, make_spmm_mesh(nsh), cfg,
                shard_axis="rows",
            )
            us = time_fn(lambda: spmm.execute_sharded(splan, b))
            emit(
                f"{name}/shards{nsh}", us,
                f"ratio={us / single_us:.2f},"
                f"imb={splan.stats_dict['rows_imbalance']:.2f}",
            )


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
