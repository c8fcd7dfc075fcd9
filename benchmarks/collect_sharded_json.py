"""Collect the sharded-executor benchmark record for the CI regression gate.

Same record shape as ``collect_fused_json`` (``execute.fused_us`` per
dataset plus the ``calib_us`` dense-matmul machine anchor), measured through
``execute_sharded`` on a forced-host-device mesh, so
``benchmarks/check_regression.py`` gates it unchanged against
``benchmarks/baseline_sharded_ci.json``.

``main`` forces the host device count itself (before jax creates its
backend), so it runs identically on a laptop and in CI:

    PYTHONPATH=src python -m benchmarks.collect_sharded_json \
        --datasets cora F1 reddit --max-dim 512 --out sharded_fresh.json
"""
import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import spmm
from repro.distributed.sharding import make_spmm_mesh
from repro.hostdevices import force_host_device_count

from .common import geomean, load_dataset, time_fn

N_FORCED_DEVICES = 8


def _calibration_us(rng: np.random.RandomState) -> float:
    """Fixed-size dense matmul: the machine-speed anchor for the gate."""
    x = jnp.asarray(rng.randn(512, 512).astype(np.float32))
    y = jnp.asarray(rng.randn(512, 128).astype(np.float32))
    f = jax.jit(lambda a, b: a @ b)
    return time_fn(lambda: f(x, y), repeats=5)


def main(argv=None) -> None:
    force_host_device_count(os.environ, N_FORCED_DEVICES)
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--datasets", nargs="*", default=["cora", "F1", "reddit"])
    p.add_argument("--max-dim", type=int, default=512)
    p.add_argument("--n", type=int, default=128, help="dense operand width")
    p.add_argument("--n-shards", type=int, default=N_FORCED_DEVICES)
    p.add_argument("--out", default="BENCH_sharded_executor.json")
    args = p.parse_args(argv)

    n_dev = len(jax.devices())
    n_shards = min(args.n_shards, n_dev)
    rng = np.random.RandomState(0)
    calib_us = _calibration_us(rng)
    mesh = make_spmm_mesh(n_shards)

    exec_us = {}
    imbalance = {}
    for name in args.datasets:
        rows, cols, vals, shape = load_dataset(name, max_dim=args.max_dim)
        b = jnp.asarray(rng.randn(shape[1], args.n).astype(np.float32))
        splan = spmm.prepare_sharded(
            rows, cols, vals, shape, mesh, spmm.SpmmConfig(impl="xla"),
            shard_axis="rows",
        )
        exec_us[name] = time_fn(lambda: spmm.execute_sharded(splan, b))
        imbalance[name] = splan.stats_dict["rows_imbalance"]

    record = {
        "panel": (f"{sorted(exec_us)} max_dim={args.max_dim} n={args.n} "
                  f"sharded rows x{n_shards}"),
        "metric": "us_per_call (best-of-3 wall clock, compile excluded)",
        "calib_us": round(calib_us, 1),
        "n_shards": n_shards,
        "shard_axis": "rows",
        "rows_imbalance": {k: round(v, 3) for k, v in imbalance.items()},
        "execute": {
            "fused_us": {k: round(v, 1) for k, v in exec_us.items()},
            "geomean_us": round(geomean(exec_us.values()), 1),
        },
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record, indent=2))


if __name__ == "__main__":
    main()
