"""Collect the SDDMM benchmark record for the CI regression gate.

Measures one fused ``execute_sddmm`` dispatch — pattern-sampled ``X @ Y``
scores over the prepared plan, the first step of the GAT serving cycle —
per dataset, plus the same dense-matmul ``calib_us`` anchor the fused
gate uses.  The record shape matches ``benchmarks/check_regression.py``
(``execute.fused_us`` + ``calib_us``), so the unchanged gate script
compares the calibration-normalized geomean against
``benchmarks/baseline_sddmm_ci.json``.

    PYTHONPATH=src python -m benchmarks.collect_sddmm_json \
        --datasets cora F1 reddit --max-dim 512 --out fresh.json
"""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import spmm
from repro.exec import execute_sddmm
from .common import geomean, load_dataset, time_fn


def _calibration_us(rng: np.random.RandomState) -> float:
    x = jnp.asarray(rng.randn(512, 512).astype(np.float32))
    y = jnp.asarray(rng.randn(512, 128).astype(np.float32))
    f = jax.jit(lambda a, b: a @ b)
    return time_fn(lambda: f(x, y), repeats=5)


def main(argv=None) -> None:
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--datasets", nargs="*", default=["cora", "F1", "reddit"])
    p.add_argument("--max-dim", type=int, default=512)
    p.add_argument("--d", type=int, default=64, help="dense operand width")
    p.add_argument("--out", default="BENCH_sddmm.json")
    args = p.parse_args(argv)

    rng = np.random.RandomState(0)
    calib_us = _calibration_us(rng)

    sddmm_us = {}
    for name in args.datasets:
        rows, cols, vals, shape = load_dataset(name, max_dim=args.max_dim)
        plan = spmm.prepare(rows, cols, vals, shape, spmm.SpmmConfig())
        x = jnp.asarray(rng.randn(shape[0], args.d).astype(np.float32))
        y = jnp.asarray(rng.randn(args.d, shape[1]).astype(np.float32))
        sddmm_us[name] = time_fn(lambda: execute_sddmm(plan, x, y),
                                 repeats=4)

    record = {
        "panel": (f"{sorted(sddmm_us)} max_dim={args.max_dim} "
                  f"d={args.d}"),
        "metric": ("us per fused SDDMM dispatch: pattern-sampled X @ Y "
                   "scores (best-of-4, compile excluded)"),
        "calib_us": round(calib_us, 1),
        "execute": {
            "fused_us": {k: round(v, 1) for k, v in sddmm_us.items()},
            "geomean_us": round(geomean(sddmm_us.values()), 1),
        },
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record, indent=2))


if __name__ == "__main__":
    main()
