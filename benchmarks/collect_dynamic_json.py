"""Collect the dynamic-update benchmark record for the CI regression gate.

Measures the incremental serving cycle — one retrace-free ``update_values``
over ~1% of the nonzeros followed by one ``execute`` — per dataset, plus
the same dense-matmul ``calib_us`` anchor the fused gate uses.  The record
shape matches ``benchmarks/check_regression.py`` (``execute.fused_us`` +
``calib_us``), so the unchanged gate script compares the calibration-
normalized geomean against ``benchmarks/baseline_dynamic_ci.json``.

    PYTHONPATH=src python -m benchmarks.collect_dynamic_json \
        --datasets cora F1 reddit --max-dim 512 --out fresh.json
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import spmm
from repro.dynamic import update_values
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
    p.add_argument("--n", type=int, default=64, help="dense operand width")
    p.add_argument("--out", default="BENCH_dynamic.json")
    args = p.parse_args(argv)

    rng = np.random.RandomState(0)
    calib_us = _calibration_us(rng)

    cycle_us = {}
    for name in args.datasets:
        rows, cols, vals, shape = load_dataset(name, max_dim=args.max_dim)
        cfg = spmm.SpmmConfig(impl="xla")
        b = jnp.asarray(rng.randn(shape[1], args.n).astype(np.float32))
        d = max(1, rows.size // 100)
        idx = rng.choice(rows.size, d, replace=False)
        state = {"plan": spmm.prepare(rows, cols, vals, shape, cfg)}
        jax.block_until_ready(spmm.execute(state["plan"], b))

        def cycle():
            state["plan"] = update_values(state["plan"], idx, rng.randn(d))
            return spmm.execute(state["plan"], b)

        best = float("inf")
        for _ in range(4):
            t0 = time.perf_counter()
            jax.block_until_ready(cycle())
            best = min(best, time.perf_counter() - t0)
        cycle_us[name] = best * 1e6

    record = {
        "panel": (f"{sorted(cycle_us)} max_dim={args.max_dim} "
                  f"n={args.n}"),
        "metric": ("us per dynamic serving cycle: update_values(~1% nnz) "
                   "+ execute (best-of-4, compile excluded)"),
        "calib_us": round(calib_us, 1),
        "execute": {
            "fused_us": {k: round(v, 1) for k, v in cycle_us.items()},
            "geomean_us": round(geomean(cycle_us.values()), 1),
        },
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record, indent=2))


if __name__ == "__main__":
    main()
