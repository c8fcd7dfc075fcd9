"""Collect the fused-executor benchmark record (BENCH_fused_executor.json).

Measures the current tree's end-to-end ``execute`` us_per_call on a dataset
panel plus host ``prepare`` time on the preprocessing panel, and writes them
next to the frozen seed numbers (measured on the same machine at the seed
commit) with per-dataset and geomean speedups.  Seed comparisons are only
emitted for the canonical full panel (``--max-dim 2048``); smaller panels —
e.g. the CI regression gate's — record absolute numbers only.

The record also carries ``calib_us``, the time of a fixed dense matmul on
the same process/backend: dividing exec times by it gives a machine-portable
number, which is what benchmarks/check_regression.py gates on.

    PYTHONPATH=src python -m benchmarks.collect_fused_json
    PYTHONPATH=src python -m benchmarks.collect_fused_json \
        --datasets cora F1 reddit --max-dim 512 --skip-prepare --out ci.json
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import spmm
from .common import BENCH_DATASETS, geomean, load_dataset, time_fn

# seed-commit numbers, best-of-3 (same harness as bench_overall /
# bench_preprocess) on this machine
SEED_EXEC_US = {
    "cora": 8183.9, "wiki-RfA": 49303.3, "ogbn-arxiv": 17504.8,
    "pattern1": 52329.0, "human_gene1": 110029.1, "F1": 9313.8,
    "mouse_gene": 103260.0, "reddit": 14549.0,
}
SEED_PREPARE_US = {"cora": 3311.2, "ogbn-arxiv": 11473.4, "reddit": 36049.6}
PREP_PANEL = (("cora", 2048), ("ogbn-arxiv", 2048), ("reddit", 4096))
SEED_DIM = 2048
N = 128


def _calibration_us(rng: np.random.RandomState) -> float:
    """Fixed-size dense matmul: the machine-speed anchor for the gate."""
    x = jnp.asarray(rng.randn(512, 512).astype(np.float32))
    y = jnp.asarray(rng.randn(512, 128).astype(np.float32))
    f = jax.jit(lambda a, b: a @ b)
    return time_fn(lambda: f(x, y), repeats=5)


def main(argv=None) -> None:
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--datasets", nargs="*", default=list(BENCH_DATASETS))
    p.add_argument("--max-dim", type=int, default=SEED_DIM)
    p.add_argument("--n", type=int, default=N, help="dense operand width")
    p.add_argument("--out", default="BENCH_fused_executor.json")
    p.add_argument("--skip-prepare", action="store_true",
                   help="skip the host prepare() timing panel")
    p.add_argument("--telemetry-out", default=None, metavar="PATH",
                   help="run the exec panel with SpmmConfig.telemetry "
                        "enabled and dump the repro.obs snapshot (metrics "
                        "+ traces + phase spans) as JSON")
    args = p.parse_args(argv)
    telemetry = args.telemetry_out is not None

    rng = np.random.RandomState(0)
    calib_us = _calibration_us(rng)

    exec_after = {}
    for name in args.datasets:
        rows, cols, vals, shape = load_dataset(name, max_dim=args.max_dim)
        b = jnp.asarray(rng.randn(shape[1], args.n).astype(np.float32))
        plan = spmm.prepare(rows, cols, vals, shape,
                            spmm.SpmmConfig(impl="xla",
                                            telemetry=telemetry))
        exec_after[name] = time_fn(lambda: spmm.execute(plan, b))

    record = {
        "panel": (f"{sorted(exec_after)} max_dim={args.max_dim} "
                  f"n={args.n}"),
        "metric": "us_per_call (best-of-3 wall clock, compile excluded)",
        "calib_us": round(calib_us, 1),
        "execute": {
            "fused_us": {k: round(v, 1) for k, v in exec_after.items()},
            "geomean_us": round(geomean(exec_after.values()), 1),
        },
    }

    is_seed_panel = (
        args.max_dim == SEED_DIM and args.n == N
        and all(k in SEED_EXEC_US for k in exec_after)
    )
    if is_seed_panel:
        speedups = {k: SEED_EXEC_US[k] / exec_after[k] for k in exec_after}
        record["execute"]["seed_us"] = {
            k: SEED_EXEC_US[k] for k in exec_after
        }
        record["execute"]["speedup"] = {
            k: round(v, 2) for k, v in speedups.items()
        }
        record["execute"]["geomean_speedup"] = round(
            geomean(speedups.values()), 2
        )

    if not args.skip_prepare:
        prep_after = {}
        for name, dim in PREP_PANEL:
            rows, cols, vals, shape = load_dataset(name, max_dim=dim)
            best = float("inf")
            for _ in range(7):
                t0 = time.perf_counter()
                spmm.prepare(rows, cols, vals, shape,
                             spmm.SpmmConfig(impl="xla"))
                best = min(best, time.perf_counter() - t0)
            prep_after[name] = best * 1e6
        prep_speedups = {
            k: SEED_PREPARE_US[k] / prep_after[k] for k in prep_after
        }
        record["prepare"] = {
            "seed_us": SEED_PREPARE_US,
            "new_us": {k: round(v, 1) for k, v in prep_after.items()},
            "speedup": {k: round(v, 2) for k, v in prep_speedups.items()},
        }

    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record, indent=2))

    if telemetry:
        import repro.obs as obs
        snap = obs.snapshot()
        snap["prometheus"] = obs.prometheus_text()
        with open(args.telemetry_out, "w") as f:
            json.dump(snap, f, indent=2)


if __name__ == "__main__":
    main()
