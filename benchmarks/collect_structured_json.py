"""Collect the structured-sparsity fast-lane record (BENCH_structured.json).

Runs the DLMC-style pruned-DNN panel twice — once through the structured
fast lane (``prepare`` auto-detects the N:M pattern and packs the matrix
path's payload) and once with the same plan pinned to the general lane
(``structure_hint="general"``) — and records both, plus the
calibration-normalized margin between them.  ``bn`` is matched to the
operand width so neither lane pays column padding.

The record is schema-compatible with ``benchmarks/check_regression.py``
(``panel`` / ``calib_us`` / ``execute.fused_us``): the gated series is the
structured lane's own exec time, so CI catches a fast-lane regression the
way it catches one on the general panel.

    PYTHONPATH=src python -m benchmarks.collect_structured_json
    PYTHONPATH=src python -m benchmarks.collect_structured_json \
        --datasets dlmc-nm-1-32 --max-dim 2048 --out ci.json
"""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import spmm
from .common import STRUCTURED_DATASETS, geomean, load_dataset, time_fn

N = 128


def _calibration_us(rng: np.random.RandomState) -> float:
    """Fixed-size dense matmul: the machine-speed anchor for the gate.

    Larger and more repeated than the fused collector's anchor: this
    panel is only two to three datasets, so anchor noise dominates the
    normalized geomean unless the anchor itself is stable.
    """
    x = jnp.asarray(rng.randn(1024, 1024).astype(np.float32))
    y = jnp.asarray(rng.randn(1024, 128).astype(np.float32))
    f = jax.jit(lambda a, b: a @ b)
    return time_fn(lambda: f(x, y), repeats=9, warmup=2)


def main(argv=None) -> None:
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--datasets", nargs="*", default=list(STRUCTURED_DATASETS))
    p.add_argument("--max-dim", type=int, default=4096)
    p.add_argument("--n", type=int, default=N, help="dense operand width")
    p.add_argument("--out", default="BENCH_structured.json")
    args = p.parse_args(argv)

    import dataclasses

    rng = np.random.RandomState(0)
    calib_us = _calibration_us(rng)
    cfg = spmm.SpmmConfig(impl="xla", bn=max(args.n, 128))

    struct_us, general_us, formats, waste = {}, {}, {}, {}
    for name in args.datasets:
        rows, cols, vals, shape = load_dataset(name, max_dim=args.max_dim)
        b = jnp.asarray(rng.randn(shape[1], args.n).astype(np.float32))
        plan_s = spmm.prepare(rows, cols, vals, shape, cfg)
        plan_g = spmm.prepare(
            rows, cols, vals, shape,
            dataclasses.replace(cfg, structure_hint="general"))
        struct_us[name] = time_fn(lambda: spmm.execute(plan_s, b))
        general_us[name] = time_fn(lambda: spmm.execute(plan_g, b))
        formats[name] = plan_s.matrix_format
        waste[name] = plan_s.stats_dict["padding_waste"]

    speedups = {k: general_us[k] / struct_us[k] for k in struct_us}
    # the structured lane's win, measured on the N:M rows it actually
    # claims (the unstructured control stays general by design: its
    # speedup is ~1.0 and would dilute the margin it exists to contrast)
    claimed = [k for k in struct_us if formats[k] != "general"]
    record = {
        "panel": (f"{sorted(struct_us)} max_dim={args.max_dim} "
                  f"n={args.n} structured"),
        "metric": "us_per_call (best-of-3 wall clock, compile excluded)",
        "calib_us": round(calib_us, 1),
        "execute": {
            # gated series: the structured lane's own exec time
            "fused_us": {k: round(v, 1) for k, v in struct_us.items()},
            "geomean_us": round(geomean(struct_us.values()), 1),
        },
        "structured": {
            "general_us": {k: round(v, 1) for k, v in general_us.items()},
            "speedup": {k: round(v, 2) for k, v in speedups.items()},
            "format": formats,
            "padding_waste": {k: round(v, 3) for k, v in waste.items()},
            "normalized_structured": {
                k: round(v / calib_us, 3) for k, v in struct_us.items()},
            "normalized_general": {
                k: round(v / calib_us, 3) for k, v in general_us.items()},
            "geomean_speedup_structured_rows": (
                round(geomean(speedups[k] for k in claimed), 2)
                if claimed else None),
        },
    }

    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record, indent=2))


if __name__ == "__main__":
    main()
