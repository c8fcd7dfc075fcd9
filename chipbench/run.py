#!/usr/bin/env python
"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload arxiv.spmm --seed 7 --seconds 20 --trace 0

The cell (a configuration under a traffic mix) is named in
``BENCHMARK.json`` at the root of the checkout.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The last line of
standard output is one JSON object; the numbers compared with the float64
reference are the last lines of standard error, each beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))


def check_lines(checks: dict) -> list:
    return [f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g}) "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}"
            for k, c in checks.items()]


def chips_missing(chips: int):
    """Why this machine cannot run the cell, or None when it can."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: jax sees platform {devs[0].platform!r}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, jax sees {len(devs)}"
    return None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import harness

    bench = harness.Bench(ROOT)
    chips = int(bench.workload(args.workload)["chips"])
    why = chips_missing(chips)
    if why:
        print(f"chipbench: {why}; nothing was run", file=sys.stderr)
        return 2

    import jax
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program of the cell goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"chipbench: compile cache {cache}", file=sys.stderr, flush=True)
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T0)
    sys.stderr.write("\n".join(check_lines(result["checks"])) + "\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
