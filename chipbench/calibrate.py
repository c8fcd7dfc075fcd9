#!/usr/bin/env python
"""Readings the limits and the serving rate are set from, in one process.

    python chipbench/calibrate.py --workload arxiv.spmm --seconds 3 \\
        --seeds 11,12,13 --control-seeds 21,22,23
    python chipbench/calibrate.py --workload arxiv.serve --seconds 10 \\
        --rates 30,40,50,60

For each ``--seeds`` entry it runs the cell as the benchmark does and
prints the numbers compared (the program's readings, the lower ends of the
limits).  For each ``--control-seeds`` entry it runs the cell with the
bfloat16 control (``control.py``) answering in the program's place (the
upper ends).  ``--rates`` runs an open mix at each rate and prints its
latencies and how long the window took to drain: the sweep that finds the
knee.  The benchmark's own runs never run this.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, chips_missing  # run also sets sys.path


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--seeds", type=_ints, default=[])
    p.add_argument("--control-seeds", type=_ints, default=[])
    p.add_argument("--rates", type=lambda s: [float(x) for x in s.split(",")],
                   default=[])
    args = p.parse_args(argv)

    import harness

    bench = harness.Bench(ROOT)
    wl = bench.workload(args.workload)
    why = chips_missing(int(wl["chips"]))
    if why:
        print(f"calibrate: {why}", file=sys.stderr)
        return 2
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import control

    mix = bench.mix(wl["traffic"])
    out = {"workload": args.workload, "program": [], "control": [],
           "rates": []}

    def one(seed):
        t = time.perf_counter()
        r = harness.run_cell(ROOT, args.workload, seed, args.seconds, False,
                             t)
        return {"seed": seed, "correct": r["correct"],
                "checks": {k: c["value"] for k, c in r["checks"].items()},
                "metrics": {k: m["value"] for k, m in r["metrics"].items()},
                "info": {k: v for k, v in r["info"].items()
                         if k != "compared_calls"}}

    for seed in args.seeds:
        out["program"].append(one(seed))
        print("program", json.dumps(out["program"][-1]), flush=True)
    for seed in args.control_seeds:
        with control.in_program_place(mix["op"], mix["kind"]):
            out["control"].append(one(seed))
        print("control", json.dumps(out["control"][-1]), flush=True)
    orig_mix = harness.Bench.mix
    for i, rate in enumerate(args.rates):
        harness.Bench.mix = lambda self, name, rate=rate: dict(
            orig_mix(self, name), rate_per_s=rate)
        try:
            out["rates"].append(dict(one(1000 + i), rate_per_s=rate))
        finally:
            harness.Bench.mix = orig_mix
        print("rate", json.dumps(out["rates"][-1]), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
