#!/usr/bin/env python
"""One traced run of a cell, read by the program's own spans and scopes.

    python chipbench/scopes.py --workload nd12k.spmm --seed 7 --seconds 10

Runs the cell as ``run.py --trace 1`` does and reduces the same profiler
trace a second time with ``program_trace``: device time per call in each
stage scope of the fused body, the unscoped share and its largest
operations, the scopes' sum against the busy time, the medians of the
program's ``repro.*`` spans, and the device's idle time by program phase.
The last line of standard output is one JSON object; it holds the traced
window's own ``call_ms`` or ``req_p95_ms`` too, for a comparison with an
untraced run.  Without a TPU it exits 2, as ``run.py`` does.

The TPU's trace names an operation by its HLO instruction only, so the
run compiles every program anew (no persistent cache) with XLA dumping
the fused bodies' optimized modules, whose ``op_name`` metadata give each
instruction's scope (``program_trace.hlo_scopes``).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402


HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import harness  # noqa: E402
import program_trace  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402


def summarize(red: dict, busy_ns: float, window_ns: float,
              calls: int) -> dict:
    """Per-call readings from ``program_trace.reduce``'s result."""
    scopes = red["scope_ns"]
    total = sum(scopes.values())
    flush = sum(ns for k, ns in red["program_gaps"].items()
                if k.startswith("repro.flush"))
    return {
        "scope_ms_per_call": {k: v * 1e-6 / calls
                              for k, v in sorted(scopes.items())},
        "busy_ms_per_call": busy_ns * 1e-6 / calls,
        "scope_sum_over_busy": total / busy_ns if busy_ns else None,
        "unscoped_share": scopes.get(program_trace.UNSCOPED, 0.0) / total
        if total else None,
        "unscoped_ops_s": red["unscoped_ops"],
        "span_median_us": {k: statistics.median(v) * 1e-3
                           for k, v in sorted(red["program_spans"].items())},
        "span_count": {k: len(v)
                       for k, v in sorted(red["program_spans"].items())},
        "program_gaps_s": program_trace.top(red["program_gaps"]),
        "flush_idle_pct": 100.0 * flush / window_ns if window_ns else None,
    }


def dump_fused_bodies() -> str:
    """Have XLA dump the fused bodies' optimized modules (they compile as
    ``jit(_run)``, their ``shard_map`` flavors as ``jit(_exec)``) into a
    new directory; only in effect when set before jax starts."""
    dump = tempfile.mkdtemp(prefix="chipbench-hlo-")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump}",
        "--xla_dump_hlo_as_text",
        "--xla_dump_hlo_module_re=jit__run|jit__exec")))
    return dump


def read_cell(root, workload: str, seed: int, seconds: float,
              dump=None, impl=None) -> dict:
    """Run the cell traced and return its readings (``main``'s line);
    ``dump`` is the directory of ``dump_fused_bodies``."""
    seen = {}
    load_xspace, p95 = trace_reduce.load_xspace, harness._p95

    def load_both(trace_dir):
        texts = [Path(f).read_text() for f in glob.glob(
            os.path.join(dump, "*after_optimizations.txt"))] if dump else []
        seen["modules"] = len(texts)
        seen["events"] = program_trace.load(trace_dir, texts)
        return load_xspace(trace_dir)

    def p95_seen(values):
        seen["p95_s"] = p95(values)
        return seen["p95_s"]

    trace_reduce.load_xspace, harness._p95 = load_both, p95_seen
    try:
        result = harness.run_cell(root, workload, seed, seconds, True, T0,
                                  impl=impl)
    finally:
        trace_reduce.load_xspace, harness._p95 = load_xspace, p95
    info = result["info"]
    calls = info.get("calls") or info["requests"]
    red = program_trace.reduce(seen["events"])
    dev = result["device"]
    out = {"workload": workload, "seed": seed,
           "correct": result["correct"], "metrics": result["metrics"],
           "traced_call_ms": info["window_s"] / calls * 1e3,
           "traced_req_p95_ms": seen["p95_s"] * 1e3 if "p95_s" in seen
           else None,
           "calls": calls, "device": dev, "hlo_modules": seen["modules"],
           # the named Pallas kernels: the breakdown's entries that are no
           # HLO instruction label
           "kernel_ms_per_call": {
               k: s * 1e3 / calls for k, s in result["breakdown"]["device_ops"]
               if " = " not in k}}
    out.update(summarize(red, dev["busy_s"] * 1e9, dev["window_s"] * 1e9,
                         calls))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)

    dump = dump_fused_bodies()
    bench = harness.Bench(run.ROOT)
    why = run.chips_missing(int(bench.workload(args.workload)["chips"]))
    if why:
        print(f"chipbench: {why}; nothing was run", file=sys.stderr)
        return 2
    import jax

    # a program loaded from a cache is not compiled, and not dumped
    jax.config.update("jax_enable_compilation_cache", False)
    print(json.dumps(read_cell(run.ROOT, args.workload, args.seed,
                               args.seconds, dump)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
