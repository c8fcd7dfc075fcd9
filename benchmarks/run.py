"""Benchmark driver — one suite per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [suite ...]

Prints ``name,us_per_call,derived`` CSV rows.
"""
import sys

from repro.compile_cache import enable_compile_cache

SUITES = [
    "bench_overall",        # Fig. 15
    "bench_coordination",   # Fig. 16
    "bench_migration",      # Fig. 17/18
    "bench_threshold",      # Fig. 19
    "bench_orchestration",  # Fig. 20
    "bench_density",        # Fig. 21
    "bench_tile_shape",     # Fig. 22
    "bench_scaling_n",      # Fig. 23
    "bench_tile_redundancy",  # Table 1
    "bench_preprocess",     # Tables 3/4
    "bench_fused",          # fused single-dispatch executor vs two-dispatch
    "bench_sharded",        # multi-device sharded executor scaling
    "bench_dynamic",        # dynamic updates vs full re-prepare
]


def main() -> None:
    enable_compile_cache()
    only = set(sys.argv[1:])
    print("name,us_per_call,derived")
    for suite in SUITES:
        if only and suite not in only:
            continue
        mod = __import__(f"benchmarks.{suite}", fromlist=["run"])
        mod.run()


if __name__ == "__main__":
    main()
