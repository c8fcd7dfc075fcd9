"""Offline autotuner collector: warm a persistent tuning table on disk.

Runs the ``core.tuner`` microbenchmark pass for each dataset's shape class
with ``autotune=True`` and persists the resulting table through a
``PlanRegistry`` at ``--registry``, then emits a JSON record of what was
measured.  A second run against the same registry is table-served: every
resolve hits the persisted table and the process performs **zero**
microbenchmarks — ``--expect-warm`` turns that into a gate (exit 1 if any
microbenchmark ran), which is how CI proves the persistence path works.

    PYTHONPATH=src python -m benchmarks.collect_tuning_json \
        --registry /tmp/tuning-registry --out tuning_cold.json
    PYTHONPATH=src python -m benchmarks.collect_tuning_json \
        --registry /tmp/tuning-registry --out tuning_warm.json --expect-warm
"""
import argparse
import json
import sys

from repro.compile_cache import enable_compile_cache
from repro.core import spmm, tuner
from repro.dynamic.tuning import install_registry_store

from .common import BENCH_DATASETS, load_dataset

# small panel by default: one dataset per distinct tuner shape class is
# enough to exercise measure + persist + warm-serve
DEFAULT_DATASETS = ["cora", "F1", "reddit"]


def main(argv=None) -> int:
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--registry", required=True,
                   help="PlanRegistry root to persist the tuning table in")
    p.add_argument("--datasets", nargs="*", default=list(DEFAULT_DATASETS),
                   choices=list(BENCH_DATASETS))
    p.add_argument("--max-dim", type=int, default=512)
    p.add_argument("--out", default="BENCH_tuning.json")
    p.add_argument("--expect-warm", action="store_true",
                   help="fail (exit 1) if any microbenchmark ran — the "
                        "table was expected to serve every resolve")
    args = p.parse_args(argv)

    install_registry_store(args.registry)
    tuner.reset_tune_call_count()
    config = spmm.SpmmConfig(autotune=True)

    resolved = {}
    for name in args.datasets:
        rows, _, _, shape = load_dataset(name, max_dim=args.max_dim)
        m, k = shape
        nnz = int(rows.shape[0])
        cm = tuner.resolve_cost_model("spmm", m, k, nnz, config)
        # the tile-shape decision rides the same record: asking for it here
        # puts it under the --expect-warm gate (a warm process answers from
        # the table with zero microbenchmarks)
        ts = cm.tile_shape(m, k, int(config.bn), nnz)
        resolved[name] = {
            "shape_class": tuner.shape_class("spmm", m, k, nnz, config),
            "source": getattr(cm, "source", "analytic"),
            "tile_shape": list(ts) if ts is not None else None,
        }

    counters = tuner.get_tuner().counters()
    record = {
        "device": tuner.device_fingerprint(),
        "datasets": resolved,
        "counters": counters,
        "report": tuner.tuning_report(),
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    print(json.dumps({k: record[k] for k in ("device", "counters")},
                     indent=2))

    if args.expect_warm and tuner.tune_call_count() > 0:
        print(f"FAIL: expected a warm table-served run, but "
              f"{tuner.tune_call_count()} microbenchmark call(s) ran "
              f"(cold_misses={counters['cold_misses']}, "
              f"store_errors={counters['store_errors']})")
        return 1
    if args.expect_warm:
        print("OK: warm run, zero microbenchmark calls")
    return 0


if __name__ == "__main__":
    sys.exit(main())
