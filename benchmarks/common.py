"""Shared benchmark utilities: timing, datasets, CSV emission.

Every bench prints ``name,us_per_call,derived`` rows (one per paper
table/figure cell).  Wall-clock numbers are CPU-host timings of the XLA
paths — meaningful as *relative* comparisons that exercise the framework's
coordination logic; chip timings come from ``chipbench/``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import jax
import numpy as np

from repro.core.tuner import timed_best_of
from repro.data import graphs

# scaled-down dataset panel (paper Table 2 character, CPU-friendly sizes)
BENCH_DATASETS = [
    "cora", "wiki-RfA", "ogbn-arxiv", "pattern1", "human_gene1", "F1",
    "mouse_gene", "reddit",
]

# pruned-DNN panel for the structured-sparsity fast lane: magnitude-pruned
# N:M weights (auto-detected, ride the packed lane) + the unstructured
# control at the same density (stays on the general lane)
STRUCTURED_DATASETS = ["dlmc-nm-1-32", "dlmc-nm-2-32", "dlmc-unstr"]


def load_dataset(name: str, max_dim: int = 4096):
    spec = graphs.PAPER_DATASETS[name]
    spec = dataclasses.replace(spec, m=min(spec.m, max_dim),
                               k=min(spec.k, max_dim))
    rows, cols, vals = graphs.generate(spec)
    return rows, cols, vals, (spec.m, spec.k)


def time_fn(fn: Callable[[], jax.Array], repeats: int = 3,
            warmup: int = 1) -> float:
    """Best-of wall time in microseconds (compile excluded).

    The synchronized best-of-N loop itself lives in ``repro.core.tuner``
    (it is also what the autotuner measures with); this is the
    microsecond-unit CSV-facing wrapper.
    """
    return timed_best_of(fn, repeats=repeats, warmup=warmup) * 1e6


def geomean(values) -> float:
    return float(np.exp(np.mean(np.log(list(values)))))


def emit(name: str, us_per_call: float, derived: str) -> str:
    line = f"{name},{us_per_call:.1f},{derived}"
    print(line, flush=True)
    return line


def spmm_gflops(nnz: int, n: int, us: float) -> float:
    return 2.0 * nnz * n / (us * 1e-6) / 1e9
