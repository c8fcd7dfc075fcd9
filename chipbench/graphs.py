"""Sparse matrices of the benchmark's configurations, made from a seed.

A configuration fixes its structure (kind, sizes, degree and a structure
seed, all in ``configs/<name>.json``): like the real ogbn-arxiv or nd12k, a
deployment serves one fixed sparsity pattern.  The run's ``--seed`` draws
the values, so every seed gives the same work with other numbers.

The generators are copies of ``power_law`` and ``banded`` from the
program's ``repro.data.graphs``, kept here so that the yardstick does not
move when the program does.  They use numpy's ``Generator``, which takes
seeds of any size.
"""
from __future__ import annotations

import numpy as np


def _dedupe(rows, cols, k):
    keys = np.unique(rows.astype(np.int64) * k + cols)
    return keys // k, keys % k


def structure(gen: dict):
    """(rows, cols) of the configuration's pattern, row-major and unique."""
    rng = np.random.default_rng(int(gen["structure_seed"]))
    m, k = int(gen["m"]), int(gen["k"])
    if gen["kind"] == "power_law":
        # Zipf-like row degrees; columns skewed towards low ids
        deg = rng.pareto(float(gen["skew"]), m) + 1.0
        deg = np.minimum(deg / deg.mean() * float(gen["avg_degree"]), k)
        deg = np.maximum(deg.astype(np.int64), 1)
        rows = np.repeat(np.arange(m, dtype=np.int64), deg)
        cols = (k * rng.power(0.3, rows.size)).astype(np.int64) % k
    elif gen["kind"] == "banded":
        # FEM-style band: each row draws `band` columns within +-band of
        # its diagonal position
        band = int(gen["band"])
        rows = np.repeat(np.arange(m, dtype=np.int64), band)
        offs = rng.integers(-band, band + 1, rows.size)
        cols = np.clip((rows * k) // m + offs, 0, k - 1)
    else:
        raise ValueError(f"unknown generator kind {gen['kind']!r}")
    return _dedupe(rows, cols, k)

