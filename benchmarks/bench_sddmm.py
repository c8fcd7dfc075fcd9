"""SDDMM benchmarks: fused pattern-sampled scores vs the dense detour.

Two rows per dataset:
- ``sddmm_fused``  — one fused ``execute_sddmm`` dispatch (tile dots on
  the core stream + fringe gather, merged in the original COO order);
- ``sddmm_dense``  — the cost the operator replaces: materialize the full
  dense ``X @ Y`` product, then gather the pattern's entries.

``derived`` reports the dense-detour ratio (dense-then-gather time /
fused time) and the edge throughput — the figure of merit for GAT-style
attention, where the dense (M, K) score matrix must never exist.

    PYTHONPATH=src python -m benchmarks.bench_sddmm [--max-dim 1024]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import spmm
from repro.exec import execute_sddmm
from .common import emit, load_dataset, time_fn

DATASETS = ["cora", "ogbn-arxiv", "F1", "reddit"]
D = 64  # feature dim of both dense operands


def run(max_dim: int = 1024) -> None:
    rng = np.random.RandomState(0)
    for name in DATASETS:
        rows, cols, vals, shape = load_dataset(name, max_dim=max_dim)
        plan = spmm.prepare(rows, cols, vals, shape, spmm.SpmmConfig())
        x = jnp.asarray(rng.randn(shape[0], D).astype(np.float32))
        y = jnp.asarray(rng.randn(D, shape[1]).astype(np.float32))
        nnz = rows.size

        fused_us = time_fn(lambda: execute_sddmm(plan, x, y))

        ri = jnp.asarray(rows.astype(np.int32))
        ci = jnp.asarray(cols.astype(np.int32))
        dense_gather = jax.jit(lambda a, b: (a @ b)[ri, ci])
        dense_us = time_fn(lambda: dense_gather(x, y))

        edges_per_us = nnz / fused_us
        emit(f"sddmm_fused[{name}]", fused_us,
             f"dense_ratio={dense_us / fused_us:.2f}x "
             f"edges_per_us={edges_per_us:.0f} nnz={nnz} d={D}")
        emit(f"sddmm_dense[{name}]", dense_us,
             f"dense_MK={shape[0] * shape[1]} nnz={nnz}")


def main(argv=None) -> None:
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--max-dim", type=int, default=1024)
    args = p.parse_args(argv)
    run(max_dim=args.max_dim)


if __name__ == "__main__":
    main()
