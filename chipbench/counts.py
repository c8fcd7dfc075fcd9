"""Operations and bytes of the work a call or a kernel's path must do.

Counted from the nonzeros alone (their rows and columns, and which engine
path holds each), never from the tiles a plan pads them into: a change
that packs tiles tighter or streams fewer bytes leaves these counts as
they are, and so does any kernel that takes over a path.

- spmm, ``nnz`` nonzeros of a path, width ``n``: ``2 nnz n`` operations;
  ``nnz x (4 B value + 4 B index)`` plus the distinct B rows the path reads
  and C rows it writes, ``x n x 4 B``.
- sddmm, width ``d``: ``2 nnz d`` operations; ``nnz x (4 B output + 4 B
  index)`` plus the distinct X rows and Y columns it reads, ``x d x 4 B``.
- a whole spmm call writes every row of C, so its C term is ``m x n x 4``.
"""
from __future__ import annotations

import numpy as np

F32 = 4
PER_NNZ = 8  # 4 B value (or output) + 4 B index


def _distinct(ids) -> int:
    return int(np.unique(ids).size)


def spmm_path(rows, cols, n: int):
    """(flops, bytes) of the spmm work on the given nonzeros."""
    nnz = int(rows.size)
    touched = _distinct(cols) + _distinct(rows)
    return 2.0 * nnz * n, float(nnz * PER_NNZ + touched * n * F32)


def sddmm_path(rows, cols, d: int):
    """(flops, bytes) of the sddmm work on the given nonzeros."""
    nnz = int(rows.size)
    touched = _distinct(rows) + _distinct(cols)
    return 2.0 * nnz * d, float(nnz * PER_NNZ + touched * d * F32)


def spmm_call(rows, cols, shape, n: int):
    """(flops, bytes) of one whole ``C = A @ B``: every C row is written."""
    nnz = int(rows.size)
    return (2.0 * nnz * n,
            float(nnz * PER_NNZ + (_distinct(cols) + shape[0]) * n * F32))


def sddmm_call(rows, cols, shape, d: int):
    """(flops, bytes) of one whole sddmm call."""
    del shape
    return sddmm_path(rows, cols, d)
