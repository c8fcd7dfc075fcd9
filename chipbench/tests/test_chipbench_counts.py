"""Operations and bytes the roofline shares divide by (chipbench/counts.py)."""
import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (CPU platform, paths)
import counts


def test_spmm_path_counts_by_hand():
    rows = np.array([0, 0, 2, 5])
    cols = np.array([1, 3, 3, 3])
    flops, nbytes = counts.spmm_path(rows, cols, n=128)
    assert flops == 2 * 4 * 128
    # 4 nonzeros x 8 B, B rows {1, 3} read and C rows {0, 2, 5} written
    assert nbytes == 4 * 8 + (2 + 3) * 128 * 4


def test_sddmm_and_call_counts_by_hand():
    rows = np.array([0, 1, 1])
    cols = np.array([2, 2, 7])
    assert counts.sddmm_path(rows, cols, d=64) == (
        2 * 3 * 64, 3 * 8 + (2 + 2) * 64 * 4)
    # a whole spmm call writes every one of the 10 C rows
    assert counts.spmm_call(rows, cols, (10, 8), n=32) == (
        2 * 3 * 32, 3 * 8 + (2 + 10) * 32 * 4)


@pytest.mark.parametrize("tile", [(128, 64), (256, 128)])
def test_padded_tiles_do_not_change_counts(tile):
    """Two plans of one matrix, every nonzero on the matrix path, with
    tiles of different sizes (and so different padding): the counts the
    harness takes from the plan's split are the same."""
    import harness
    import repro.sparse as sp
    from repro.core.plan_ir import SpmmConfig

    rng = np.random.default_rng(3)
    rows = rng.integers(0, 300, 900)
    cols = rng.integers(0, 300, 900)
    keys = np.unique(rows * 300 + cols)
    rows, cols = keys // 300, keys % 300
    vals = rng.standard_normal(rows.size).astype(np.float32)
    bm, bk = tile
    a = sp.from_coo(rows, cols, vals, (300, 300), config=SpmmConfig(
        bm=bm, bk=bk, bn=128, alpha=1e-9, enable_col_stage=False))
    st = a.plan.stats_dict
    assert st["core_nnz"] == rows.size and st["padding_waste"] > 0.9
    inp = harness.Inputs(rows, cols, vals, (300, 300), 128)
    work = harness._work(a.plan, inp, "spmm")
    assert work["dense_tile_spmm"] == counts.spmm_path(rows, cols, 128)
    assert work["call"] == counts.spmm_call(rows, cols, (300, 300), 128)
