"""Host format builders of ``core/formats.py`` against ``scipy.sparse`` on
the inputs they must survive: no nonzeros, one row, one column, duplicate
triplets (summed, as COO semantics say) and a plain random matrix."""
import numpy as np
import pytest
import scipy.sparse as sps

from repro.core import formats


def _triplets(case):
    """(rows, cols, vals, shape) for each input kind."""
    rng = np.random.RandomState(11)
    if case == "empty":
        return (np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.float32), (5, 7))
    if case == "one_row":
        cols = np.array([9, 0, 4, 13], np.int32)
        return (np.zeros(4, np.int32), cols,
                rng.randn(4).astype(np.float32), (1, 16))
    if case == "one_col":
        rows = np.array([12, 3, 0, 7, 8], np.int32)
        return (rows, np.zeros(5, np.int32),
                rng.randn(5).astype(np.float32), (16, 1))
    if case == "duplicates":
        rows = np.array([2, 0, 2, 2, 5, 0, 5], np.int32)
        cols = np.array([1, 3, 1, 0, 5, 3, 5], np.int32)
        return rows, cols, rng.randn(7).astype(np.float32), (6, 6)
    mask = rng.rand(20, 33) < 0.15
    rows, cols = np.nonzero(mask)
    return (rows.astype(np.int32), cols.astype(np.int32),
            rng.randn(rows.size).astype(np.float32), (20, 33))


CASES = ["empty", "one_row", "one_col", "duplicates", "random"]


def _scipy_dense(rows, cols, vals, shape):
    return sps.coo_matrix((vals.astype(np.float64), (rows, cols)),
                          shape=shape).toarray()


def _csr_dense(csr):
    out = np.zeros(csr.shape, np.float64)
    row_of = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))
    np.add.at(out, (row_of, csr.indices), csr.data.astype(np.float64))
    return out


@pytest.mark.parametrize("case", CASES)
def test_csr_from_coo_np_matches_scipy(case):
    rows, cols, vals, shape = _triplets(case)
    csr = formats.csr_from_coo_np(rows, cols, vals, shape)
    assert csr.shape == shape and csr.nnz == rows.size
    assert csr.indptr.shape == (shape[0] + 1,) and csr.indptr[0] == 0
    np.testing.assert_array_equal(_csr_dense(csr),
                                  _scipy_dense(rows, cols, vals, shape))
    # stored entries keep duplicates; scipy's canonical form sums them
    ref = sps.csr_matrix((vals, (rows, cols)), shape=shape)
    ref.sum_duplicates()
    for r in range(shape[0]):
        mine = csr.indices[csr.indptr[r]:csr.indptr[r + 1]]
        assert (np.diff(mine) >= 0).all()  # columns sorted within a row
        np.testing.assert_array_equal(
            np.unique(mine), ref.indices[ref.indptr[r]:ref.indptr[r + 1]])
    if case != "duplicates":
        np.testing.assert_array_equal(csr.indptr, ref.indptr)
        np.testing.assert_array_equal(csr.indices, ref.indices)
        np.testing.assert_array_equal(csr.data, ref.data)


@pytest.mark.parametrize("case", CASES)
def test_csr_from_dense_matches_scipy(case):
    rows, cols, vals, shape = _triplets(case)
    a = _scipy_dense(rows, cols, vals, shape).astype(np.float32)
    csr = formats.csr_from_dense(a)
    ref = sps.csr_matrix(a)
    np.testing.assert_array_equal(csr.indptr, ref.indptr)
    np.testing.assert_array_equal(csr.indices, ref.indices)
    np.testing.assert_array_equal(csr.data, ref.data)
    assert csr.shape == ref.shape and csr.nnz == ref.nnz


@pytest.mark.parametrize("case", CASES)
def test_row_lengths_count_stored_entries(case):
    rows, cols, vals, shape = _triplets(case)
    lengths = formats.csr_from_coo_np(rows, cols, vals, shape).row_lengths()
    assert lengths.shape == (shape[0],) and lengths.sum() == rows.size
    np.testing.assert_array_equal(lengths,
                                  np.bincount(rows, minlength=shape[0]))
    canonical = np.diff(sps.csr_matrix((vals, (rows, cols)),
                                       shape=shape).indptr)
    if case == "duplicates":
        # a duplicate triplet is a stored entry: row 2 stores (2, 1) twice
        assert lengths[2] == 3 and canonical[2] == 2
        assert (lengths >= canonical).all()
    else:
        np.testing.assert_array_equal(lengths, canonical)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("pad_to", [1, 8])
def test_coo_from_arrays_sorts_and_pads(case, pad_to):
    rows, cols, vals, shape = _triplets(case)
    coo = formats.coo_from_arrays(rows, cols, vals, shape, pad_to=pad_to)
    nnz = rows.size
    r, c, v = (np.asarray(x) for x in (coo.rows, coo.cols, coo.vals))
    assert coo.nnz == nnz and coo.shape == shape
    assert r.size % pad_to == 0 and nnz <= r.size
    assert r.size == max(pad_to, -(-nnz // pad_to) * pad_to)
    # row-major sorted like scipy's canonical order (duplicates adjacent)
    ref = sps.coo_matrix((vals, (rows, cols)), shape=shape).tocsr().tocoo()
    ref.sum_duplicates()
    keys = r[:nnz].astype(np.int64) * shape[1] + c[:nnz]
    assert (np.diff(keys) >= 0).all()
    np.testing.assert_array_equal(np.unique(keys),
                                  ref.row.astype(np.int64) * shape[1]
                                  + ref.col)
    # padding repeats the last row with zero values: it adds nothing
    assert not v[nnz:].any()
    if nnz:
        assert (r[nnz:] == r[nnz - 1]).all()
    np.testing.assert_allclose(formats.dense_from_coo(coo),
                               _scipy_dense(rows, cols, vals, shape),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", CASES)
def test_block_structure_from_coo_matches_scipy_pattern(case):
    """The active (window, k-block) pairs are the nonzero pattern of the
    block-count matrix, in row-major order; slots count within a window."""
    rows, cols, vals, shape = _triplets(case)
    bm, bk = 4, 2
    nw, nkb = -(-shape[0] // bm), -(-shape[1] // bk)
    wids, kblk = rows.astype(np.int64) // bm, cols.astype(np.int64) // bk
    bs = formats.block_structure_from_coo(wids, kblk, nw, nkb)
    ref = sps.csr_matrix((np.ones(rows.size), (wids, kblk)), shape=(nw, nkb))
    ref.sum_duplicates()
    ref.sort_indices()
    ref_uw = np.repeat(np.arange(nw), np.diff(ref.indptr))
    np.testing.assert_array_equal(bs.uw, ref_uw)
    np.testing.assert_array_equal(bs.ub, ref.indices)
    np.testing.assert_array_equal(bs.counts, np.diff(ref.indptr))
    np.testing.assert_array_equal(bs.slot,
                                  np.arange(ref.nnz) - ref.indptr[ref_uw])
    assert bs.max_blocks == max(1, int(np.diff(ref.indptr).max()))
    # every nonzero points at its own pair; each pair holds its count
    np.testing.assert_array_equal(bs.uw[bs.inv_idx], wids)
    np.testing.assert_array_equal(bs.ub[bs.inv_idx], kblk)
    np.testing.assert_array_equal(
        np.bincount(bs.inv_idx, minlength=ref.nnz), ref.data)
