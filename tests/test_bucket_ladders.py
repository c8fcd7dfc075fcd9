"""The degree-bucketed fringe, called directly: ``plan_ir.bucket_fringe_rows``
lays a packed fringe stream out in power-of-two buckets and
``ref.bucketed_gather_spmm`` gathers and reduces over that layout.  This is
the fringe that ``arxiv.spmm`` and ``products.spmm`` run; here it is checked
on ladders chosen for their edges and against ``ref_gather_spmm``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_ir, spmm
from repro.kernels import ops, ref


def _stream(degrees, k, seed=0):
    """A packed row-sorted fringe stream: row ``i`` holds ``degrees[i]``
    distinct random columns of ``k``."""
    rng = np.random.RandomState(seed)
    pr = np.repeat(np.arange(len(degrees)), degrees).astype(np.int32)
    pc = np.concatenate([np.sort(rng.choice(k, d, replace=False))
                         for d in degrees]).astype(np.int32)
    pv = rng.randn(pr.size).astype(np.float32)
    return pr, pc, pv


def _power_law_degrees(n=90, k=300, seed=5):
    rng = np.random.RandomState(seed)
    return np.minimum((rng.pareto(1.2, n) * 2 + 1).astype(int), k).tolist()


K = 300
LADDERS = {
    # every row the same length: one bucket
    "uniform": [6] * 13,
    # one row holds every nonzero
    "one_row": [257],
    # lengths exactly at and one past each power of two
    "pow2_edges": [1, 2, 3, 4, 5, 8, 9, 16, 17, 32, 33, 64, 65],
    "power_law": _power_law_degrees(),
}


# one compile per ladder and width, instead of one per eager op
_bucketed = jax.jit(ref.bucketed_gather_spmm, static_argnames=("buckets",))
_scatter = jax.jit(ref.ref_gather_spmm, static_argnames=("num_rows",))


def _expected_ladder(degrees):
    """((n_rows_b, width_b), ...): widths are degrees rounded up to a power
    of two, ascending; each bucket's rows filled to a multiple of 8."""
    widths = [1 << max(0, int(d - 1).bit_length()) for d in degrees]
    ladder = []
    for w in sorted(set(widths)):
        n = widths.count(w)
        ladder.append((-(-n // plan_ir.BUCKET_ROW_ALIGN)
                       * plan_ir.BUCKET_ROW_ALIGN, w))
    return tuple(ladder)


@pytest.mark.parametrize("name", sorted(LADDERS))
def test_bucket_fringe_rows_layout(name):
    degrees = LADDERS[name]
    pr, pc, pv = _stream(degrees, K)
    rows, cols, vals, row_order, slot, ladder = plan_ir.bucket_fringe_rows(
        pr, pc, pv)
    assert ladder == _expected_ladder(degrees)
    n_new = sum(n for n, _ in ladder)
    assert rows.size == cols.size == vals.size == sum(n * w for n, w in ladder)
    assert row_order.size == n_new
    # every old row appears once; the rest of the new rows are fillers
    assert sorted(row_order[row_order >= 0].tolist()) == list(range(
        len(degrees)))
    # each entry sits at its slot, under its row's new id
    new_of_old = np.empty(len(degrees), np.int64)
    new_of_old[row_order[row_order >= 0]] = np.flatnonzero(row_order >= 0)
    np.testing.assert_array_equal(rows[slot], new_of_old[pr])
    np.testing.assert_array_equal(cols[slot], pc)
    np.testing.assert_array_equal(vals[slot], pv)
    pad = np.ones(rows.size, bool)
    pad[slot] = False
    assert not vals[pad].any()
    # width-major within each bucket: slot j * n_b + i is entry j of row i
    start, first_row = 0, 0
    for n_b, w in ladder:
        blk = rows[start:start + n_b * w].reshape(w, n_b)
        np.testing.assert_array_equal(
            blk, np.broadcast_to(first_row + np.arange(n_b), (w, n_b)))
        olds = row_order[first_row:first_row + n_b]
        real = olds[olds >= 0]
        deg = np.asarray(degrees)[real]
        assert (deg <= w).all() and (2 * deg > w).all()
        # a bucket keeps the packed order of its rows
        assert (np.diff(real) > 0).all()
        start += n_b * w
        first_row += n_b


@pytest.mark.parametrize("n", [1, 100, 256])
@pytest.mark.parametrize("name", sorted(LADDERS))
def test_bucketed_gather_spmm_matches_scatter_oracle(name, n):
    degrees = LADDERS[name]
    pr, pc, pv = _stream(degrees, K, seed=len(degrees))
    rows, cols, vals, row_order, _slot, ladder = plan_ir.bucket_fringe_rows(
        pr, pc, pv)
    rng = np.random.RandomState(n)
    b = rng.randn(K, n).astype(np.float32)
    got = np.asarray(_bucketed(jnp.asarray(cols), jnp.asarray(vals),
                               jnp.asarray(b), buckets=ladder))
    n_new = sum(nb for nb, _ in ladder)
    assert got.shape == (n_new, n)
    want = np.asarray(_scatter(jnp.asarray(rows), jnp.asarray(cols),
                               jnp.asarray(vals), jnp.asarray(b),
                               num_rows=n_new))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and against float64 over the packed stream, through the row order
    dense = np.zeros((len(degrees), K))
    np.add.at(dense, (pr, pc), pv.astype(np.float64))
    exact = dense @ b.astype(np.float64)
    real = row_order >= 0
    np.testing.assert_allclose(got[real], exact[row_order[real]],
                               rtol=1e-4, atol=1e-4)
    assert not got[~real].any()  # filler rows stay zero


def test_empty_fringe_gets_no_ladder_and_adds_nothing():
    """A matrix that lies wholly on the matrix path is never bucketed:
    prepare keeps its one-slot dummy fringe stream without a ladder, and
    the ladder-less XLA fringe over it (or over no stream) adds zero."""
    a = np.zeros((16, 64), np.float32)
    a[:8] = np.arange(1, 65, dtype=np.float32)
    rows, cols = np.nonzero(a)
    plan = spmm.prepare(rows, cols, a[rows, cols], a.shape, spmm.SpmmConfig(
        impl="xla", alpha=1e-9, enable_col_stage=False))
    assert plan.fringe_buckets == ()
    assert plan.stats_dict["fringe_nnz"] == 0
    assert plan.stats_dict["fringe_buckets"] == 0
    b = jnp.asarray(np.random.RandomState(0).randn(64, 8).astype(np.float32))
    nr = int(plan.fringe_row_ids.shape[0])
    out = ops.fringe_spmm(plan.fringe_rows, plan.fringe_cols,
                          plan.fringe_vals, b, num_rows=nr, impl="xla",
                          buckets=plan.fringe_buckets)
    assert out.shape == (nr, 8) and not np.asarray(out).any()
    none = ops.fringe_spmm(jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32),
                           jnp.zeros(0, jnp.float32), b, num_rows=0,
                           impl="xla")
    assert none.shape == (0, 8)
