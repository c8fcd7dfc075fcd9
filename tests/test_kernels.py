"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan_ir import bucket_fringe_kblocks
from repro.kernels import ops, ref
from repro.kernels.dense_tile_spmm import dense_tile_spmm
from repro.kernels.gather_spmm import STEP, gather_spmm, gather_spmm_ksharded
from repro.kernels.sddmm import gather_sddmm


def _block_stream(rng, num_windows, max_blocks, bm, bk, k_blocks, dtype):
    """Random flat tile stream (window-major sorted)."""
    steps_w, steps_c = [], []
    for w in range(num_windows):
        n = rng.randint(1, max_blocks + 1)
        steps_w += [w] * n
        steps_c += rng.choice(k_blocks, n, replace=False).tolist()
    t = len(steps_w)
    vals = rng.randn(t, bm, bk).astype(dtype)
    # sparsify tiles a bit
    vals *= (rng.rand(t, bm, bk) < 0.3)
    return (
        jnp.asarray(np.array(steps_w, np.int32)),
        jnp.asarray(np.array(steps_c, np.int32)),
        jnp.asarray(vals),
    )


@pytest.mark.parametrize("bm,bk,bn", [(8, 8, 128), (16, 32, 128), (128, 64, 256)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_dense_tile_spmm_matches_ref(bm, bk, bn, dtype):
    rng = np.random.RandomState(0)
    num_windows, k_blocks = 3, 4
    sw, sc, vals = _block_stream(rng, num_windows, 3, bm, bk, k_blocks, np.float32)
    vals = vals.astype(dtype)
    b = jnp.asarray(rng.randn(k_blocks * bk, bn), dtype)
    out = dense_tile_spmm(sw, sc, vals, b, num_windows=num_windows,
                          bm=bm, bk=bk, bn=bn, interpret=True)
    expect = ref.ref_block_stream_spmm(sw, sc, vals, b, num_windows)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bn", [128, 256])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_gather_spmm_matches_ref(bn, dtype):
    rng = np.random.RandomState(1)
    num_rows, kk, nnz = 6, 32, 40
    rows = np.sort(rng.randint(0, num_rows, nnz)).astype(np.int32)
    rows[:2] = 0
    rows[-2:] = num_rows - 1  # every packed row visited
    for r in range(num_rows):  # ensure all rows present
        if r not in rows:
            rows[rng.randint(nnz)] = r
    rows = np.sort(rows)
    cols = rng.randint(0, kk, nnz).astype(np.int32)
    vals = rng.randn(nnz).astype(np.float32)
    b = jnp.asarray(rng.randn(kk, bn), dtype)
    out = gather_spmm(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals),
                      b, num_rows=num_rows, bn=bn, interpret=True)
    expect = ref.ref_gather_spmm(jnp.asarray(rows), jnp.asarray(cols),
                                 jnp.asarray(vals), b, num_rows)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


def test_gather_spmm_duplicate_columns():
    """Consecutive same-col nonzeros (copy-elision path) accumulate correctly."""
    rows = jnp.asarray(np.array([0, 0, 0, 1], np.int32))
    cols = jnp.asarray(np.array([2, 2, 2, 2], np.int32))
    vals = jnp.asarray(np.array([1.0, 2.0, 3.0, 4.0], np.float32))
    b = jnp.asarray(np.eye(4, 128, dtype=np.float32) + 1.0)
    out = gather_spmm(rows, cols, vals, b, num_rows=2, bn=128, interpret=True)
    expect = ref.ref_gather_spmm(rows, cols, vals, b, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6)


def _bucketed_stream(rng, num_rows, num_kb, bk, chunk, max_per_kb=6):
    """Hand-built k-bucketed fringe stream (the gather_spmm_ksharded layout):
    per-k-block row-sorted entries padded to a chunk multiple; empty
    k-blocks own no chunks.  Returns the stream plus the dense A it encodes."""
    kb_chunk, rows_l, cols_l, vals_l = [], [], [], []
    a = np.zeros((num_rows, num_kb * bk), np.float32)
    for kb in range(num_kb):
        cnt = rng.randint(0, max_per_kb + 1)
        if cnt == 0:
            continue
        r = np.sort(rng.randint(0, num_rows, cnt)).astype(np.int32)
        c = rng.randint(0, bk, cnt).astype(np.int32)
        v = rng.randn(cnt).astype(np.float32)
        np.add.at(a, (r, kb * bk + c), v)
        pad = ((cnt + chunk - 1) // chunk) * chunk - cnt
        rows_l.append(np.concatenate([r, np.zeros(pad, np.int32)]))
        cols_l.append(np.concatenate([c, np.zeros(pad, np.int32)]))
        vals_l.append(np.concatenate([v, np.zeros(pad, np.float32)]))
        kb_chunk += [kb] * ((cnt + chunk - 1) // chunk)
    return (
        jnp.asarray(np.array(kb_chunk, np.int32)),
        jnp.asarray(np.concatenate(rows_l)),
        jnp.asarray(np.concatenate(cols_l)),
        jnp.asarray(np.concatenate(vals_l)),
        a,
    )


@pytest.mark.parametrize("chunk", [1, 3, 4, 8])
def test_gather_spmm_ksharded_matches_refs(chunk):
    """K-sharded streaming kernel vs its k-blocked oracle and the dense
    answer; rows recur across k-blocks, so partial sums must merge in the
    resident output block across chunk steps."""
    rng = np.random.RandomState(chunk)
    num_rows, num_kb, bk = 6, 5, 8
    kb_chunk, rows, cols, vals, a = _bucketed_stream(
        rng, num_rows, num_kb, bk, chunk)
    b = jnp.asarray(rng.randn(num_kb * bk, 128).astype(np.float32))
    out = gather_spmm_ksharded(kb_chunk, rows, cols, vals, b,
                               num_rows=num_rows, bk=bk, bn=128,
                               interpret=True)
    oracle = ref.ref_gather_spmm_kblocked(kb_chunk, rows, cols, vals, b,
                                          num_rows, bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_gather_spmm_ksharded_ragged_k():
    """K not a multiple of bk: the kernel pads B internally."""
    rng = np.random.RandomState(11)
    num_rows, num_kb, bk, chunk = 4, 3, 8, 2
    kb_chunk, rows, cols, vals, _ = _bucketed_stream(
        rng, num_rows, num_kb, bk, chunk, max_per_kb=4)
    k_ragged = num_kb * bk - 3
    # zero entries addressing the (padded-away) tail columns
    keep_cols = jnp.repeat(kb_chunk, chunk) * bk + cols < k_ragged
    vals = jnp.where(keep_cols, vals, 0.0)
    b = jnp.asarray(rng.randn(k_ragged, 128).astype(np.float32))
    out = gather_spmm_ksharded(kb_chunk, rows, cols, vals, b,
                               num_rows=num_rows, bk=bk, bn=128,
                               interpret=True)
    oracle = ref.ref_gather_spmm_kblocked(kb_chunk, rows, cols, vals, b,
                                          num_rows, bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


def test_densified_duplicate_pairs_accumulate():
    """Regression: hand-built streams may repeat a (window, k-block) pair;
    the add-based densify must accumulate both tiles (previously the last
    tile of a duplicated slot silently won)."""
    rng = np.random.RandomState(9)
    bm, bk = 8, 8
    sw = jnp.asarray(np.array([0, 0, 1, 0], np.int32))
    sc = jnp.asarray(np.array([1, 1, 0, 1], np.int32))  # slot (0,1) thrice
    vals = jnp.asarray(rng.randn(4, bm, bk).astype(np.float32))
    b = jnp.asarray(rng.randn(2 * bk, 128).astype(np.float32))
    out = ref.densified_block_stream_spmm(sw, sc, vals, b, 2)
    expect = ref.ref_block_stream_spmm(sw, sc, vals, b, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    # the duplicate stream is above the occupancy threshold, so the default
    # ops dispatch (no uniqueness guarantee) must also take the safe densify
    out_ops = ops.block_stream_spmm(sw, sc, vals, b, num_windows=2,
                                    bm=bm, bk=bk, bn=128, impl="xla")
    np.testing.assert_allclose(np.asarray(out_ops), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_densified_unique_matches_safe_on_unique_streams():
    """The fast plan-stream densify (index scatter + gather) agrees with
    the add-based one whenever pairs are unique."""
    rng = np.random.RandomState(10)
    sw, sc, vals = _block_stream(rng, 3, 3, 8, 8, 4, np.float32)
    b = jnp.asarray(rng.randn(32, 128).astype(np.float32))
    fast = ref.densified_block_stream_spmm_unique(sw, sc, vals, b, 3)
    safe = ref.densified_block_stream_spmm(sw, sc, vals, b, 3)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(safe),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_ops_dispatch(impl):
    rng = np.random.RandomState(2)
    sw, sc, vals = _block_stream(rng, 2, 2, 8, 8, 3, np.float32)
    b = jnp.asarray(rng.randn(24, 128).astype(np.float32))
    out = ops.block_stream_spmm(sw, sc, vals, b, num_windows=2, bm=8, bk=8,
                                bn=128, impl=impl)
    expect = ref.ref_block_stream_spmm(sw, sc, vals, b, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5)


def test_zero_value_padding_steps_are_noops():
    """Padding tiles (window 0, block 0, zero values) must not perturb."""
    sw = jnp.asarray(np.array([0, 0], np.int32))
    sc = jnp.asarray(np.array([0, 1], np.int32))
    vals = jnp.asarray(np.stack([np.eye(8, 8), np.zeros((8, 8))]).astype(np.float32))
    b = jnp.asarray(np.random.RandomState(3).randn(16, 128).astype(np.float32))
    out = dense_tile_spmm(sw, sc, vals, b, num_windows=1, bm=8, bk=8, bn=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(b[:8]), rtol=1e-6)


# --- chunk-streamed fringe kernels: the nonzero stream rides SMEM blocks ----
# of STEP entries per grid step, so a fringe past the ~80k-nonzero ceiling
# of a wholly scalar-prefetched stream (1 MiB of SMEM) still fits

OLD_SMEM_CEILING_NNZ = 80_000


def _sorted_fringe(rng, num_rows, k, nnz):
    rows = np.sort(rng.randint(0, num_rows, nnz)).astype(np.int32)
    cols = rng.randint(0, k, nnz).astype(np.int32)
    vals = rng.randn(nnz).astype(np.float32)
    return jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals)


STREAM_CASES = [(nnz, chunk) for nnz in (1, STEP - 1, STEP, 2 * STEP + 7)
                for chunk in (1, 5, 8, 64)]
STREAM_CASES.append((OLD_SMEM_CEILING_NNZ + 4321, 8))


@pytest.mark.parametrize("nnz,chunk", STREAM_CASES)
def test_gather_spmm_streamed_matches_ref(nnz, chunk):
    """Every unroll factor walks whole STEP blocks, ragged tails included."""
    rng = np.random.RandomState(nnz % 97 + chunk)
    num_rows, k = 300, 512
    rows, cols, vals = _sorted_fringe(rng, num_rows, k, nnz)
    b = jnp.asarray(rng.randn(k, 128).astype(np.float32))
    out = gather_spmm(rows, cols, vals, b, num_rows=num_rows, bn=128,
                      chunk=chunk, interpret=True)
    expect = ref.ref_gather_spmm(rows, cols, vals, b, num_rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


KSHARDED_CASES = [(nnz, bucket, chunk)
                  for nnz, bucket in ((700, 3), (700, 8), (3 * STEP + 11, STEP))
                  for chunk in (1, 8, 64)]
KSHARDED_CASES.append((OLD_SMEM_CEILING_NNZ + 4321, STEP, 8))


@pytest.mark.parametrize("nnz,bucket,chunk", KSHARDED_CASES)
def test_gather_spmm_ksharded_streamed_matches_refs(nnz, bucket, chunk):
    """Plan-bucketed streams (STEP buckets: one chunk per grid step) and
    hand-sized buckets (re-padded to STEP inside the wrapper) both match
    the k-blocked oracle and the dense answer."""
    rng = np.random.RandomState(nnz % 89 + bucket + chunk)
    num_rows, k, bk = 200, 1000, 128
    rows, cols, vals = (np.asarray(x) for x in
                        _sorted_fringe(rng, num_rows, k, nnz))
    k_pad = -(-k // bk) * bk
    kb_chunk, kb_rows, kb_cols, kb_vals, _ = bucket_fringe_kblocks(
        rows, cols, vals, k_pad, bk, bucket)
    b = jnp.asarray(rng.randn(k, 128).astype(np.float32))
    args = tuple(jnp.asarray(x) for x in (kb_chunk, kb_rows, kb_cols, kb_vals))
    out = gather_spmm_ksharded(*args, b, num_rows=num_rows, bk=bk, bn=128,
                               chunk=chunk, interpret=True)
    oracle = ref.ref_gather_spmm_kblocked(*args, b, num_rows, bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)
    dense = ref.ref_gather_spmm(jnp.asarray(rows), jnp.asarray(cols),
                                jnp.asarray(vals), b, num_rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nnz,chunk", [(1, 8), (STEP + 3, 1), (STEP + 3, 32),
                                       (OLD_SMEM_CEILING_NNZ + 4321, 32)])
def test_gather_sddmm_streamed_matches_ref(nnz, chunk):
    """The sddmm gather's row/col id blocks stream through SMEM too; dots
    come back in input order whatever the unroll factor."""
    rng = np.random.RandomState(nnz % 83 + chunk)
    m, k, d = 300, 400, 96
    rows = jnp.asarray(rng.randint(0, m, nnz).astype(np.int32))
    cols = jnp.asarray(rng.randint(0, k, nnz).astype(np.int32))
    x = jnp.asarray(rng.randn(m, d).astype(np.float32))
    yt = jnp.asarray(rng.randn(k, d).astype(np.float32))
    out = gather_sddmm(rows, cols, x, yt, chunk=chunk, interpret=True)
    expect = ref.ref_gather_sddmm(rows, cols, x, yt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)
