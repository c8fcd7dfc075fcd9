"""The XLA reference kernels of ``kernels/ref.py`` against float64 dense
products, and the chunk helpers that size the fringe kernels' steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats
from repro.kernels import ops, ref
from repro.kernels.gather_spmm import STEP, unroll_factor

# one compile per case, instead of one per eager op
_nm_stream = jax.jit(ref.ref_nm_stream_spmm, static_argnums=(5, 6, 7, 8),
                     static_argnames=("tile_chunk",))
_expand = jax.jit(ref.expand_bitmap_tiles, static_argnums=(2,))
_bitmap_stream_spmm = jax.jit(ref.ref_bitmap_stream_spmm,
                              static_argnums=(5, 6))


def _tile_stream(rng, t, bm, bk, num_windows, num_kblocks, density=0.4):
    """A random flat (T, bm, bk) tile stream with its step metadata."""
    flat = (rng.rand(t, bm, bk) < density) * rng.randn(t, bm, bk)
    sw = rng.randint(0, num_windows, t).astype(np.int32)
    sc = rng.randint(0, num_kblocks, t).astype(np.int32)
    return flat.astype(np.float32), sw, sc


def _stream_product(flat, sw, sc, b, num_windows):
    """float64 out[w] += flat[t] @ B[sc[t] block] for every step t."""
    t, bm, bk = flat.shape
    out = np.zeros((num_windows, bm, b.shape[1]))
    for i in range(t):
        blk = b[sc[i] * bk:(sc[i] + 1) * bk].astype(np.float64)
        out[sw[i]] += flat[i].astype(np.float64) @ blk
    return out.reshape(num_windows * bm, -1)


def _close(got, want, tol=1e-5):
    got = np.asarray(got, np.float64)
    scale = max(1.0, float(np.abs(want).max()))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * scale


@pytest.mark.parametrize("m, k, n, dtype", [
    (1, 40, 16, np.float32),
    (40, 1, 16, np.float32),
    (33, 47, 5, np.float32),
    (16, 64, 8, jnp.bfloat16),
], ids=["one_row", "one_col", "random", "bf16_inputs"])
def test_ref_spmm_dense_matches_float64(m, k, n, dtype):
    rng = np.random.RandomState(m + k)
    a = jnp.asarray(rng.randn(m, k), dtype)
    b = jnp.asarray(rng.randn(k, n), dtype)
    got = ref.ref_spmm_dense(a, b)
    assert got.dtype == jnp.float32  # fp32 accumulation, whatever the input
    want = (np.asarray(a, np.float64) @ np.asarray(b, np.float64))
    _close(got, want)


@pytest.mark.parametrize("t, d", [(1, 3), (9, 17)])
def test_ref_tile_sddmm_matches_float64(t, d):
    rng = np.random.RandomState(t)
    bm, bk, nw, nkb = 8, 16, 3, 4
    xp = rng.randn(nw * bm, d).astype(np.float32)
    yp = rng.randn(d, nkb * bk).astype(np.float32)
    sw = rng.randint(0, nw, t).astype(np.int32)
    sc = rng.randint(0, nkb, t).astype(np.int32)
    got = ref.ref_tile_sddmm(jnp.asarray(sw), jnp.asarray(sc),
                             jnp.asarray(xp), jnp.asarray(yp), bm, bk)
    x64, y64 = xp.astype(np.float64), yp.astype(np.float64)
    want = np.stack([x64[w * bm:(w + 1) * bm] @ y64[:, c * bk:(c + 1) * bk]
                     for w, c in zip(sw, sc)])
    _close(got, want)


@pytest.mark.parametrize("n_pat, m_pat, tile_chunk", [
    (2, 4, 2),   # 5 tiles in chunks of 2: the last chunk is padded
    (1, 4, 8),   # one chunk larger than the stream
    (4, 8, 1),
])
def test_ref_nm_stream_spmm_matches_float64(n_pat, m_pat, tile_chunk):
    rng = np.random.RandomState(n_pat * 10 + m_pat)
    t, bm, bk, nw, nkb, n = 5, 8, 16, 3, 4, 12
    flat, sw, sc = _tile_stream(rng, t, bm, bk, nw, nkb, density=1.0)
    # keep the n_pat largest magnitudes of every group of m_pat columns
    g = flat.reshape(t, bm, bk // m_pat, m_pat)
    drop = np.argsort(np.abs(g), axis=-1)[..., :m_pat - n_pat]
    np.put_along_axis(g, drop, 0.0, axis=-1)
    flat = g.reshape(t, bm, bk)
    nm_values, nm_codes = formats.pack_nm_tiles(flat, n_pat, m_pat)
    b = rng.randn(nkb * bk, n).astype(np.float32)
    got = _nm_stream(
        jnp.asarray(sw), jnp.asarray(sc), jnp.asarray(nm_values),
        jnp.asarray(nm_codes), jnp.asarray(b), nw, n_pat, m_pat, bk,
        tile_chunk=tile_chunk)
    _close(got, _stream_product(flat, sw, sc, b, nw))


def _bitmap_stream(seed, bk):
    rng = np.random.RandomState(seed)
    flat, sw, sc = _tile_stream(rng, 6, 8, bk, 3, 2, density=0.3)
    # the sign bit of every 32-bit word is set somewhere
    flat[0, 0, 31::32] = 1.5
    flat[1, 7, :] = rng.randn(bk)  # a full row
    flat[2] = 0.0                  # an empty tile
    return flat, sw, sc


@pytest.mark.parametrize("bk", [32, 64, 48])
def test_expand_bitmap_tiles_inverts_packing(bk):
    flat, _, _ = _bitmap_stream(bk, bk)
    words, values, row_cap = formats.pack_bitmap_tiles(flat)
    assert values.shape[-1] == row_cap
    got = np.asarray(_expand(
        jnp.asarray(words), jnp.asarray(values), bk))
    np.testing.assert_array_equal(got, flat)
    np.testing.assert_array_equal(
        got, formats.unpack_bitmap_tiles(words, values, bk))


@pytest.mark.parametrize("bk", [32, 64])
def test_ref_bitmap_stream_spmm_matches_float64(bk):
    flat, sw, sc = _bitmap_stream(bk + 1, bk)
    words, values, _ = formats.pack_bitmap_tiles(flat)
    b = np.random.RandomState(3).randn(2 * bk, 20).astype(np.float32)
    got = _bitmap_stream_spmm(
        jnp.asarray(sw), jnp.asarray(sc), jnp.asarray(words),
        jnp.asarray(values), jnp.asarray(b), 3, bk)
    _close(got, _stream_product(flat, sw, sc, b, 3))


# --- chunk helpers ---------------------------------------------------------


@pytest.mark.parametrize("n, want", [
    (0, 1), (1, 1), (2, 2), (3, 4), (8, 8), (9, 16),
    (1023, 1024), (1024, 1024), (1025, 2048),
])
def test_pow2_at_least(n, want):
    assert ops.pow2_at_least(n) == want


@pytest.mark.parametrize("chunk, want", [
    (None, 8), (0, 8), (1, 1), (7, 7), (64, 64), (65, 64), (4096, 64),
])
def test_effective_chunk_defaults_and_clamps(chunk, want):
    assert ops.effective_chunk(chunk) == want


@pytest.mark.parametrize("chunk, want", [
    (0, 1), (1, 1), (2, 2), (3, 2), (63, 32), (64, 64), (65, 64),
    (1024, 64),
])
def test_unroll_factor_is_a_pow2_that_divides_a_step(chunk, want):
    u = unroll_factor(chunk)
    assert u == want
    assert STEP % u == 0 and u <= max(1, chunk)
