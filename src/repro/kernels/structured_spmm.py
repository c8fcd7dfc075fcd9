"""Structured-sparsity matrix-engine kernels: N:M and bitmap tile streams.

Two alternative payloads for the flat active-tile stream consumed by
``dense_tile_spmm`` (same grid, same scalar-prefetched metadata, same
resident fp32 accumulator) that stop paying HBM/VMEM bandwidth for the
zeros inside occupied tiles:

- **N:M packed** (NM-SpMM-style): each m-wide group of a tile row keeps at
  most n values.  The payload is a slot-major packed value block
  (bm, n*gk) plus an int32 position-code block (bm, gk) carrying 8 bits
  per slot; the kernel re-expands to the (bm, bk) dense tile *in VMEM*
  with a static n-step select loop — no gather — and feeds the MXU the
  same static dense GEMM.  Payload bytes drop from bm*bk to
  bm*gk*(n + 1).

- **Bitmap packed** (Acc-SpMM-style): per-row occupancy bitmaps
  (bm, ceil(bk/32)) plus a packed value stream (bm, row_cap).  Expansion
  ranks each set bit by its row-wise prefix count (one MXU matmul) and
  selects its value from the packed stream.  General (no pattern assumption); wins when tiles are
  mostly empty but row counts are bounded.

Both expansions cost VPU work proportional to bm*bk per tile, traded
against the payload-byte reduction — the matrix path is bandwidth-bound
exactly when tiles are padding-heavy, which is when these formats are
selected (core/cost_model.select_matrix_format).

MXU mapping: identical to dense_tile_spmm (bm, bn multiples of 128, bk a
multiple of 8, fp32 accumulation, window-resident out block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _repeat_cols(x: jax.Array, reps: int) -> jax.Array:
    """Repeat each column ``reps`` times: (r, c) -> (r, c*reps).

    broadcast_in_dim + reshape (not jnp.repeat) so Mosaic sees a static
    relayout instead of a gather.
    """
    r, c = x.shape
    wide = jax.lax.broadcast_in_dim(x, (r, c, reps), (0, 1))
    return wide.reshape(r, c * reps)


def _nm_expand(vals: jax.Array, codes: jax.Array, n_pat: int, m_pat: int,
               bk: int) -> jax.Array:
    """Re-expand one tile's N:M payload to the dense (bm, bk) fp32 tile.

    ``vals`` is (bm, n*gk) slot-major (slot j at [:, j*gk:(j+1)*gk]);
    ``codes`` is (bm, gk) with slot j's in-group position in bits
    [8j, 8j+8).  Empty slots carry (position 0, value 0.0) and contribute
    an exact 0.  2D ops only; the slot loop is a static python unroll
    (n_pat <= 4).
    """
    bm = vals.shape[0]
    gk = bk // m_pat
    offs = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1) % m_pat
    a = jnp.zeros((bm, bk), jnp.float32)
    for j in range(n_pat):
        pos_j = (codes >> (8 * j)) & 0xFF              # (bm, gk)
        val_j = vals[:, j * gk:(j + 1) * gk]           # (bm, gk)
        pos_rep = _repeat_cols(pos_j, m_pat)           # (bm, bk)
        val_rep = _repeat_cols(val_j, m_pat)
        a = a + jnp.where(pos_rep == offs, val_rep, 0.0)
    return a


def _bitmap_expand(words: jax.Array, packed: jax.Array, bk: int) -> jax.Array:
    """Re-expand one tile's bitmap payload to the dense (bm, bk) fp32 tile.

    ``words`` is (bm, ceil(bk/32)) int32 occupancy bits (column c of the
    row lives at bit c%32 of word c//32 — arithmetic shift is sign-safe
    for bit 31 since only bit 0 of the shifted value is read); ``packed``
    is (bm, row_cap) per-row nonzeros in column order.  Each set bit's
    rank (its row-wise exclusive prefix count) selects its packed value.
    Mosaic lowers neither cumsum nor a lane gather, so the prefix count is
    one MXU matmul against a strictly upper-triangular ones matrix (exact:
    0/1 operands, counts <= bk in fp32) and the gather a static select
    over the row_cap slots.
    """
    bm, n_words = words.shape
    row_cap = packed.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
    word_rep = _repeat_cols(words, 32)[:, :bk]         # (bm, bk)
    bits = (word_rep >> (cols % 32)) & 1
    upper = (jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
             < jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1))
    rank = jnp.dot(bits.astype(jnp.float32), upper.astype(jnp.float32),
                   preferred_element_type=jnp.float32)  # exclusive prefix
    rank = jnp.where(bits == 1, rank, -1.0)
    a = jnp.zeros((bm, bk), jnp.float32)
    for j in range(row_cap):
        a = jnp.where(rank == float(j), packed[:, j:j + 1], a)
    return a


def _nm_kernel(n_pat, m_pat, bk, step_window_ref, step_col_ref,
               vals_ref, codes_ref, b_ref, o_ref):
    t = pl.program_id(1)
    first = jnp.logical_or(
        t == 0, step_window_ref[t] != step_window_ref[jnp.maximum(t - 1, 0)]
    )

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = _nm_expand(vals_ref[0], codes_ref[0], n_pat, m_pat, bk)
    o_ref[...] += jnp.dot(a, b_ref[...], preferred_element_type=jnp.float32)


def _bitmap_kernel(bk, step_window_ref, step_col_ref,
                   words_ref, vals_ref, b_ref, o_ref):
    t = pl.program_id(1)
    first = jnp.logical_or(
        t == 0, step_window_ref[t] != step_window_ref[jnp.maximum(t - 1, 0)]
    )

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = _bitmap_expand(words_ref[0], vals_ref[0], bk)
    o_ref[...] += jnp.dot(a, b_ref[...], preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("num_windows", "bm", "bk", "bn", "n_pat", "m_pat",
                     "interpret"),
)
def nm_tile_spmm(
    step_window: jax.Array,  # (T,) int32, window-major sorted
    step_col: jax.Array,     # (T,) int32
    nm_values: jax.Array,    # (T, bm, n*gk) fp32 slot-major packed values
    nm_codes: jax.Array,     # (T, bm, gk) int32 position codes
    b: jax.Array,            # (K, N) — K a multiple of bk, N of bn
    *,
    num_windows: int,
    bm: int,
    bk: int,
    bn: int = 256,
    n_pat: int,
    m_pat: int,
    interpret: bool = False,
) -> jax.Array:
    """N:M-packed tile-stream SpMM; returns packed fp32 (num_windows*bm, N)."""
    t_steps = nm_values.shape[0]
    k, n = b.shape
    assert k % bk == 0 and n % bn == 0, (k, bk, n, bn)
    assert bk % m_pat == 0, (bk, m_pat)
    gk = bk // m_pat

    if not interpret:
        from ..core.cost_model import assert_step_metadata_smem

        assert_step_metadata_smem(t_steps, "nm_tile_spmm")

    grid = (n // bn, t_steps)
    out = pl.pallas_call(
        functools.partial(_nm_kernel, n_pat, m_pat, bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, n_pat * gk), lambda j, t, w, c: (t, 0, 0)),
                pl.BlockSpec((1, bm, gk), lambda j, t, w, c: (t, 0, 0)),
                pl.BlockSpec((bk, bn), lambda j, t, w, c: (c[t], j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda j, t, w, c: (w[t], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_windows * bm, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="nm_tile_spmm",
    )(step_window, step_col, nm_values, nm_codes, b)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("num_windows", "bm", "bk", "bn", "row_cap", "interpret"),
)
def bitmap_tile_spmm(
    step_window: jax.Array,    # (T,) int32, window-major sorted
    step_col: jax.Array,       # (T,) int32
    bitmap_words: jax.Array,   # (T, bm, ceil(bk/32)) int32 occupancy bits
    bitmap_values: jax.Array,  # (T, bm, row_cap) fp32 packed row values
    b: jax.Array,              # (K, N) — K a multiple of bk, N of bn
    *,
    num_windows: int,
    bm: int,
    bk: int,
    bn: int = 256,
    row_cap: int,
    interpret: bool = False,
) -> jax.Array:
    """Bitmap-packed tile-stream SpMM; returns packed fp32 (num_windows*bm, N)."""
    t_steps = bitmap_words.shape[0]
    k, n = b.shape
    assert k % bk == 0 and n % bn == 0, (k, bk, n, bn)
    n_words = (bk + 31) // 32
    assert bitmap_words.shape[2] == n_words, (bitmap_words.shape, bk)
    assert bitmap_values.shape[2] == row_cap, (bitmap_values.shape, row_cap)

    if not interpret:
        from ..core.cost_model import assert_step_metadata_smem

        assert_step_metadata_smem(t_steps, "bitmap_tile_spmm")

    grid = (n // bn, t_steps)
    out = pl.pallas_call(
        functools.partial(_bitmap_kernel, bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, n_words), lambda j, t, w, c: (t, 0, 0)),
                pl.BlockSpec((1, bm, row_cap), lambda j, t, w, c: (t, 0, 0)),
                pl.BlockSpec((bk, bn), lambda j, t, w, c: (c[t], j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda j, t, w, c: (w[t], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_windows * bm, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="bitmap_tile_spmm",
    )(step_window, step_col, bitmap_words, bitmap_values, b)
    return out
