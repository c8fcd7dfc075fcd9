"""Pure-jnp oracles for the NeutronSparse kernels.

Every Pallas kernel in this package has an oracle here; tests sweep shapes
and dtypes asserting allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ref_spmm_dense(a_dense: jax.Array, b: jax.Array) -> jax.Array:
    """C = A @ B with fp32 accumulation."""
    return jnp.dot(
        a_dense.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def ref_block_stream_spmm(
    step_window: jax.Array,  # (T,) int32 — destination window of each block step
    step_col: jax.Array,     # (T,) int32 — B column-block id of each step
    flat_values: jax.Array,  # (T, bm, bk)
    b: jax.Array,            # (K, N)
    num_windows: int,
) -> jax.Array:
    """Oracle for the matrix-path flat block stream: for each step t,
    out[step_window[t]] += values[t] @ B[step_col[t]*bk : +bk].
    Returns packed (num_windows*bm, N) fp32."""
    t, bm, bk = flat_values.shape
    n = b.shape[1]
    b_blocks = b.reshape(-1, bk, n)  # (K//bk, bk, N)
    gathered = b_blocks[step_col]    # (T, bk, N)
    partial = jnp.einsum(
        "tmk,tkn->tmn",
        flat_values.astype(jnp.float32),
        gathered.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    out = jnp.zeros((num_windows, bm, n), jnp.float32)
    out = out.at[step_window].add(partial)
    return out.reshape(num_windows * bm, n)


def densified_block_stream_spmm(
    step_window: jax.Array,  # (T,) int32
    step_col: jax.Array,     # (T,) int32
    flat_values: jax.Array,  # (T, bm, bk)
    b: jax.Array,            # (K, N) — K a multiple of bk
    num_windows: int,
) -> jax.Array:
    """High-occupancy XLA formulation of the flat block stream.

    The per-tile batched einsum keeps every (bm, bk)x(bk, N) product as its
    own small matmul — far below peak on wide backends.  When most k-blocks
    of each window are active, summing the tile stream back into a
    densified (num_windows*bm, K) core and issuing ONE large matmul trades
    a few wasted zero-block FLOPs for full-rate GEMM throughput.  The
    densify is an *add-based* segment sum over (window, k-block) slots
    (sorted so XLA takes the contiguous-run path), so duplicate pairs —
    impossible in plan-generated streams but legal in hand-built ones —
    accumulate exactly like the streaming/pallas forms instead of
    last-tile-wins.  Plan-driven callers that can statically guarantee
    uniqueness should use :func:`densified_block_stream_spmm_unique`, which
    replaces the tile scatter with a ~4x-faster index-scatter + gather.
    Returns packed (num_windows*bm, N) fp32.
    """
    t, bm, bk = flat_values.shape
    k, n = b.shape
    nkb = k // bk
    lin = step_window * nkb + step_col
    perm = jnp.argsort(lin)
    tiles = jax.ops.segment_sum(
        flat_values.astype(jnp.float32)[perm], lin[perm],
        num_segments=num_windows * nkb, indices_are_sorted=True,
    )
    core = tiles.reshape(num_windows, nkb, bm, bk)
    core = core.transpose(0, 2, 1, 3).reshape(num_windows * bm, k)
    return jnp.dot(
        core, b.astype(jnp.float32), preferred_element_type=jnp.float32
    )


def densified_block_stream_spmm_unique(
    step_window: jax.Array,  # (T,) int32
    step_col: jax.Array,     # (T,) int32
    flat_values: jax.Array,  # (T, bm, bk)
    b: jax.Array,            # (K, N) — K a multiple of bk
    num_windows: int,
) -> jax.Array:
    """Fast-path densified GEMM for streams with unique (window, k-block)
    pairs — the invariant ``prepare()`` guarantees by construction.

    Scatters only the T slot *indices* (cheap), then densifies by GATHERING
    tiles — large XLA tile scatters are far slower than the equivalent
    gather.  With duplicate pairs this silently drops all but one tile per
    slot; use :func:`densified_block_stream_spmm` when uniqueness cannot be
    proven.  Returns packed (num_windows*bm, N) fp32.
    """
    t, bm, bk = flat_values.shape
    k, n = b.shape
    nkb = k // bk
    slot = jnp.full((num_windows, nkb), t, jnp.int32)
    slot = slot.at[step_window, step_col].set(
        jnp.arange(t, dtype=jnp.int32), mode="drop"
    )
    valid = slot < t
    tiles = flat_values.astype(jnp.float32)[jnp.where(valid, slot, 0)]
    tiles = jnp.where(valid[..., None, None], tiles, 0.0)
    core = tiles.transpose(0, 2, 1, 3).reshape(num_windows * bm, k)
    return jnp.dot(
        core, b.astype(jnp.float32), preferred_element_type=jnp.float32
    )


def ref_gather_spmm(
    rows: jax.Array,  # (nnz,) int32, values scatter-add into packed row ids
    cols: jax.Array,  # (nnz,) int32
    vals: jax.Array,  # (nnz,)
    b: jax.Array,     # (K, N)
    num_rows: int,
    chunk: int | None = None,
) -> jax.Array:
    """Oracle for the vector path: out[rows[i]] += vals[i] * B[cols[i]].

    It stays the oracle of every fringe formulation, the degree-bucketed
    :func:`bucketed_gather_spmm` included, and it is the XLA fringe of
    streams that carry no bucket ladder (sharded plans, the delta sidecar,
    Pallas plans demoted to XLA, direct calls): a sort of the row ids and
    a row scatter-add per call.

    ``chunk`` bounds the materialized gather to (chunk, N) per step via a
    scanned accumulate — the XLA analogue of the chunked Pallas kernel's
    grid step — instead of the (nnz, N) one-shot intermediate.
    """
    nnz = rows.shape[0]
    if chunk is None or nnz <= chunk:
        gathered = (
            b[cols].astype(jnp.float32) * vals.astype(jnp.float32)[:, None]
        )
        return jax.ops.segment_sum(gathered, rows, num_segments=num_rows)

    nnz_pad = ((nnz + chunk - 1) // chunk) * chunk
    if nnz_pad != nnz:
        pad = nnz_pad - nnz
        rows = jnp.concatenate([rows, jnp.zeros(pad, rows.dtype)])
        cols = jnp.concatenate([cols, jnp.zeros(pad, cols.dtype)])
        vals = jnp.concatenate([vals, jnp.zeros(pad, vals.dtype)])
    n_chunks = nnz_pad // chunk
    xs = (
        rows.reshape(n_chunks, chunk),
        cols.reshape(n_chunks, chunk),
        vals.reshape(n_chunks, chunk),
    )

    def body(out, x):
        r, c, v = x
        gathered = b[c].astype(jnp.float32) * v.astype(jnp.float32)[:, None]
        return out.at[r].add(gathered), None

    init = jnp.zeros((num_rows, b.shape[1]), jnp.float32)
    out, _ = jax.lax.scan(body, init, xs)
    return out


# bytes of gathered B rows one gather of the bucketed fringe produces at
# most: small enough that XLA's memory-space assignment keeps B itself in
# VMEM (128 MiB on a TPU v5e) across a bucket's gathers, where row gathers
# run several times faster than from HBM
GATHER_BLOCK_BYTES = 16 << 20
# gathered bytes of a whole bucket above which its blocks run in a rolled
# loop, one block live at a time: unrolled, XLA keeps many blocks of a
# bucket alive at once (the fused body at ogbn-products' ladder asked for
# 18.5 GB of the v5e's 15.75 GB), and such a bucket's B rows come from
# HBM anyway
ROLL_BUCKET_BYTES = 16 * GATHER_BLOCK_BYTES
LANES = 128  # the minor tile of a TPU array's layout


def bucketed_gather_spmm(
    cols: jax.Array,  # (slots,) int32, a degree-bucketed stream
    vals: jax.Array,  # (slots,) — zero on padding slots
    b: jax.Array,     # (K, N)
    buckets: tuple,   # ((n_rows_b, width_b), ...) in stream order
) -> jax.Array:
    """XLA fringe over a degree-bucketed (ELL) stream, with no scatter.

    Bucket ``b`` is the stream's next ``n_rows_b * width_b`` slots, stored
    width-major (``plan_ir.bucket_fringe_rows`` lays it out): reshaped to
    ``(width_b, n_rows_b)``, row ``j`` holds width position ``j`` of every
    row of the bucket.  Each bucket gathers its B rows a block of width
    positions at a time (at most ``GATHER_BLOCK_BYTES`` per gather, or one
    position where a single one is larger), multiplies by its values in
    fp32 and sums over the width; the buckets concatenate to the packed
    ``(sum n_rows_b, N)`` output.  A bucket whose gathers total more than
    ``ROLL_BUCKET_BYTES`` runs its blocks in a ``fori_loop`` (a
    power-of-two block that divides the width), so device memory holds one
    block.  The same fp32 products as :func:`ref_gather_spmm`, summed per
    row in another order; padding slots add exact zeros for finite B.

    On a TPU v5e at ogbn-arxiv's ladder (width 128) the whole product ran
    5.91 ms per call this way, against 7.29 ms with whole-bucket gathers
    (B evicted to HBM for some) and 20.50 ms with the segment sum; at
    ogbn-products' ladder (B 1.25 GB, in HBM) 625 ms.
    """
    outs = []
    start = 0
    row_bytes = b.shape[1] * 4
    for n_rows, width in buckets:
        end = start + n_rows * width
        c, v = cols[start:end], vals[start:end]
        if n_rows < LANES:
            # a (width, n_rows) view pads its rows to the lane tile; XLA
            # would hoist that reshape over the whole stream (16x of
            # ogbn-products' 86M slots at n_rows 8) unless the slice stays
            c, v = jax.lax.optimization_barrier((c, v))
        c = c.reshape(width, n_rows)
        v = v.reshape(width, n_rows).astype(jnp.float32)
        step = max(1, GATHER_BLOCK_BYTES // (n_rows * row_bytes))

        def block(cj, vj):
            return jnp.sum(b[cj].astype(jnp.float32) * vj[:, :, None],
                           axis=0)

        if n_rows * width * row_bytes > ROLL_BUCKET_BYTES:
            step = 1 << (step.bit_length() - 1)
            cs = c.reshape(width // step, step, n_rows)
            vs = v.reshape(width // step, step, n_rows)
            acc = jax.lax.fori_loop(
                0, width // step,
                lambda i, acc: acc + block(cs[i], vs[i]),
                jnp.zeros((n_rows, b.shape[1]), jnp.float32))
        else:
            acc = None
            for j in range(0, width, step):
                part = block(c[j:j + step], v[j:j + step])
                acc = part if acc is None else acc + part
        outs.append(acc)
        start = end
    return jnp.concatenate(outs)


def ref_tile_sddmm(
    step_window: jax.Array,  # (T,) int32
    step_col: jax.Array,     # (T,) int32
    xp: jax.Array,           # (num_windows*bm, D) window-gathered X rows
    yp: jax.Array,           # (D, K) — K a multiple of bk
    bm: int,
    bk: int,
) -> jax.Array:
    """Oracle for the SDDMM matrix path: for each active tile t,
    tiles[t] = Xp[step_window[t]*bm : +bm] @ Yp[:, step_col[t]*bk : +bk].
    Returns the fp32 tile stream (T, bm, bk)."""
    d = xp.shape[1]
    xw = xp.reshape(-1, bm, d)[step_window]                  # (T, bm, D)
    yb = yp.reshape(d, -1, bk).transpose(1, 0, 2)[step_col]  # (T, D, bk)
    return jnp.einsum(
        "tmd,tdk->tmk", xw.astype(jnp.float32), yb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def ref_gather_sddmm(
    rows: jax.Array,  # (nnz,) int32 row ids into x
    cols: jax.Array,  # (nnz,) int32 row ids into yt
    x: jax.Array,     # (M, D)
    yt: jax.Array,    # (K, D) — Y pre-transposed
    chunk: int | None = None,
) -> jax.Array:
    """Oracle for the SDDMM vector path: out[i] = x[rows[i]] . yt[cols[i]].

    ``chunk`` bounds the materialized gather to (chunk, D) per step via a
    scanned dot — the XLA analogue of the Pallas kernel's grid step —
    instead of the (nnz, D) one-shot intermediate.
    """
    nnz = rows.shape[0]
    if chunk is None or nnz <= chunk:
        return jnp.sum(
            x[rows].astype(jnp.float32) * yt[cols].astype(jnp.float32),
            axis=-1,
        )

    nnz_pad = ((nnz + chunk - 1) // chunk) * chunk
    if nnz_pad != nnz:
        pad = nnz_pad - nnz
        rows = jnp.concatenate([rows, jnp.zeros(pad, rows.dtype)])
        cols = jnp.concatenate([cols, jnp.zeros(pad, cols.dtype)])
    n_chunks = nnz_pad // chunk
    xs = (rows.reshape(n_chunks, chunk), cols.reshape(n_chunks, chunk))

    def body(_, idx):
        r, c = idx
        return None, jnp.sum(
            x[r].astype(jnp.float32) * yt[c].astype(jnp.float32), axis=-1
        )

    _, out = jax.lax.scan(body, None, xs)
    return out.reshape(-1)[:nnz]


def ref_nm_stream_spmm(
    step_window: jax.Array,  # (T,) int32
    step_col: jax.Array,     # (T,) int32
    nm_values: jax.Array,    # (T, bm, n*gk) fp32 slot-major packed values
    nm_codes: jax.Array,     # (T, bm, gk) int32, 8-bit positions per slot
    b: jax.Array,            # (K, N) — K a multiple of bk
    num_windows: int,
    n_pat: int,
    m_pat: int,
    bk: int,
    tile_chunk: int = 8,
) -> jax.Array:
    """Oracle for the N:M-packed tile stream — FLOP-light gather form.

    Instead of re-expanding to dense (bm, bk) tiles and paying the full
    tile GEMM, each packed value contracts directly against its own B row:
    decode slot positions into global B rows, gather, and batched
    multiply-sum over the q = n*gk packed slots — n/m of the dense-tile
    FLOPs.  ``tile_chunk`` bounds the materialized (tc, bm, q, N) gather
    per scan step, mirroring ref_gather_spmm's chunking.  Returns packed
    (num_windows*bm, N) fp32.
    """
    t, bm, _ = nm_values.shape
    n = b.shape[1]
    gk = bk // m_pat
    q = n_pat * gk
    bf = b.astype(jnp.float32)
    # slot-major local columns: value [t, m, j*gk + g] sits at in-tile
    # column g*m_pat + ((codes[t, m, g] >> 8j) & 0xFF)
    shifts = 8 * jnp.arange(n_pat, dtype=jnp.int32)[:, None]    # (n, 1)
    pos = (nm_codes[:, :, None, :] >> shifts) & 0xFF            # (T, bm, n, gk)
    base = jnp.arange(gk, dtype=jnp.int32) * m_pat              # (gk,)
    cols_local = (pos + base).reshape(t, bm, q)
    bcols = step_col[:, None, None] * bk + cols_local           # (T, bm, q)
    vals = nm_values.astype(jnp.float32)

    tc = max(1, min(tile_chunk, t))
    t_pad = ((t + tc - 1) // tc) * tc
    sw = step_window
    if t_pad != t:  # pad tiles carry zero values into window 0 (inert)
        pad = t_pad - t
        sw = jnp.concatenate([sw, jnp.zeros(pad, sw.dtype)])
        bcols = jnp.concatenate(
            [bcols, jnp.zeros((pad, bm, q), bcols.dtype)]
        )
        vals = jnp.concatenate([vals, jnp.zeros((pad, bm, q), vals.dtype)])
    n_chunks = t_pad // tc
    xs = (
        sw.reshape(n_chunks, tc),
        bcols.reshape(n_chunks, tc, bm, q),
        vals.reshape(n_chunks, tc, bm, q),
    )

    def body(out, x):
        w, bc, v = x
        gathered = bf[bc]                                  # (tc, bm, q, N)
        contrib = jnp.einsum(
            "tmq,tmqn->tmn", v, gathered,
            preferred_element_type=jnp.float32,
        )
        return out.at[w].add(contrib), None

    init = jnp.zeros((num_windows, bm, n), jnp.float32)
    out, _ = jax.lax.scan(body, init, xs)
    return out.reshape(num_windows * bm, n)


def expand_bitmap_tiles(
    bitmap_words: jax.Array,   # (T, bm, ceil(bk/32)) int32 occupancy bits
    bitmap_values: jax.Array,  # (T, bm, row_cap) fp32 packed row values
    bk: int,
) -> jax.Array:
    """Re-expand a bitmap payload to the dense (T, bm, bk) fp32 stream.

    Device-side analogue of core.formats.unpack_bitmap_tiles: rank each
    set bit with a row-wise exclusive cumsum and gather its packed value.
    The arithmetic right shift is sign-safe for bit 31 — only bit 0 of the
    shifted word is read.
    """
    row_cap = bitmap_values.shape[2]
    cols = jnp.arange(bk, dtype=jnp.int32)
    words = bitmap_words[:, :, cols // 32]                 # (T, bm, bk)
    bits = (words >> (cols % 32)) & 1
    rank = jnp.cumsum(bits, axis=-1) - bits                # exclusive prefix
    gathered = jnp.take_along_axis(
        bitmap_values, jnp.clip(rank, 0, row_cap - 1), axis=-1
    )
    return jnp.where(bits == 1, gathered, 0.0)


def ref_bitmap_stream_spmm(
    step_window: jax.Array,    # (T,) int32
    step_col: jax.Array,       # (T,) int32
    bitmap_words: jax.Array,   # (T, bm, ceil(bk/32)) int32
    bitmap_values: jax.Array,  # (T, bm, row_cap) fp32
    b: jax.Array,              # (K, N) — K a multiple of bk
    num_windows: int,
    bk: int,
) -> jax.Array:
    """Oracle for the bitmap-packed tile stream: expand, then the general
    streaming einsum.  Returns packed (num_windows*bm, N) fp32."""
    flat_values = expand_bitmap_tiles(bitmap_words, bitmap_values, bk)
    return ref_block_stream_spmm(
        step_window, step_col, flat_values, b, num_windows
    )


def ref_gather_spmm_kblocked(
    chunk_kb: jax.Array,  # (num_chunks,) int32, chunk -> k-block id
    rows: jax.Array,  # (num_chunks*chunk,) int32, k-bucketed packed row ids
    cols: jax.Array,  # (num_chunks*chunk,) int32, k-block-LOCAL column ids
    vals: jax.Array,  # (num_chunks*chunk,) — zero for bucket-padding entries
    b: jax.Array,     # (K, N)
    num_rows: int,
    bk: int,
) -> jax.Array:
    """Oracle for the K-sharded streaming tier's bucketed layout.

    Consumes exactly the plan-built stream ``gather_spmm_ksharded`` takes:
    chunk c's entries address B rows ``chunk_kb[c]*bk + cols[i]``.  Must
    equal ``ref_gather_spmm`` on the un-bucketed stream (padding entries
    carry value 0).
    """
    num_chunks = chunk_kb.shape[0]
    chunk = rows.shape[0] // num_chunks
    k = b.shape[0]
    k_pad = ((k + bk - 1) // bk) * bk
    if k_pad != k:
        b = jnp.pad(b, ((0, k_pad - k), (0, 0)))
    global_cols = jnp.repeat(chunk_kb, chunk) * bk + cols
    gathered = (
        b[global_cols].astype(jnp.float32) * vals.astype(jnp.float32)[:, None]
    )
    return jax.ops.segment_sum(gathered, rows, num_segments=num_rows)
