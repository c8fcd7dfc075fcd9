"""Vector-engine ("AIV") path: chunked sorted-COO gather-accumulate kernels.

The sparse fringes execute in the paper's AIV style: for each nonzero,
Gather the B row addressed by its column index, scale by the value, and
accumulate into the output row (ScatterAdd).  TPU adaptation — two kernels
sharing one chunk-accumulate body, chosen by the VMEM dispatch tier
(core/cost_model.select_fringe_tier):

``gather_spmm`` (tier "resident")

  grid = (N/bn, ceil(nnz/STEP))  STEP nonzeros per grid step
  rows/cols/vals : (STEP,) SMEM blocks, streamed per step
  B        : B[:, j*bn : ]           (K, bn)        resident across the whole
                                     chunk loop for one n-block (loaded once)
  out      : out[:, j*bn : ]         (num_rows, bn) resident fp32 accumulator,
                                     written back once per n-block

``gather_spmm_ksharded`` (tier "ksharded") — the reduction dimension is
tiled so arbitrarily large K streams through VMEM (Acc-SpMM/FlashSparse
style k-dimension tiling under the tile-based execution model):

  grid = (N/bn, num_chunks)      chunk c owns STEP nonzeros of ONE k-block
  rows/cols/vals : (STEP,) SMEM blocks, streamed per step
  B        : B[kb[c]*bk : , j*bn : ]  (bk, bn)      streamed per chunk step
                                     (double-buffered by the grid pipeline;
                                     consecutive chunks of one k-block elide
                                     the copy)
  out      : out[:, j*bn : ]         (num_rows, bn) resident fp32 accumulator

The caller buckets nonzeros by k-block at plan-build time (column ids become
k-block-local, each bucket padded to a chunk multiple with zero-value
entries) and scalar-prefetches ``chunk_kb`` mapping chunk -> k-block (the
only whole-stream SMEM operand: the B index map reads it); empty
k-blocks get no chunks at all, so fully inactive B slices are never fetched.

Each grid step walks its STEP nonzeros in sub-chunks of G = ``chunk``
(a power of two), each an unrolled, *segment-boundary-aware* accumulate: contributions of a run of equal row ids are summed in a
register accumulator and flushed to the VMEM output row only when the row id
changes (the COO is row-sorted within a bucket, so runs are contiguous).
Partial sums of a row split across k-blocks merge in the resident output
block via the end-of-chunk flush read-modify-write.

Vector-tile merging (paper §7): entries are (row, col)-sorted, so repeated
columns within a row reuse the resident B block, and bn is a multiple of the
128-lane VPU width so every lane is active.

VMEM working sets: (K + num_rows_pad) * bn * 4 bytes resident,
(2*bk + num_rows_pad) * bn * 4 streaming.  Callers go through
``ops.fringe_spmm``, which picks the tier from the VMEM budget instead of
hard-erroring on large fringes.

Outputs are *packed* fringe rows (the caller gathers them into original row
ids via the plan's inverse row map).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Nonzeros per grid step: one (8, 128) 32-bit tile of each SMEM-blocked
# stream.  Mosaic blocks a rank-1 operand only in multiples of 128, and a
# block of exactly one tile wastes no HBM layout padding.
STEP = 1024


def unroll_factor(chunk: int) -> int:
    """Largest power of two <= ``chunk`` (capped at 64): the sub-chunk the
    kernels unroll, which therefore always divides a ``STEP`` block."""
    return 1 << (max(1, min(int(chunk), 64)).bit_length() - 1)


def _stream_spec(block: int, index_map) -> pl.BlockSpec:
    """One ``block``-nonzero slice of a stream per grid step, in SMEM.

    Streaming the slice (instead of scalar-prefetching the whole stream)
    keeps the SMEM claim at two blocks per stream whatever the fringe's
    size; a whole prefetched stream overflows the 1 MiB SMEM near 80k
    nonzeros.
    """
    return pl.BlockSpec((block,), index_map, memory_space=pltpu.SMEM)


def _accumulate_chunk(rows_ref, cols_ref, vals_ref, b_ref, o_ref, base, chunk):
    """Unrolled segment-boundary-aware accumulate of one G-nonzero chunk.

    Reads entries ``[base, base + chunk)`` of this step's SMEM stream
    blocks.  Column ids address rows of ``b_ref`` directly (global for the
    resident kernel, k-block-local for the K-sharded one).
    """

    def contrib(g):
        brow = b_ref[pl.ds(cols_ref[base + g], 1), :]
        return vals_ref[base + g] * brow.astype(jnp.float32)

    def flush(row, acc):
        o_ref[pl.ds(row, 1), :] = o_ref[pl.ds(row, 1), :] + acc

    cur_row = rows_ref[base]
    acc = contrib(0)
    for g in range(1, chunk):
        r = rows_ref[base + g]
        same = r == cur_row

        @pl.when(jnp.logical_not(same))
        def _flush(acc=acc, cur_row=cur_row):
            flush(cur_row, acc)

        acc = jnp.where(same, acc + contrib(g), contrib(g))
        cur_row = r
    flush(cur_row, acc)


def _make_kernel(block: int, chunk: int):
    """Kernel over one ``block``-nonzero step, in ``chunk``-sized unrolls.

    Scalar-prefetch refs (the K-sharded tier's ``chunk_kb``), if any, lead
    the argument list and are read by the index maps only.
    """

    def _kernel(*refs):
        rows_ref, cols_ref, vals_ref, b_ref, o_ref = refs[-5:]

        @pl.when(pl.program_id(1) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        def sub_chunk(s, carry):
            _accumulate_chunk(rows_ref, cols_ref, vals_ref, b_ref, o_ref,
                              s * chunk, chunk)
            return carry

        jax.lax.fori_loop(0, block // chunk, sub_chunk, 0)

    return _kernel


def _pad_stream(x: jax.Array, size: int, fill) -> jax.Array:
    pad = size - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.broadcast_to(fill, (pad,)).astype(x.dtype)])


@functools.partial(
    jax.jit, static_argnames=("num_rows", "bn", "chunk", "interpret")
)
def gather_spmm(
    rows: jax.Array,  # (nnz,) int32, row-sorted, packed row ids [0, num_rows)
    cols: jax.Array,  # (nnz,) int32
    vals: jax.Array,  # (nnz,)
    b: jax.Array,     # (K, N) — N a multiple of bn
    *,
    num_rows: int,
    bn: int = 256,
    chunk: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Resident-panel tier: returns packed fp32 output (num_rows, N).

    Claims (K + num_rows_pad) * bn * 4 bytes of VMEM; use
    ``ops.fringe_spmm`` (or ``gather_spmm_ksharded`` directly) when that
    exceeds the budget.
    """
    nnz = rows.shape[0]
    k, n = b.shape
    assert n % bn == 0, (n, bn)
    assert chunk >= 1, chunk
    # direct-call guard against the PHYSICAL 16 MB VMEM ceiling only — a
    # raw call past it would die as an opaque Mosaic allocation failure.
    # Soft-budget policy (default 12 MB, user-overridable) belongs to the
    # tier dispatch in ops.fringe_spmm / cost_model.select_fringe_tier,
    # which may legitimately route near-ceiling claims here.  The byte
    # estimate is the cost model's own (one formula for tier selection and
    # this guard — they cannot drift); lazy import because core imports
    # kernels at module-init time.
    from ..core.cost_model import assert_vmem_claim, fringe_resident_bytes

    if not interpret:
        assert_vmem_claim(
            fringe_resident_bytes(k, num_rows, bn),
            f"gather_spmm resident working set (K={k}, rows={num_rows}, "
            f"bn={bn}, fp32)",
        )

    # pad the nonzero stream to a STEP multiple; padding entries replicate
    # the last row id with value 0 so they accumulate nothing
    nnz_pad = pl.cdiv(nnz, STEP) * STEP
    rows = _pad_stream(rows, nnz_pad, rows[-1])
    cols = _pad_stream(cols, nnz_pad, 0)
    vals = _pad_stream(vals.astype(jnp.float32), nnz_pad, 0.0)
    # pad packed output rows to the fp32 sublane multiple
    nr_pad = max(8, ((num_rows + 7) // 8) * 8)

    stream = _stream_spec(STEP, lambda j, i: (i,))
    out = pl.pallas_call(
        _make_kernel(STEP, unroll_factor(chunk)),
        grid=(n // bn, nnz_pad // STEP),
        in_specs=[
            stream, stream, stream,
            pl.BlockSpec((k, bn), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((nr_pad, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((nr_pad, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="gather_spmm",
    )(rows, cols, vals, b)
    return out[:num_rows]


def _pad_chunks(x: jax.Array, num_chunks: int, block: int, edge: bool):
    """Re-pad each chunk of a bucketed stream to ``block`` entries.

    ``edge`` repeats the chunk's last entry (row ids: keeps the row run
    unbroken), otherwise pads with zeros (columns, values: inert).
    """
    x = x.reshape(num_chunks, -1)
    widths = ((0, 0), (0, block - x.shape[1]))
    return jnp.pad(x, widths, mode="edge" if edge else "constant").reshape(-1)


@functools.partial(
    jax.jit, static_argnames=("num_rows", "bk", "bn", "chunk", "interpret")
)
def gather_spmm_ksharded(
    chunk_kb: jax.Array,  # (num_chunks,) int32, chunk -> k-block id
    rows: jax.Array,  # (num_chunks*chunk,) int32, k-bucketed packed row ids
    cols: jax.Array,  # (num_chunks*chunk,) int32, k-block-LOCAL column ids
    vals: jax.Array,  # (num_chunks*chunk,) — zero for bucket-padding entries
    b: jax.Array,     # (K, N) — N a multiple of bn
    *,
    num_rows: int,
    bk: int,
    bn: int = 256,
    chunk: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """K-sharded streaming tier: returns packed fp32 output (num_rows, N).

    The nonzero stream must be the plan-built k-bucketed layout: sorted by
    (k-block, row, col), each bucket padded to a multiple of the bucket
    chunk (derived as ``rows.size // chunk_kb.size``), columns local to
    their k-block.  Plan builders bucket in ``STEP`` chunks, so each chunk
    is exactly one grid step; other chunk sizes are re-padded to a STEP
    multiple here.  ``chunk`` is the unroll factor.  Only a (bk, bn) slice
    of B is VMEM-resident per grid step, so K is unbounded by the VMEM
    budget.
    """
    num_chunks = chunk_kb.shape[0]
    assert num_chunks >= 1 and rows.shape[0] % num_chunks == 0, (
        rows.shape, chunk_kb.shape
    )
    bucket = rows.shape[0] // num_chunks
    block = pl.cdiv(bucket, STEP) * STEP
    vals = vals.astype(jnp.float32)
    if block != bucket:
        rows = _pad_chunks(rows, num_chunks, block, edge=True)
        cols = _pad_chunks(cols, num_chunks, block, edge=False)
        vals = _pad_chunks(vals, num_chunks, block, edge=False)
    k, n = b.shape
    assert n % bn == 0, (n, bn)
    k_pad = ((k + bk - 1) // bk) * bk
    if k_pad != k:
        b = jnp.pad(b, ((0, k_pad - k), (0, 0)))
    nr_pad = max(8, ((num_rows + 7) // 8) * 8)
    if not interpret:
        # chunk_kb is scalar-prefetched whole (the B index map reads it)
        from ..core.cost_model import assert_smem_claim, stream_smem_bytes

        assert_smem_claim(
            stream_smem_bytes(3) + 4 * num_chunks,
            f"gather_spmm_ksharded chunk map ({num_chunks} chunks)",
        )

    stream = _stream_spec(block, lambda j, i, kb: (i,))
    out = pl.pallas_call(
        _make_kernel(block, unroll_factor(chunk)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn, num_chunks),
            in_specs=[
                stream, stream, stream,
                pl.BlockSpec((bk, bn), lambda j, i, kb: (kb[i], j)),
            ],
            out_specs=pl.BlockSpec((nr_pad, bn), lambda j, i, kb: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nr_pad, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="gather_spmm_ksharded",
    )(chunk_kb, rows, cols, vals, b)
    return out[:num_rows]
