"""SDDMM kernels: sampled dense-dense matmul over a plan's sparsity pattern.

SDDMM inverts the SpMM dataflow on the same two engines:

``dense_tile_sddmm`` (matrix engine) — for each active (window, k-block)
tile the plan's stream names, compute the dense product of the gathered X
row panel and the Y column panel:

  grid = (T,)                    T = active tiles (zero padding waste)
  X panel  : Xp[w[t]*bm : , :]       (bm, D)    VMEM (consecutive steps of
                                     one window elide the HBM->VMEM copy —
                                     the same window-major reuse the SpMM
                                     tile kernel exploits)
  Y panel  : Yp^T[c[t]*bk : , :]     (bk, D)    VMEM, streamed per step
  out tile : tiles[t]                (bm, bk)   fp32

The caller extracts the core nonzeros' values from the (T, bm, bk) stream
at their ``UpdateMaps.core_lin`` slots — the exact linear slots
``prepare()`` scattered values into — and places them, with the fringe's,
in COO input order, so the result is layout-compatible with
``dynamic.update_values``.

``gather_sddmm`` (vector engine) — fringe nonzeros bypass the tile path;
each computes one dot product by gathering a row of X and a row of Y^T:

  grid = (ceil(nnz / STEP),)     STEP nonzeros per grid step, their row
                                     ids streamed as SMEM blocks
  X        : (M_pad, D)              resident across the whole grid
  Y^T      : (K_pad, D)              resident across the whole grid
  out      : (STEP/LANES, LANES)     one fp32 dot per slot

Both operand panels stay VMEM-resident (each nonzero addresses arbitrary
rows of each), so the dispatch tier is binary — resident pallas gather or
the XLA reference — selected by ``core.cost_model.select_sddmm_tier``.
Callers go through ``ops.sddmm_block_stream`` / ``ops.sddmm_gather``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gather_spmm import STEP, unroll_factor

LANES = 128  # VPU lane width: gather_sddmm's per-chunk output row


def _pad_axis(a: jax.Array, axis: int, mult: int) -> jax.Array:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _tile_kernel(
    step_window_ref,  # scalar prefetch: (T,) int32
    step_col_ref,     # scalar prefetch: (T,) int32
    x_ref,            # (bm, D) gathered X rows of this step's window
    yt_ref,           # (bk, D) Y^T rows (= Y columns) of this k-block
    o_ref,            # (1, bm, bk) fp32 out tile
):
    # X @ Y^T^T: contract D of both operands (the MXU takes the
    # transposed right operand directly)
    o_ref[0] = jax.lax.dot_general(
        x_ref[...], yt_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "interpret")
)
def dense_tile_sddmm(
    step_window: jax.Array,  # (T,) int32, window-major sorted
    step_col: jax.Array,     # (T,) int32
    xp: jax.Array,           # (num_windows*bm, D) window-gathered X rows
    yp: jax.Array,           # (D, K) — K a multiple of bk
    *,
    bm: int,
    bk: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns the fp32 dense-product tile stream (T, bm, bk).

    Y streams as (bk, D) blocks of Y^T: a (D, bk) block of Y would put
    bk (64 by default) on the lane axis, which Mosaic only blocks in
    multiples of 128.
    """
    t_steps = step_window.shape[0]
    assert xp.shape[0] % bm == 0, (xp.shape, bm)
    assert yp.shape[1] % bk == 0, (yp.shape, bk)
    assert xp.shape[1] == yp.shape[0], (xp.shape, yp.shape)
    xp = _pad_axis(xp, 1, LANES)
    ytp = _pad_axis(yp.T, 1, LANES)
    d = xp.shape[1]

    # physical-ceiling backstop (double-buffered streamed panels + out tile)
    from ..core.cost_model import assert_vmem_claim

    if not interpret:
        assert_vmem_claim(
            (2 * bm * d + 2 * d * bk + bm * bk) * 4,
            f"dense_tile_sddmm tile working set (bm={bm}, bk={bk}, D={d})",
        )

    if not interpret:
        from ..core.cost_model import assert_step_metadata_smem

        assert_step_metadata_smem(t_steps, "dense_tile_sddmm")

    grid = (t_steps,)
    out = pl.pallas_call(
        _tile_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, d), lambda t, w, c: (w[t], 0)),
                pl.BlockSpec((bk, d), lambda t, w, c: (c[t], 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, bk), lambda t, w, c: (t, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (t_steps, bm, bk), jnp.float32
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="dense_tile_sddmm",
    )(step_window, step_col, xp, ytp)
    return out


def _make_gather_kernel(chunk: int):
    def _kernel(
        rows_ref,  # (STEP,) SMEM block of X row ids
        cols_ref,  # (STEP,) SMEM block of Y^T row ids
        x_ref,     # (M_pad, D) resident X panel
        yt_ref,    # (K_pad, D) resident Y^T panel
        o_ref,     # (STEP // LANES, LANES) fp32: one dot per slot
    ):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def out_row(r, carry):
            def dots_into_lanes(u, acc):  # lanes [u*chunk, (u+1)*chunk)
                for v in range(chunk):
                    g = u * chunk + v
                    i = r * LANES + g
                    xr = x_ref[pl.ds(rows_ref[i], 1), :].astype(jnp.float32)
                    yr = yt_ref[pl.ds(cols_ref[i], 1), :].astype(jnp.float32)
                    dot = jnp.sum(xr * yr, axis=1, keepdims=True)  # (1, 1)
                    acc = jnp.where(lane == g, dot, acc)
                return acc

            acc = jax.lax.fori_loop(
                0, LANES // chunk, dots_into_lanes,
                jnp.zeros((1, LANES), jnp.float32),
            )
            o_ref[pl.ds(r, 1), :] = acc
            return carry

        jax.lax.fori_loop(0, STEP // LANES, out_row, 0)

    return _kernel


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def gather_sddmm(
    rows: jax.Array,  # (nnz,) int32 row ids into x
    cols: jax.Array,  # (nnz,) int32 row ids into yt
    x: jax.Array,     # (M, D) dense source operand
    yt: jax.Array,    # (K, D) dense destination operand, pre-transposed
    *,
    chunk: int = 32,
    interpret: bool = False,
) -> jax.Array:
    """Resident-panel SDDMM gather: fp32 dots (nnz,) in input order.

    Claims both full operand panels in VMEM; callers go through
    ``ops.sddmm_gather``, which demotes oversized shapes to the XLA
    reference via ``cost_model.select_sddmm_tier``.  ``chunk`` is the
    unroll factor of the per-lane dot loop.
    """
    nnz = rows.shape[0]
    assert x.shape[1] == yt.shape[1], (x.shape, yt.shape)
    assert chunk >= 1, chunk
    x = _pad_axis(_pad_axis(x, 1, LANES), 0, 8)
    yt = _pad_axis(_pad_axis(yt, 1, LANES), 0, 8)
    d = x.shape[1]

    from ..core.cost_model import assert_vmem_claim, sddmm_resident_bytes

    if not interpret:
        assert_vmem_claim(
            sddmm_resident_bytes(d, x.shape[0], yt.shape[0]),
            f"gather_sddmm resident working set (M={x.shape[0]}, "
            f"K={yt.shape[0]}, D={d})",
        )

    # pad the nonzero stream to a STEP multiple; padding entries address
    # row 0 of each panel and are sliced off below
    rows = _pad_axis(rows, 0, STEP)
    cols = _pad_axis(cols, 0, STEP)
    n_steps = rows.shape[0] // STEP
    rows_per_step = STEP // LANES

    stream = pl.BlockSpec((STEP,), lambda i: (i,), memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _make_gather_kernel(unroll_factor(chunk)),
        grid=(n_steps,),
        in_specs=[
            stream, stream,
            pl.BlockSpec((x.shape[0], d), lambda i: (0, 0)),
            pl.BlockSpec((yt.shape[0], d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_step, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_steps * rows_per_step, LANES), jnp.float32
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="gather_sddmm",
    )(rows, cols, x, yt)
    return out.reshape(-1)[:nnz]
