"""Matrix-engine ("AIC") path: flat-block-stream SpMM Pallas TPU kernel.

The dense core of A is packed (core/formats.BlockELL) and flattened into a
stream of active (window, k-block) tiles — the tile stream the paper's AIC
consumes.  The kernel walks the stream with scalar-prefetched metadata:

  grid = (N/bn, T)            T = number of active tiles (zero padding waste)
  A tile t   : flat_values[t]                       (bm, bk)   VMEM
  B block    : B[step_col[t]*bk : , j*bn : ]        (bk, bn)   VMEM
  out block  : out[step_window[t]*bm : , j*bn : ]   (bm, bn)   VMEM (fp32)

TPU-native reuse properties (paper §6.2 adapted):
- steps of one window are consecutive, so the fp32 out block stays resident
  in VMEM across the window's whole K-reduction (the L0C analogue) and is
  written back once per (window, n-block) — FixPipe-aligned since bn is a
  multiple of the 128-lane width;
- the reuse planner orders windows cluster-major, so consecutive steps often
  address the same B block and Pallas elides the HBM->VMEM copy — the
  shared-L2 residency analogue;
- the Pallas grid pipeline double-buffers tile fetches (paper §7).

MXU mapping: jnp.dot on (bm, bk)x(bk, bn) with fp32 accumulation; bm, bn
multiples of 128, bk a multiple of 8 (defaults from
core/reuse.select_tile_shape).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    step_window_ref,  # scalar prefetch: (T,) int32
    step_col_ref,     # scalar prefetch: (T,) int32
    a_ref,            # (1, bm, bk) block of flat_values
    b_ref,            # (bk, bn) block of B
    o_ref,            # (bm, bn) fp32 out block
):
    t = pl.program_id(1)

    # first step of a window: reset the resident accumulator
    first = jnp.logical_or(
        t == 0, step_window_ref[t] != step_window_ref[jnp.maximum(t - 1, 0)]
    )

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[0], b_ref[...], preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_windows", "bm", "bk", "bn", "interpret"),
)
def dense_tile_spmm(
    step_window: jax.Array,  # (T,) int32, window-major sorted
    step_col: jax.Array,     # (T,) int32
    flat_values: jax.Array,  # (T, bm, bk)
    b: jax.Array,            # (K, N) — K a multiple of bk, N of bn
    *,
    num_windows: int,
    bm: int,
    bk: int,
    bn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns packed fp32 output (num_windows*bm, N)."""
    t_steps = flat_values.shape[0]
    k, n = b.shape
    assert k % bk == 0 and n % bn == 0, (k, bk, n, bn)

    if not interpret:
        from ..core.cost_model import assert_step_metadata_smem

        assert_step_metadata_smem(t_steps, "dense_tile_spmm")

    grid = (n // bn, t_steps)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda j, t, w, c: (t, 0, 0)),
                pl.BlockSpec((bk, bn), lambda j, t, w, c: (c[t], j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda j, t, w, c: (w[t], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_windows * bm, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="dense_tile_spmm",
    )(step_window, step_col, flat_values, b)
    return out
