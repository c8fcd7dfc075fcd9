"""Jitted wrappers for the NeutronSparse kernels + XLA fallbacks.

``impl`` selection:
- ``pallas``           — Mosaic-lowered TPU kernels (target hardware)
- ``pallas_interpret`` — same kernel bodies executed in interpret mode
                         (CPU-validatable; used by tests/benchmarks here)
- ``xla``              — pure-jnp formulations (identical math; used
                         where Mosaic cannot lower, e.g. simulated CPU
                         meshes)

Layering: this module is the *dispatch-tier* layer — it consumes raw
arrays only (plan leaves arrive via the executor pipeline in
``repro.exec``; the leaf layout itself is owned by ``core.plan_ir``).  It
imports nothing above the kernels except ``core.cost_model`` (the
tier="auto" fallback), the one sanctioned upward edge in
``tools/check_layers.py``.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from . import ref
from .dense_tile_spmm import dense_tile_spmm
from .gather_spmm import STEP as FRINGE_STEP  # noqa: F401  (plan builders)
from .gather_spmm import gather_spmm, gather_spmm_ksharded
from .sddmm import dense_tile_sddmm, gather_sddmm
from .structured_spmm import bitmap_tile_spmm, nm_tile_spmm

Impl = Literal["pallas", "pallas_interpret", "xla"]
FringeTier = Literal["auto", "resident", "ksharded", "xla"]
SddmmTier = Literal["auto", "resident", "xla"]


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (shared by the serving batch buckets and
    the dynamic delta-sidecar capacity growth — both bound retraces by
    quantizing runtime-varying sizes to powers of two)."""
    b = 1
    while b < n:
        b *= 2
    return b


def effective_chunk(chunk: int | None) -> int:
    """Unroll factor the pallas fringe kernels use for ``chunk``.

    The kernels walk each ``FRINGE_STEP``-nonzero grid step in unrolled
    sub-chunks, so large XLA-oriented values are clamped to a
    compile-friendly unroll factor.  Plan builders bucket the k-sharded
    stream in ``FRINGE_STEP`` chunks, independent of this value.
    """
    return min(chunk or 8, 64)


# occupancy (active tiles / total slots) above which the xla impl switches
# from the streamed per-tile form to one densified GEMM; overridable per
# call (the tuner measures the actual crossover per device)
DENSIFY_OCCUPANCY = 0.25


@functools.partial(
    jax.jit,
    static_argnames=("num_windows", "bm", "bk", "bn", "impl", "assume_unique",
                     "densify_occupancy"),
)
def block_stream_spmm(
    step_window: jax.Array,
    step_col: jax.Array,
    flat_values: jax.Array,
    b: jax.Array,
    *,
    num_windows: int,
    bm: int,
    bk: int,
    bn: int = 256,
    impl: Impl = "xla",
    assume_unique: bool = False,
    densify_occupancy: float | None = None,
) -> jax.Array:
    """Matrix-engine path; returns packed (num_windows*bm, N) fp32.

    Above the occupancy threshold the xla impl dispatches to a densified
    GEMM.  The default add-based densify accumulates duplicate
    (window, k-block) pairs exactly like the streaming/pallas forms, so
    hand-built streams are safe on either side of the threshold;
    ``assume_unique=True`` (a static guarantee plan-driven callers can
    make — ``prepare()`` emits one tile per pair by construction) selects
    the ~4x-faster index-scatter + gather densify instead.
    ``densify_occupancy`` overrides the module default crossover (the
    executor pipeline passes the tuner's measured value when autotuning).
    """
    if b.ndim != 2:
        raise ValueError(
            f"block_stream_spmm expects a rank-2 (K, N) operand, got shape "
            f"{tuple(b.shape)}; batched RHS panels go through the executor "
            "pipeline (repro.exec), which vmaps the fused body per path"
        )
    if impl == "xla":
        # static occupancy = active tiles / total (window, k-block) slots.
        # Dense-ish cores run ~10-20x faster as one densified GEMM than as
        # a batched per-tile einsum; keep the streaming form only when the
        # zero-block FLOP waste would dominate (stream cost scales with
        # occupancy, the densified GEMM is occupancy-independent) or the
        # dense core would be unreasonably large in absolute terms.
        t_steps = flat_values.shape[0]
        slots = max(num_windows * (b.shape[0] // bk), 1)
        core_elems = num_windows * bm * b.shape[0]
        occ_threshold = (
            DENSIFY_OCCUPANCY if densify_occupancy is None
            else float(densify_occupancy)
        )
        if (num_windows and t_steps / slots >= occ_threshold
                and core_elems <= 2 ** 26):
            densify = (
                ref.densified_block_stream_spmm_unique
                if assume_unique else ref.densified_block_stream_spmm
            )
            return densify(
                step_window, step_col, flat_values, b, num_windows
            )
        return ref.ref_block_stream_spmm(
            step_window, step_col, flat_values, b, num_windows
        )
    return dense_tile_spmm(
        step_window, step_col, flat_values, b,
        num_windows=num_windows, bm=bm, bk=bk, bn=bn,
        interpret=(impl == "pallas_interpret"),
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_windows", "bm", "bk", "bn", "n_pat", "m_pat",
                     "impl"),
)
def nm_stream_spmm(
    step_window: jax.Array,
    step_col: jax.Array,
    nm_values: jax.Array,
    nm_codes: jax.Array,
    b: jax.Array,
    *,
    num_windows: int,
    bm: int,
    bk: int,
    bn: int = 256,
    n_pat: int,
    m_pat: int,
    impl: Impl = "xla",
) -> jax.Array:
    """Matrix-engine path over the N:M-packed tile stream; returns packed
    (num_windows*bm, N) fp32.

    The pallas kernel re-expands each packed tile in VMEM and feeds the
    MXU the same static dense GEMM as the general stream (payload bytes
    drop to ~(n+1)/m of the dense tile); the xla impl skips the expansion
    entirely and contracts packed values against gathered B rows — n/m of
    the dense-tile FLOPs.
    """
    if b.ndim != 2:
        raise ValueError(
            f"nm_stream_spmm expects a rank-2 (K, N) operand, got shape "
            f"{tuple(b.shape)}; batched RHS panels go through the executor "
            "pipeline (repro.exec), which vmaps the fused body per path"
        )
    if impl == "xla":
        return ref.ref_nm_stream_spmm(
            step_window, step_col, nm_values, nm_codes, b,
            num_windows, n_pat, m_pat, bk,
        )
    return nm_tile_spmm(
        step_window, step_col, nm_values, nm_codes, b,
        num_windows=num_windows, bm=bm, bk=bk, bn=bn,
        n_pat=n_pat, m_pat=m_pat,
        interpret=(impl == "pallas_interpret"),
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_windows", "bm", "bk", "bn", "row_cap", "impl"),
)
def bitmap_stream_spmm(
    step_window: jax.Array,
    step_col: jax.Array,
    bitmap_words: jax.Array,
    bitmap_values: jax.Array,
    b: jax.Array,
    *,
    num_windows: int,
    bm: int,
    bk: int,
    bn: int = 256,
    row_cap: int,
    impl: Impl = "xla",
) -> jax.Array:
    """Matrix-engine path over the bitmap-packed tile stream; returns
    packed (num_windows*bm, N) fp32.

    The pallas kernel expands each tile from its occupancy bitmap in VMEM
    (payload bytes drop to ~(row_cap + bk/32)/bk of the dense tile); the
    xla impl expands at trace time and runs the general streaming einsum.
    """
    if b.ndim != 2:
        raise ValueError(
            f"bitmap_stream_spmm expects a rank-2 (K, N) operand, got shape "
            f"{tuple(b.shape)}; batched RHS panels go through the executor "
            "pipeline (repro.exec), which vmaps the fused body per path"
        )
    if impl == "xla":
        return ref.ref_bitmap_stream_spmm(
            step_window, step_col, bitmap_words, bitmap_values, b,
            num_windows, bk,
        )
    return bitmap_tile_spmm(
        step_window, step_col, bitmap_words, bitmap_values, b,
        num_windows=num_windows, bm=bm, bk=bk, bn=bn, row_cap=row_cap,
        interpret=(impl == "pallas_interpret"),
    )


def _xla_fringe(rows, cols, vals, b, num_rows, chunk, buckets):
    if not buckets:
        return ref.ref_gather_spmm(rows, cols, vals, b, num_rows, chunk=chunk)
    if (sum(n for n, _ in buckets) != num_rows
            or sum(n * w for n, w in buckets) != cols.shape[0]):
        raise ValueError(
            f"bucket ladder {buckets!r} does not cover the stream: "
            f"{num_rows} rows, {cols.shape[0]} slots"
        )
    return ref.bucketed_gather_spmm(cols, vals, b, buckets)


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "bn", "impl", "chunk", "tier", "bk",
                     "buckets"),
)
def fringe_spmm(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    b: jax.Array,
    *,
    num_rows: int,
    bn: int = 256,
    impl: Impl = "xla",
    chunk: int | None = None,
    tier: FringeTier = "auto",
    bk: int = 0,
    kb_chunk: jax.Array | None = None,
    kb_rows: jax.Array | None = None,
    kb_cols: jax.Array | None = None,
    kb_vals: jax.Array | None = None,
    buckets: tuple = (),
) -> jax.Array:
    """Vector-engine path; returns packed (num_rows, N) fp32.

    Where the fringe runs on XLA (``impl="xla"``, or the "xla" tier), two
    formulations exist.  With a bucket ladder ``buckets`` — ``((n_rows_b,
    width_b), ...)``, which ``prepare`` builds for single-device plans
    whose fringe runs on XLA and lays the stream out for — each bucket is
    gathered and reduced at its fixed width (``ref.bucketed_gather_spmm``):
    no sort and no scatter.  Without one, ``ref.ref_gather_spmm`` sums the
    products with an unsorted segment sum; it stays the oracle of both.

    ``chunk`` is the per-grid-step nonzero count of the chunked gather
    kernel; for the scatter formulation it bounds the gather intermediate
    (None means the one-shot vectorized formulation); the bucketed one
    ignores it.  The pallas kernel unrolls its chunk loop in python, so
    large XLA-oriented values (thousands) are clamped to a compile-friendly
    unroll factor there.

    Pallas impls dispatch across three VMEM tiers
    (core/cost_model.select_fringe_tier): "resident" keeps the full (K, bn)
    B panel on chip, "ksharded" streams (bk, bn) slices of B through a
    third-grid-dimension k-block loop, and "xla" is the gather fallback
    when even one slice cannot fit.  ``tier="auto"`` picks from the default
    VMEM budget; plan-driven callers pass the tier chosen at prepare time
    plus the k-bucketed stream (``kb_*``, layout described in
    gather_spmm_ksharded).  Without a bucketed stream, an auto choice of
    "ksharded" degrades to the XLA fallback (bucketing needs host-side
    padding).
    """
    if b.ndim != 2:
        raise ValueError(
            f"fringe_spmm expects a rank-2 (K, N) operand, got shape "
            f"{tuple(b.shape)}; batched RHS panels go through the executor "
            "pipeline (repro.exec), which vmaps the fused body per path"
        )
    if chunk is not None and chunk < 1:
        raise ValueError(f"chunk must be a positive nonzero count, got {chunk}")
    if impl == "xla":
        return _xla_fringe(rows, cols, vals, b, num_rows, chunk, buckets)
    if tier == "auto":
        from ..core.cost_model import select_fringe_tier

        tier, auto_bk = select_fringe_tier(b.shape[0], num_rows, bn)
        if tier == "ksharded":
            # a bucketed stream is only interpretable with the bk it was
            # bucketed under, so an auto choice never overrides the
            # caller's bk; without a stream (or its bk) fall back to XLA
            if kb_rows is None or bk <= 0:
                tier = "xla"
    if tier == "resident":
        return gather_spmm(
            rows, cols, vals, b,
            num_rows=num_rows, bn=bn, chunk=effective_chunk(chunk),
            interpret=(impl == "pallas_interpret"),
        )
    if tier == "ksharded":
        if kb_rows is None or kb_chunk is None or bk <= 0:
            raise ValueError(
                "tier='ksharded' needs the k-bucketed stream (kb_chunk/"
                "kb_rows/kb_cols/kb_vals) and its bk; plans built by "
                "prepare() carry them, or use tier='auto' to fall back"
            )
        return gather_spmm_ksharded(
            kb_chunk, kb_rows, kb_cols, kb_vals, b,
            num_rows=num_rows, bk=bk, bn=bn, chunk=effective_chunk(chunk),
            interpret=(impl == "pallas_interpret"),
        )
    return _xla_fringe(rows, cols, vals, b, num_rows, chunk, buckets)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "impl")
)
def sddmm_block_stream(
    step_window: jax.Array,
    step_col: jax.Array,
    xp: jax.Array,
    yp: jax.Array,
    *,
    bm: int,
    bk: int,
    impl: Impl = "xla",
) -> jax.Array:
    """SDDMM matrix-engine path; returns the fp32 tile stream (T, bm, bk).

    ``xp`` is the window-gathered X row panel (num_windows*bm, D) and
    ``yp`` the column-permuted, K-padded Y operand (D, K).  Core nonzeros'
    values are extracted from the returned stream at the plan's
    ``UpdateMaps.core_lin`` slots — the same linear addressing prepare()
    scattered input values under, so extraction needs no new metadata.
    """
    if impl == "xla":
        return ref.ref_tile_sddmm(step_window, step_col, xp, yp, bm, bk)
    return dense_tile_sddmm(
        step_window, step_col, xp, yp, bm=bm, bk=bk,
        interpret=(impl == "pallas_interpret"),
    )


@functools.partial(
    jax.jit, static_argnames=("impl", "chunk", "tier", "vmem_budget")
)
def sddmm_gather(
    rows: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    yt: jax.Array,
    *,
    impl: Impl = "xla",
    chunk: int | None = None,
    tier: SddmmTier = "auto",
    vmem_budget: int | None = None,
) -> jax.Array:
    """SDDMM vector-engine path: fp32 dots (nnz,) in input order.

    ``yt`` is Y pre-transposed to (K, D) so both operands gather by row.
    Pallas impls keep BOTH dense panels VMEM-resident, so the dispatch is
    binary (core/cost_model.select_sddmm_tier): "resident" pallas gather,
    or the XLA reference when the panels overflow the budget — there is no
    useful K-sharded middle tier because the reduced axis is D and slicing
    it would re-stream both panels every step.
    """
    if x.shape[-1] != yt.shape[-1]:
        raise ValueError(
            f"sddmm operands disagree on D: x {tuple(x.shape)} vs "
            f"y^T {tuple(yt.shape)}"
        )
    if chunk is not None and chunk < 1:
        raise ValueError(f"chunk must be a positive nonzero count, got {chunk}")
    if impl == "xla":
        return ref.ref_gather_sddmm(rows, cols, x, yt, chunk=chunk)
    if tier == "auto":
        from ..core.cost_model import select_sddmm_tier

        tier = select_sddmm_tier(
            x.shape[-1], x.shape[0], yt.shape[0], vmem_budget=vmem_budget
        )
    if tier == "resident":
        return gather_sddmm(
            rows, cols, x, yt, chunk=effective_chunk(chunk),
            interpret=(impl == "pallas_interpret"),
        )
    return ref.ref_gather_sddmm(rows, cols, x, yt, chunk=chunk)


def delta_fringe_spmm(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    b: jax.Array,
    *,
    num_rows: int,
    bn: int = 256,
    impl: Impl = "xla",
    chunk: int | None = None,
    tier: FringeTier = "xla",
    bk: int = 0,
    kb_chunk: jax.Array | None = None,
    kb_rows: jax.Array | None = None,
    kb_cols: jax.Array | None = None,
    kb_vals: jax.Array | None = None,
) -> jax.Array:
    """Dispatch a dynamic *delta sidecar* through the fringe tier machinery.

    A delta stream (dynamic/delta.py) is a capacity-padded COO: mutations
    accumulate in place and padding entries are (row 0, col 0, value 0.0) —
    accumulate-inert in every tier, exactly like the sharded executor's
    fringe padding.  The stream is rebuilt host-side per mutation batch but
    its *shapes* only change when capacity doubles, so the executors that
    embed this dispatch retrace logarithmically in delta size.  Shares every
    kernel with the plan-driven path: the sidecar is just one more fringe,
    coordinated by the same VMEM-tier selection.
    """
    if rows.shape != cols.shape or rows.shape != vals.shape:
        raise ValueError(
            f"delta stream triplets disagree: rows={tuple(rows.shape)} "
            f"cols={tuple(cols.shape)} vals={tuple(vals.shape)}"
        )
    if tier == "ksharded" and impl != "xla" and kb_rows is None:
        raise ValueError(
            "delta tier='ksharded' needs the k-bucketed sidecar stream; "
            "dynamic.delta.DeltaFringe builds it at materialization time"
        )
    return fringe_spmm(
        rows, cols, vals, b,
        num_rows=num_rows, bn=bn, impl=impl, chunk=chunk, tier=tier, bk=bk,
        kb_chunk=kb_chunk, kb_rows=kb_rows, kb_cols=kb_cols,
        kb_vals=kb_vals,
    )
