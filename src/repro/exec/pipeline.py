"""Unified executor pipeline: one composable builder for every flavor.

Every dispatch flavor NeutronSparse executes — single-RHS fused, batched
vmap, structural-delta-extended, multi-device ``shard_map`` (rows or rhs
axis), and any combination — is produced by :func:`build_executor` from the
same fused body, composed in fixed stages:

    fused body (matrix path + vector path + gather merge)
      -> [+ delta-sidecar contribution, merged additively in-body]
      -> [shard_map wrap: stacked-leaf rows axis or column-sharded rhs]
      -> [vmap over a (batch, K, N) operand]
      -> jit

Replacing the five hand-rolled ``_*_executor`` factories with one builder
means a new execution mode is a pipeline stage, not a sixth copy of the
dispatch code — and the sharded dynamic path gets its delta contribution
*inside* the per-shard body (each shard merges the sidecar rows it owns, in
local row coordinates, before the all-gather), so sharded + delta is one
dispatch like everything else.

All executors live in one bounded LRU (``exec.cache.EXECUTOR_CACHE``) keyed
by (signature, delta signature, batch, mesh, shard axis).

Every body stage runs under one of the ``jax.named_scope`` names in
:data:`SCOPES`: ``b_prep`` (the operand permutation, padding and
relayout), ``matrix_path`` (the tile stream), ``fringe_path`` (the
per-nonzero gather kernels) and ``merge`` (the gathers of both paths into
the output's order, and their sum; for sddmm, the core values' gather and
the sort that places both paths' values in COO order).  A scope is
compile-time metadata, the ``op_name`` of each HLO operation: it costs
nothing at run time and keys no cache, and on a profiler trace it names
the stage each device operation belongs to.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.plan_ir import (
    DELTA_LEAF_RANKS, LEAF_COL_PERM, LEAF_RANKS, N_DELTA_LEAVES,
    N_PLAN_LEAVES, N_SDDMM_BODY_LEAVES, delta_child_sig, gather_rows,
    op_extra, permute_pad_b, sig_op, untag_sig,
)
from ..distributed.sharding import (
    axis_spec, leading_axis_spec, replicated_spec, shard_map,
    trailing_axis_spec,
)
from ..errors import PlanBuildError
from ..kernels import ops
from ..obs import REGISTRY
from ..robust.faults import HARNESS
from .cache import EXECUTOR_CACHE, record_fused_trace, record_sharded_trace

#: The fused bodies' stage scopes, in dataflow order.
SCOPES = ("b_prep", "matrix_path", "fringe_path", "merge")
B_PREP, MATRIX_PATH, FRINGE_PATH, MERGE = SCOPES

_BUILDS = REGISTRY.counter(
    "exec_executor_builds_total",
    "executors actually constructed (cache hits skip the build)",
    labelnames=("kind",))
# "bucketed": the degree-bucketed gather-reduce over a plan's bucket ladder;
# "scatter": a stream without one (segment-sum scatter on XLA, or a Pallas
# fringe kernel)
_FRINGE_FORMULATION = REGISTRY.counter(
    "exec_fringe_formulation_total",
    "fused-body traces with a fringe, by the fringe stream's formulation",
    labelnames=("formulation",))
# "sorted": both paths' values placed into COO order by one key-value sort;
# "core_only" / "fringe_only": one path's values are already in COO order;
# "reference": the one gather over every nonzero (impl "xla", degrade target)
_SDDMM_MERGE = REGISTRY.counter(
    "exec_sddmm_merge_total",
    "sddmm fused-body traces, by how the merge places values in COO order",
    labelnames=("placement",))


def _fused_body(sig: Tuple, densify_occupancy: Optional[float] = None):
    """Raw fused executor body for a plan signature (untraced).

    Every flavor — the single-device jit, the batched vmap, the per-shard
    ``shard_map`` body — wraps this one function, so every dispatch flavor
    runs identical math.  The trace-hook append runs once per *trace*, so
    retraces anywhere in the pipeline are observable.
    ``densify_occupancy`` overrides the matrix-path densify crossover (the
    tuner's measured value arrives via build_executor; None keeps the
    kernel default) — it is part of the executor cache key, not the plan
    signature, because it changes the lowered program but not the plan
    layout.
    """
    (_version, shape, bm, bk, bn, impl, reorder_cols, fringe_chunk,
     num_windows, _num_steps, _nnz_f, n_fringe_rows, has_core, has_fringe,
     fringe_tier, fringe_bk, _n_chunks, _nnz_kb,
     matrix_format, format_params, fringe_buckets) = sig
    m, k = shape

    def _run(step_window, step_col, flat_values, fringe_rows, fringe_cols,
             fringe_vals, col_perm, gsrc_m, gsrc_v,
             kb_chunk, kb_rows, kb_cols, kb_vals,
             nm_values, nm_codes, bitmap_words, bitmap_values, b):
        record_fused_trace(sig)
        if has_fringe:
            _FRINGE_FORMULATION.inc(
                formulation="bucketed" if fringe_buckets else "scatter")
        if impl != "xla":  # pallas tiers lower here, at trace time
            HARNESS.fire("pallas_lowering", context=sig)
        n = b.shape[1]
        with jax.named_scope(B_PREP):
            bp = permute_pad_b(b, col_perm, reorder_cols, bk, bn)

        packed_m = packed_v = None
        if has_core:
            # structured fast lane: the signature-carried format selects
            # which payload the matrix stage consumes (the general flat
            # stream always rides along, so format demotion reuses these
            # same leaves).  Same degrade-to-XLA health gating: an impl
            # demotion via xla_fallback_sig keeps the format and routes it
            # to the structured XLA reference form.
            with jax.named_scope(MATRIX_PATH):
                if matrix_format == "nm":
                    n_pat, m_pat = format_params
                    packed_m = ops.nm_stream_spmm(
                        step_window, step_col, nm_values, nm_codes, bp,
                        num_windows=num_windows, bm=bm, bk=bk, bn=bn,
                        n_pat=n_pat, m_pat=m_pat, impl=impl,
                    )[:, :n]
                elif matrix_format == "bitmap":
                    _n_words, row_cap = format_params
                    packed_m = ops.bitmap_stream_spmm(
                        step_window, step_col, bitmap_words, bitmap_values,
                        bp, num_windows=num_windows, bm=bm, bk=bk, bn=bn,
                        row_cap=row_cap, impl=impl,
                    )[:, :n]
                else:
                    packed_m = ops.block_stream_spmm(
                        step_window, step_col, flat_values, bp,
                        num_windows=num_windows, bm=bm, bk=bk, bn=bn,
                        impl=impl,
                        assume_unique=True,  # prepare() emits unique pairs
                        densify_occupancy=densify_occupancy,
                    )[:, :n]
        if has_fringe:
            with jax.named_scope(FRINGE_PATH):
                packed_v = ops.fringe_spmm(
                    fringe_rows, fringe_cols, fringe_vals, bp,
                    num_rows=n_fringe_rows, bn=bn, impl=impl,
                    chunk=fringe_chunk, tier=fringe_tier, bk=fringe_bk,
                    kb_chunk=kb_chunk, kb_rows=kb_rows,
                    kb_cols=kb_cols, kb_vals=kb_vals, buckets=fringe_buckets,
                )[:, :n]
        with jax.named_scope(MERGE):
            c = None
            if packed_m is not None:
                c = gather_rows(packed_m, gsrc_m)
            if packed_v is not None:
                cv = gather_rows(packed_v, gsrc_v)
                c = cv if c is None else c + cv
            if c is None:  # empty matrix
                c = jnp.zeros((m, n), jnp.float32)
        return c

    return _run


def _sddmm_body(sig: Tuple):
    """Fused SDDMM body for an op-tagged plan signature (untraced).

    Inverts the SpMM dataflow on the same plan structure: the matrix engine
    computes dense ``X_window @ Y_kblock`` products for exactly the tiles
    the plan's stream names and each core nonzero's value is *extracted*
    at its ``c_lin`` slot; fringe nonzeros gather one X row and one Y
    column each on the vector engine.  Output is (nnz,) fp32 in the plan's
    original COO input order — feed it straight to
    ``dynamic.update_values(plan, arange(nnz), out)``.
    """
    (_version, shape, bm, bk, _bn, impl, reorder_cols, fringe_chunk,
     _num_windows, _num_steps, _nnz_f, _n_fringe_rows, has_core, has_fringe,
     _fringe_tier, _fringe_bk, _n_chunks, _nnz_kb,
     _matrix_format, _format_params, _fringe_buckets) = untag_sig(sig)
    _m, k = shape
    # nnz / nnz_f key the cache (shapes come from the arrays at trace time);
    # the budget must live in the sig so equal-structure plans with
    # different budgets never alias one executor
    _nnz, _nnz_fs, vmem_budget = op_extra(sig)

    def _run(step_window, step_col, core_row_map, col_perm,
             g_rows, g_cols, c_lin, place, f_rows, f_cols, x, y):
        record_fused_trace(sig)
        if impl != "xla":  # pallas tiers lower here, at trace time
            HARNESS.fire("pallas_lowering", context=sig)
        with jax.named_scope(B_PREP):
            yt = jnp.swapaxes(y, 0, 1)  # (K, D): both gathers address rows
        if impl == "xla" or not (has_core or has_fringe):
            # reference gather over every nonzero — also the complete
            # degrade target xla_fallback_sig demotes pallas failures to
            _SDDMM_MERGE.inc(placement="reference")
            with jax.named_scope(FRINGE_PATH):
                return ops.sddmm_gather(
                    g_rows, g_cols, x, yt, impl="xla", chunk=fringe_chunk,
                )
        core_vals = fv = None
        if has_core:
            # matrix path: window-gathered X rows x column-permuted Y panel
            with jax.named_scope(B_PREP):
                xp = jnp.where(
                    (core_row_map >= 0)[:, None],
                    x[jnp.clip(core_row_map, 0, x.shape[0] - 1)], 0.0,
                )
                yp = y[:, col_perm] if reorder_cols else y
                k_pad = ((k + bk - 1) // bk) * bk
                if k_pad != k:
                    yp = jnp.pad(yp, ((0, 0), (0, k_pad - k)))
            with jax.named_scope(MATRIX_PATH):
                tiles = ops.sddmm_block_stream(
                    step_window, step_col, xp, yp, bm=bm, bk=bk, impl=impl,
                )
            # only the core nonzeros' slots, addressed in the (T, bm, bk)
            # layout the kernel wrote: a flat view would relayout the stream
            with jax.named_scope(MERGE):
                core_vals = tiles[c_lin // (bm * bk), (c_lin // bk) % bm,
                                  c_lin % bk]
        if has_fringe:
            with jax.named_scope(FRINGE_PATH):
                fv = ops.sddmm_gather(
                    f_rows, f_cols, x, yt, impl=impl, chunk=fringe_chunk,
                    vmem_budget=vmem_budget,
                )
        # one path alone is already in COO order; both are placed there by
        # sorting the values on their fixed COO positions (the keys are
        # unique, so an unstable sort places them alike and compiles faster)
        if fv is None:
            _SDDMM_MERGE.inc(placement="core_only")
            return core_vals
        if core_vals is None:
            _SDDMM_MERGE.inc(placement="fringe_only")
            return fv
        _SDDMM_MERGE.inc(placement="sorted")
        with jax.named_scope(MERGE):
            return lax.sort((place, jnp.concatenate([core_vals, fv])),
                            num_keys=1, is_stable=False)[1]

    return _run


def _sddmm_flat_body(sig: Tuple):
    """Gather-only SDDMM body for ("sddmm_flat", impl, nnz, chunk) sigs.

    The sharded-plan form: a ``ShardedPlan`` keeps one *global* COO mirror
    (``ShardedUpdateMaps``), and SDDMM output is a flat (nnz,) vector —
    tiny next to the dense operands — so the op runs as one replicated
    gather program over the global maps instead of a per-shard shard_map
    (no health gating on this synthetic signature; the gather has no
    lowering-failure modes the plan path doesn't already cover).
    """
    _tag, impl, _nnz, chunk = sig

    def _run(g_rows, g_cols, x, y):
        record_fused_trace(sig)
        with jax.named_scope(B_PREP):
            yt = jnp.swapaxes(y, 0, 1)
        with jax.named_scope(FRINGE_PATH):
            return ops.sddmm_gather(g_rows, g_cols, x, yt, impl=impl,
                                    chunk=chunk)

    return _run


def _spspmm_body(sig: Tuple):
    """Numeric SpGEMM body for ("spspmm", n_exp, nnz_c) signatures.

    The symbolic phase (exec.api.execute_spspmm) intersects the two plans'
    row-window metadata host-side and emits three index streams: expansion
    term t multiplies A's nonzero ``ae[t]`` by B's nonzero ``be[t]`` and
    accumulates into output slot ``ce[t]`` (sorted, so the segment sum
    takes the contiguous-run path).  This body is the single jitted
    dispatch of the numeric phase.
    """
    _tag, _n_exp, nnz_c = sig

    def _run(ae, be, ce, va, vb):
        record_fused_trace(sig)
        prod = va[ae].astype(jnp.float32) * vb[be].astype(jnp.float32)
        return jax.ops.segment_sum(
            prod, ce, num_segments=nnz_c, indices_are_sorted=True,
        )

    return _run


def _delta_contrib_body(m: int, bk_cfg: int, bn: int, impl,
                        reorder_cols: bool, fringe_chunk, dsig: Tuple):
    """Delta-sidecar contribution body: (delta leaves, col_perm, b) -> (m, N).

    ``dsig`` may be a plain ("delta", ...) signature or the per-shard slice
    of a ("sharded_delta", ...) one — the math is identical; only the leaf
    routing upstream differs.
    """
    _tag, _cap, num_rows, tier, dbk, _nch, _nkb = delta_child_sig(dsig)

    def contrib(d_rows, d_cols, d_vals, d_gsrc, kbc, kbr, kbcol, kbv,
                col_perm, b):
        n = b.shape[1]
        with jax.named_scope(B_PREP):
            bp = permute_pad_b(b, col_perm, reorder_cols, bk_cfg, bn)
        with jax.named_scope(FRINGE_PATH):
            packed = ops.delta_fringe_spmm(
                d_rows, d_cols, d_vals, bp,
                num_rows=num_rows, bn=bn, impl=impl, chunk=fringe_chunk,
                tier=tier, bk=dbk,
                kb_chunk=kbc, kb_rows=kbr, kb_cols=kbcol, kb_vals=kbv,
            )[:, :n]
        with jax.named_scope(MERGE):
            return gather_rows(packed, d_gsrc)

    return contrib


def _flat_body(sig: Tuple, dsig: Optional[Tuple],
               densify_occupancy: Optional[float] = None):
    """(leaves, [delta leaves], *operands) -> out: the per-device program.

    Operator dispatch point of the pipeline: every op on the plan IR is a
    fused-body stage selected here by signature — not a separate executor
    family — so caching, batching, health demotion, and the trace counters
    cover new ops identically.  Returns ``(body, n_leaf_args, n_operands)``
    where the body takes ``n_leaf_args`` broadcast leaf args followed by
    ``n_operands`` dense operands (the axes vmapped in the batched flavor).
    """
    op = sig[0] if isinstance(sig[0], str) else sig_op(sig)
    if op not in ("spmm",) and dsig is not None:
        raise PlanBuildError(
            f"op {op!r} does not take a delta sidecar; fold structural "
            "deltas (DynamicPlan compaction) before dispatching it"
        )
    if op == "sddmm_flat":
        return _sddmm_flat_body(sig), 2, 2
    if op == "spspmm":
        return _spspmm_body(sig), 3, 2
    if op == "sddmm":
        return _sddmm_body(sig), N_SDDMM_BODY_LEAVES, 2
    run = _fused_body(sig, densify_occupancy)
    if dsig is None:
        return run, N_PLAN_LEAVES, 1
    (_version, shape, _bm, bk, bn, impl, reorder_cols, fringe_chunk,
     *_rest) = sig
    contrib = _delta_contrib_body(
        shape[0], bk, bn, impl, reorder_cols, fringe_chunk, dsig
    )

    def body(*args):
        leaves = args[:N_PLAN_LEAVES]
        dleaves = args[N_PLAN_LEAVES:N_PLAN_LEAVES + N_DELTA_LEAVES]
        b = args[-1]
        c = run(*leaves, b)
        d = contrib(*dleaves, leaves[LEAF_COL_PERM], b)
        with jax.named_scope(MERGE):
            return c + d

    return body, N_PLAN_LEAVES + N_DELTA_LEAVES, 1


def _build(sig: Tuple, batch: Optional[int], dsig: Optional[Tuple],
           mesh: Any, axis_name: Optional[str], shard_axis: Optional[str],
           densify_occupancy: Optional[float] = None):
    # fault seam: fires once per executor *build* (cache hits skip _build
    # entirely, so a demoted-then-cached executor never re-fires)
    HARNESS.fire("executor_build", context=sig)
    if mesh is None:
        _BUILDS.inc(kind="fused" if batch is None else "batched")
    else:
        _BUILDS.inc(kind=f"sharded:{shard_axis}")
    body, n_leaf_args, n_operands = _flat_body(sig, dsig, densify_occupancy)

    if mesh is None:
        if batch is None:
            return jax.jit(body)
        # plan (and delta) leaves broadcast; only the dense operands carry
        # the mapped axis (one RHS for SpMM, the X/Y pair for SDDMM)
        return jax.jit(jax.vmap(
            body, in_axes=(None,) * n_leaf_args + (0,) * n_operands
        ))

    if n_operands != 1:
        raise PlanBuildError(
            "shard_map flavors exist for the SpMM body only; sddmm on "
            "sharded plans dispatches through its flat gather form and "
            "spspmm is a host-symbolic + single-device numeric op"
        )

    # --- sharded flavors ---------------------------------------------------
    b_rank = 2 if batch is None else 3
    leaf_ranks = LEAF_RANKS + (DELTA_LEAF_RANKS if dsig is not None else ())

    def device_body(*args):
        *lv, bb = args
        if batch is None:
            return body(*lv, bb)
        return jax.vmap(lambda one: body(*lv, one))(bb)

    if shard_axis == "rows":
        # leaves (plan + routed delta) arrive stacked along a leading shard
        # dim; each device squeezes its slice and runs the fused(+delta)
        # body on replicated b.  out_specs concatenate the disjoint packed
        # row blocks — the only cross-device movement is the all-gather of
        # results, regardless of whether a delta rides along.
        in_specs = tuple(
            leading_axis_spec(r + 1, axis_name) for r in leaf_ranks
        ) + (replicated_spec(b_rank),)
        out_specs = (
            leading_axis_spec(2, axis_name) if batch is None
            else axis_spec(3, 1, axis_name)  # (batch, shard-stacked rows, N)
        )

        def shard_body(*args):
            *lv, bb = args
            lv = [x[0] for x in lv]  # squeeze this device's shard slice
            return device_body(*lv, bb)

        sm = shard_map(shard_body, mesh, in_specs, out_specs)

        @jax.jit
        def _exec(*args):
            record_sharded_trace((sig, shard_axis, batch, dsig))
            *leaves, assemble, b = args
            flat = sm(*leaves, b)  # (..., n_shards * rows_per_shard, N)
            with jax.named_scope(MERGE):
                return jnp.take(flat, assemble, axis=-2)

        return _exec

    # rhs: replicated plan (and replicated, un-routed delta), column-sharded
    # b, outputs concatenated along N
    in_specs = tuple(replicated_spec(r) for r in leaf_ranks) + (
        trailing_axis_spec(b_rank, axis_name),
    )
    out_specs = trailing_axis_spec(b_rank, axis_name)

    sm = shard_map(device_body, mesh, in_specs, out_specs)

    @jax.jit
    def _exec(*args):
        record_sharded_trace((sig, shard_axis, batch, dsig))
        return sm(*args)

    return _exec


def build_executor(
    sig: Tuple,
    *,
    batch: Optional[int] = None,
    delta_sig: Optional[Tuple] = None,
    mesh: Any = None,
    axis_name: Optional[str] = None,
    shard_axis: Optional[str] = None,
    densify_occupancy: Optional[float] = None,
):
    """Build (or fetch) the executor for one plan structure + flavor.

    ``sig`` is a :meth:`NeutronPlan.signature` tuple (for sharded flavors,
    the mesh-uniform per-shard signature).  ``batch`` selects the vmapped
    multi-RHS form, ``delta_sig`` appends the structural-sidecar merge,
    ``mesh``/``axis_name``/``shard_axis`` wrap the body in ``shard_map``.

    The returned callable takes ``(*plan_leaves, [*delta_leaves],
    [assemble], b)`` — assemble only for ``shard_axis="rows"`` — and is
    cached in the process-wide bounded LRU: repeated builds for one
    structure reuse one compiled program, and capacity eviction (not
    process lifetime) bounds memory in long-lived serving processes.
    """
    if mesh is None and (axis_name or shard_axis):
        raise PlanBuildError("axis_name/shard_axis need a mesh")
    if mesh is not None and shard_axis not in ("rows", "rhs"):
        raise PlanBuildError(
            f"shard_axis must be rows|rhs, got {shard_axis!r}")
    key = (sig, batch, delta_sig, mesh, axis_name, shard_axis,
           densify_occupancy)
    return EXECUTOR_CACHE.get_or_build(
        key,
        functools.partial(_build, sig, batch, delta_sig, mesh, axis_name,
                          shard_axis, densify_occupancy),
    )


def build_delta_only_executor(
    m: int, bk_cfg: int, bn: int, impl, fringe_chunk,
    dsig: Tuple, batch: Optional[int],
):
    """Standalone delta contribution executor (compat path).

    Pre-pipeline releases added the sharded delta contribution as a second
    dispatch through this program; it remains as the implementation of
    ``execute_delta_contribution`` (public API, and the differential
    baseline the single-dispatch parity tests compare against).
    """
    key = ("delta_only", m, bk_cfg, bn, impl, fringe_chunk, dsig, batch)

    def _builder():
        _BUILDS.inc(kind="delta_only")
        contrib = _delta_contrib_body(
            m, bk_cfg, bn, impl, False, fringe_chunk, dsig
        )

        def body(*args):
            *dleaves, col_perm, b = args
            return contrib(*dleaves, col_perm, b)

        if batch is None:
            return jax.jit(body)
        return jax.jit(
            jax.vmap(body, in_axes=(None,) * (N_DELTA_LEAVES + 1) + (0,))
        )

    return EXECUTOR_CACHE.get_or_build(key, _builder)


def _leaf_count_probe() -> None:
    # plan_ir and the pipeline must agree on the leaf contract; cheap import-
    # time assertion so a drifted edit fails loudly, not with shape errors
    assert len(LEAF_RANKS) == N_PLAN_LEAVES
    assert len(DELTA_LEAF_RANKS) == N_DELTA_LEAVES


_leaf_count_probe()
