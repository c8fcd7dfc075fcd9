"""NeutronSparse plan construction + public API facade.

``prepare`` runs the full preprocessing pipeline from the paper's workflow
(Fig. 7): cost-model split -> two-stage extraction -> global-local reorder
-> BlockELL packing + flat tile stream -> reuse-ordered grid -> fringe COO.
``prepare_sharded`` extends it across a ``jax.sharding.Mesh``: row-windows
(or RHS columns) are balanced across devices and every shard gets a padded,
mesh-uniform sub-plan.

The *representation* the builders emit (leaf layout, signatures, padding
rules, COO->slot update maps) lives in :mod:`repro.core.plan_ir`, and the
*execution* of prepared plans lives in the :mod:`repro.exec` pipeline —
one composable builder produces every dispatch flavor (fused, batched,
delta-extended, sharded, any combination) from the same fused body, each a
single jitted dispatch.  This module re-exports both sides, so historical
call sites keep working::

    from repro.core.spmm import prepare, execute, execute_sharded, ...

New code should go through the :mod:`repro.sparse` facade instead — one
``SparseMatrix`` handle fronting the whole operator family (spmm, bspmm,
sddmm, spspmm); the execution forwarders here emit a one-per-process
``DeprecationWarning``.

Execution names are forwarded lazily (PEP 562) to keep the core layer's
static import graph pointing strictly downward — ``tools/check_layers.py``
enforces that ``core/`` never imports ``exec``/``dynamic``/``serve`` and
carries the one documented allowance for this facade.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..distributed.sharding import leading_axis_spec, replicated_spec
from ..errors import PlanBuildError
from . import formats, partition, plan_ir, reorder, reuse
from .coordinator import (
    balance_row_window_list, list_imbalance, window_costs_from_coo,
)
from .cost_model import EngineCostModel, select_shard_axis
from .tuner import resolve_cost_model
from .plan_ir import (  # noqa: F401  (public re-exports; layout owned by plan_ir)
    LEAF_FLAT_VALUES, LEAF_FRINGE_VALS, LEAF_KB_VALS, PATH_CORE, PATH_FRINGE,
    PLAN_FORMAT_VERSION, NeutronPlan, ShardedPlan, ShardedUpdateMaps,
    SpmmConfig, UpdateMaps,
)

from ..obs import REGISTRY, span

_PREPARES = REGISTRY.counter(
    "core_prepares_total", "host-side prepare() preprocessing runs")

# execution API lives in repro.exec.api; forwarded lazily so importing the
# core layer never pulls the executor pipeline (or anything above it) in
_EXEC_FORWARDS = (
    "execute", "execute_with_delta", "execute_sharded",
    "execute_delta_contribution", "execute_matrix_path",
    "execute_vector_path", "neutron_spmm", "SpMMOperator", "NeutronSpMM",
    "fused_trace_count", "sharded_trace_count", "dispatch_count",
)


_WARNED_FORWARD = False  # one DeprecationWarning per process, not per access


def __getattr__(name: str):
    if name in _EXEC_FORWARDS:
        import importlib

        global _WARNED_FORWARD
        if not _WARNED_FORWARD:
            import warnings

            _WARNED_FORWARD = True
            warnings.warn(
                "importing execution names from repro.core.spmm is "
                "deprecated; use the repro.sparse facade (or repro.exec "
                "directly) instead",
                DeprecationWarning,
                stacklevel=2,
            )
        return getattr(importlib.import_module("repro.exec.api"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXEC_FORWARDS))


def prepare_call_count() -> int:
    """Number of ``prepare()`` calls since process start.

    Test hook for the warm-start guarantees: a service restoring plans from
    the on-disk registry must serve without re-running preprocessing.
    Reads the ``core_prepares_total`` registry counter.
    """
    return int(_PREPARES.total())


# structured-payload leaf dummies: every plan carries the four structured
# leaves; non-selected formats get (1, 1, 1) zero arrays (inert and cheap,
# the same idiom as the k-bucketed fringe stream)
_DUMMY_F32 = np.zeros((1, 1, 1), np.float32)
_DUMMY_I32 = np.zeros((1, 1, 1), np.int32)


def _structured_payload(
    rows: np.ndarray,
    cols: np.ndarray,
    shape: Tuple[int, int],
    config: SpmmConfig,
    cm: EngineCostModel,
    flat_values: np.ndarray,
    has_core: bool,
    tile_density: float,
):
    """Choose and build the structured matrix-path payload for prepare().

    Returns ``(matrix_format, format_params, (nm_values, nm_codes),
    (bitmap_words, bitmap_values))``.  The general flat stream is always
    kept alongside — structured payloads are alternative *encodings*, so
    format demotion never needs a re-prepare.
    """
    hint = config.structure_hint
    general = (
        "general", (0, 0), (_DUMMY_F32, _DUMMY_I32), (_DUMMY_I32, _DUMMY_F32)
    )
    if hint == "general" or not has_core:
        return general
    explicit_nm = (
        isinstance(hint, tuple) and len(hint) == 3 and hint[0] == "nm"
    )
    if config.reorder_cols:
        # the column permutation moves nonzeros across m-groups, so
        # group-local structure no longer matches the original pattern
        if explicit_nm or hint in ("nm", "bitmap"):
            raise PlanBuildError(
                "structure_hint is incompatible with reorder_cols=True: "
                "the column permutation destroys group-local structure"
            )
        return general
    nm_pat = None
    if explicit_nm:
        nm_pat = (int(hint[1]), int(hint[2]))
        if nm_pat[1] <= 0 or config.bk % nm_pat[1]:
            raise PlanBuildError(
                f"structure_hint {hint!r} needs m dividing bk={config.bk}"
            )
    elif hint in (None, "nm"):
        nm_pat = formats.detect_nm_pattern(rows, cols, shape)
        # tiles chunk columns at bk boundaries; groups must not straddle
        if nm_pat is not None and config.bk % nm_pat[1]:
            nm_pat = None
    t_steps, bm, bk = flat_values.shape
    # bitmap row capacity the packer would choose (max per-row count,
    # rounded up), priced before committing to the pack
    per_row_max = int(np.count_nonzero(flat_values, axis=2).max())
    row_cap_est = max(8, ((per_row_max + 7) // 8) * 8)
    fmt = cm.select_matrix_format(
        nm_pattern=nm_pat,
        tile_zero_fraction=1.0 - float(tile_density),
        num_steps=int(t_steps), bm=int(bm), bk=int(bk),
        row_cap=row_cap_est, hint=hint,
    )
    if fmt == "nm" and nm_pat is not None:
        n_pat, m_pat = nm_pat
        try:
            nm_values, nm_codes = formats.pack_nm_tiles(
                flat_values, n_pat, m_pat
            )
        except ValueError as e:
            if explicit_nm:
                raise PlanBuildError(
                    f"core tile stream violates the hinted {n_pat}:{m_pat} "
                    f"pattern: {e}"
                ) from e
            return general
        return (
            "nm", (n_pat, m_pat), (nm_values, nm_codes),
            (_DUMMY_I32, _DUMMY_F32),
        )
    if fmt == "bitmap":
        words, packed, row_cap = formats.pack_bitmap_tiles(flat_values)
        return (
            "bitmap", (int(words.shape[2]), int(row_cap)),
            (_DUMMY_F32, _DUMMY_I32), (words, packed),
        )
    return general


def prepare(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    shape: Tuple[int, int],
    config: SpmmConfig = SpmmConfig(),
    cost_model: Optional[EngineCostModel] = None,
    *,
    _shard_part: bool = False,
) -> NeutronPlan:
    """Host-side preprocessing (one-time; amortized across epochs).

    Runs under the ``repro.prepare`` span, its phases under ``partition``,
    ``reorder``, ``pack`` and ``upload`` (:class:`repro.obs.span`); the
    plan stats ``t_partition_s``, ``t_reorder_s`` and ``t_pack_s`` are
    those spans' durations.

    ``_shard_part`` marks a per-shard sub-prepare of ``prepare_sharded``:
    the tile shape is already resolved at the global shape, and the fringe
    keeps the plain row-sorted stream (a bucket ladder would have to be
    mesh-uniform).
    """
    with span("prepare"):
        return _prepare(rows, cols, vals, shape, config, cost_model,
                        _shard_part)


def _prepare(rows, cols, vals, shape, config, cost_model, _shard_part):
    """The phases of :func:`prepare`, each under a span of its own."""
    m, k = shape
    rows, cols, vals = plan_ir.validate_coo(rows, cols, vals, shape)
    _PREPARES.inc()
    # analytic model unless config.autotune enables the measured table
    # (core.tuner); every dispatch decision below consults cm so a tuned
    # model can override any of them
    cm = cost_model if cost_model is not None else resolve_cost_model(
        "spmm", int(m), int(k), int(rows.shape[0]), config
    )
    # tuned (bm, bk) applies before partitioning — the tile shape drives
    # window costs, the core/fringe split, and every static plan shape.
    # prepare_sharded resolves it once at the global shape and passes
    # _shard_part=True so per-shard sub-prepares stay mesh-uniform.
    if not _shard_part and config.autotune:
        ts = cm.tile_shape(int(m), int(k), config.bn, int(rows.shape[0]))
        if ts is not None:
            config = dataclasses.replace(config, bm=int(ts[0]), bk=int(ts[1]))
    # 1) heterogeneous workload partitioning (§5.2)
    with span("partition") as s_part:
        part = partition.partition_rows_cols(
            rows, cols, vals, shape, cm, alpha=config.alpha,
            col_stage=config.enable_col_stage,
        )
    # 2) global-local reordering of the dense core (§6.1).  Only the active
    # (window, k-block) *structure* is computed here — tile values are
    # written once, directly into the flat stream (step 3), instead of
    # materializing a BlockELL values array and re-gathering it.
    with span("reorder") as s_reorder:
        n_core = int(part.core_row_ids.shape[0])
        nw = (n_core + config.bm - 1) // config.bm
        nkb = (k + config.bk - 1) // config.bk
        if n_core:
            local_of_row = np.full(m, -1, np.int64)
            local_of_row[part.core_row_ids] = np.arange(n_core)
            lrows = local_of_row[part.core_rows]
            ro = reorder.reorder(
                lrows, part.core_cols, (n_core, k), config.bm, config.bk,
                enable_global=config.enable_global_reorder,
                enable_local=config.enable_local_reorder,
                reorder_cols=config.reorder_cols,
                max_clusters=config.max_clusters,
                seed=config.seed,
            )
            inv_col = np.empty(k, np.int64)
            inv_col[ro.col_order] = np.arange(k)
            ccols = inv_col[part.core_cols]
            inv_row = np.empty(n_core, np.int64)
            inv_row[ro.row_order] = np.arange(n_core)
            prow = inv_row[lrows]
            st = formats.block_structure_from_coo(
                prow // config.bm, ccols // config.bk, nw, nkb
            )
            block_cols = np.zeros((nw, st.max_blocks), np.int32)
            block_cols[st.uw, st.slot] = st.ub.astype(np.int32)
            num_blocks = st.counts
            cluster_of_window = ro.cluster_of_row[:: config.bm][:nw]
            col_perm = ro.col_order
            tile_density = part.core_nnz / max(
                st.uw.size * config.bm * config.bk, 1
            )
        else:
            st = None
            block_cols = np.zeros((0, 1), np.int32)
            num_blocks = np.zeros(0, np.int64)
            cluster_of_window = np.zeros(0, np.int64)
            col_perm = np.arange(k, dtype=np.int64)
            tile_density = 0.0
    # 3) reuse-ordered flat tile stream (§6.2), then the fringe stream and
    # the merge's row maps
    with span("pack") as s_pack:
        if config.enable_reuse_order and nw:
            plan_r = reuse.plan_window_order(
                block_cols, num_blocks, np.asarray(cluster_of_window)
            )
            worder = plan_r.window_order
            reuse_factor = plan_r.reuse_factor
        else:
            worder = np.arange(nw, dtype=np.int64)
            reuse_factor = 1.0
        if st is not None and st.uw.size:
            # pair p of window w occupies stream position start(w) + slot(p);
            # nonzeros then land at (their pair's step, row%bm, col%bk) via one
            # flat scatter-add — no per-window python loop, no value re-gather
            cnt = num_blocks[worder]
            total = int(cnt.sum())
            starts_w = np.zeros(nw, np.int64)
            starts_w[worder] = np.cumsum(cnt) - cnt
            step_of_pair = starts_w[st.uw] + st.slot
            step_window = np.zeros(total, np.int32)
            step_window[step_of_pair] = st.uw.astype(np.int32)
            step_col = np.zeros(total, np.int32)
            step_col[step_of_pair] = st.ub.astype(np.int32)
            lin = (
                step_of_pair[st.inv_idx] * config.bm + prow % config.bm
            ) * config.bk + ccols % config.bk
            flat = np.zeros(total * config.bm * config.bk, np.float32)
            np.add.at(flat, lin, part.core_vals.astype(np.float32))
            flat_values = flat.reshape(total, config.bm, config.bk)
            core_lin = lin
        else:  # degenerate all-fringe matrix: one zero tile keeps shapes static
            step_window = np.zeros(1, np.int32)
            step_col = np.zeros(1, np.int32)
            flat_values = np.zeros((1, config.bm, config.bk), np.float32)
            core_lin = np.zeros(0, np.int64)

        # 3b) structured matrix-path payload (structured-sparsity fast lane):
        # detect N:M structure on the deduped pattern (or honor an explicit
        # structure_hint) and re-encode the flat tile stream as a packed
        # payload when the cost model prices it cheaper than the padding waste
        matrix_format, format_params, nm_payload, bitmap_payload = (
            _structured_payload(
                rows, cols, shape, config, cm, flat_values,
                has_core=bool(part.core_nnz), tile_density=float(tile_density),
            )
        )

        # map packed core rows -> original ids
        core_row_map = np.full(nw * config.bm, -1, np.int64)
        if n_core:
            core_row_map[:n_core] = part.core_row_ids[ro.row_order]
        core_row_map = core_row_map.astype(np.int32)

        # 4) fringe packing: one single-key stable sort (rows are already the
        # major key, so row runs come out contiguous); packed ids by run scan
        f_rows, f_cols, f_vals = part.fringe_rows, part.fringe_cols, part.fringe_vals
        if f_rows.size:
            order = np.argsort(f_rows * np.int64(k) + f_cols, kind="stable")
            sr = f_rows[order]
            first = np.concatenate([[True], sr[1:] != sr[:-1]])
            fringe_row_ids = sr[first]
            pr = (np.cumsum(first) - 1).astype(np.int32)
            pc = f_cols[order].astype(np.int32)
            # kernels accumulate in fp32; int/f64 input values are cast once
            # here instead of per-dispatch (and jnp would silently keep ints)
            pv = f_vals[order].astype(np.float32)
            fringe_pos = np.empty(order.size, np.int64)
            fringe_pos[order] = np.arange(order.size)  # fringe entry -> slot
        else:
            fringe_row_ids = np.zeros(1, np.int64)
            pr = np.zeros(1, np.int32)
            pc = np.zeros(1, np.int32)
            pv = np.zeros(1, np.float32)
            fringe_pos = np.zeros(0, np.int64)

        # 4b) vector-path dispatch tier: a VMEM-budget estimate picks the fringe
        # kernel (resident single-panel / K-sharded streaming / XLA fallback) so
        # the coordinator's split stays consistent with what the vector engine
        # can actually execute.  The K-sharded tier consumes the k-bucketed
        # stream built by plan_ir.bucket_fringe_kblocks; empty k-blocks get no
        # chunks (their B slices are never fetched).
        k_pad = ((k + config.bk - 1) // config.bk) * config.bk
        fringe_tier, fringe_bk = cm.select_fringe_tier(
            k_pad, int(fringe_row_ids.shape[0]), config.bn,
            vmem_budget=config.fringe_vmem_budget, nnz=int(f_rows.size),
        )
        # 4c) a fringe that runs on XLA is laid out degree-bucketed (ELL): the
        # executor then gathers and reduces each bucket at a fixed width, with
        # no per-call sort and no row scatter-add
        fringe_buckets = ()
        if f_rows.size and not _shard_part and (
                fringe_tier == "xla" or config.impl == "xla"):
            pr, pc, pv, row_order, slot, fringe_buckets = (
                plan_ir.bucket_fringe_rows(pr, pc, pv))
            fringe_row_ids = np.where(
                row_order >= 0, fringe_row_ids[np.maximum(row_order, 0)], -1)
            fringe_pos = slot[fringe_pos]
        # the k-bucketed stream is only consumed by the pallas kernels; xla-impl
        # plans skip the bucketing sort/scatter passes (tier is still recorded)
        if fringe_tier == "ksharded" and f_rows.size and config.impl != "xla":
            kb_chunk, kb_rows, kb_cols, kb_vals, kb_pos_of_packed = (
                plan_ir.bucket_fringe_kblocks(pr, pc, pv, k_pad, fringe_bk)
            )
        else:
            kb_chunk = np.zeros(1, np.int32)
            kb_rows = np.zeros(1, np.int32)
            kb_cols = np.zeros(1, np.int32)
            kb_vals = np.zeros(1, np.float32)
            kb_pos_of_packed = None

        # inverse row maps for the scatter-free merge: C's row r gathers from
        # packed matrix row gather_src_matrix[r] and/or packed fringe row
        # gather_src_vector[r] (-1 = no contribution from that path)
        gather_src_matrix = np.full(m, -1, np.int32)
        valid_slots = np.flatnonzero(core_row_map >= 0)
        gather_src_matrix[core_row_map[valid_slots]] = valid_slots
        gather_src_vector = np.full(m, -1, np.int32)
        if f_rows.size:
            packed_ids = np.flatnonzero(fringe_row_ids >= 0)
            gather_src_vector[fringe_row_ids[packed_ids]] = packed_ids
        update_maps = plan_ir.build_update_maps(
            rows, cols, vals, shape, part, core_lin, fringe_pos,
            kb_pos_of_packed,
        )
    stats = (
        ("alpha", float(part.alpha)),
        ("nnz", int(part.nnz)),
        ("fringe_nnz", int(part.fringe_nnz)),
        ("core_nnz", int(part.core_nnz)),
        ("fringe_fraction", float(part.fringe_fraction())),
        ("tile_density", float(tile_density)),
        ("reuse_factor", float(reuse_factor)),
        ("num_windows", int(nw)),
        ("num_steps", int(step_window.shape[0])),
        ("t_partition_s", s_part.seconds),
        ("t_reorder_s", s_reorder.seconds),
        ("t_pack_s", s_pack.seconds),
        ("k_pad", k_pad),
        ("fringe_tier", fringe_tier),
        ("fringe_bk", int(fringe_bk)),
        # degree buckets of the fringe stream (0: not bucketed) and its
        # length; fringe_slots / fringe_nnz is the bucketing's padding
        ("fringe_buckets", len(fringe_buckets)),
        ("fringe_slots", int(pr.shape[0]) if f_rows.size else 0),
        ("matrix_format", matrix_format),
        ("format_params", tuple(format_params)),
        # zero fraction of the *active* tiles — the padding waste the
        # structured formats remove (0 when there is no core path)
        ("padding_waste",
         float(1.0 - tile_density) if part.core_nnz else 0.0),
    )
    with span("upload"):
        return NeutronPlan(
            step_window=jnp.asarray(step_window),
            step_col=jnp.asarray(step_col),
            flat_values=jnp.asarray(flat_values),
            core_row_map=jnp.asarray(core_row_map),
            fringe_rows=jnp.asarray(pr),
            fringe_cols=jnp.asarray(pc),
            fringe_vals=jnp.asarray(pv),
            fringe_row_ids=jnp.asarray(fringe_row_ids.astype(np.int32)),
            col_perm=jnp.asarray(col_perm.astype(np.int32)),
            gather_src_matrix=jnp.asarray(gather_src_matrix),
            gather_src_vector=jnp.asarray(gather_src_vector),
            fringe_kb_chunk=jnp.asarray(kb_chunk),
            fringe_kb_rows=jnp.asarray(kb_rows),
            fringe_kb_cols=jnp.asarray(kb_cols),
            fringe_kb_vals=jnp.asarray(kb_vals),
            nm_values=jnp.asarray(nm_payload[0]),
            nm_codes=jnp.asarray(nm_payload[1]),
            bitmap_words=jnp.asarray(bitmap_payload[0]),
            bitmap_values=jnp.asarray(bitmap_payload[1]),
            shape=tuple(shape),
            config=config,
            stats=stats,
            fringe_tier=fringe_tier,
            fringe_bk=int(fringe_bk),
            matrix_format=matrix_format,
            format_params=tuple(format_params),
            fringe_buckets=fringe_buckets,
            update_maps=update_maps,
        )


# --- multi-device sharded plan build ----------------------------------------
# The window-cost model that balances the two intra-chip engine paths also
# balances inter-device shards: row-windows are LPT-assigned to mesh devices
# by coordinator.balance_row_window_list over cost-model window costs, each
# shard gets its own NeutronPlan (padded to mesh-uniform static shapes so one
# shard_map body serves every device), and since every shard owns a disjoint
# set of output rows the merge is an all-gather of packed rows followed by
# one gather — no psum, no scatter-add.


def _place_on_mesh(arrays, mesh, axis_name: str, stacked: bool) -> Tuple:
    """Put plan arrays where the sharded executor reads them.

    Stacked per-shard leaves split along their leading shard axis (each
    device holds only its own shard); everything else is replicated.  Left
    on the default device, every dispatch would first copy the whole plan
    out of device 0.
    """
    def spec(x):
        if stacked:
            return leading_axis_spec(x.ndim, axis_name)
        return replicated_spec(x.ndim)

    return tuple(jax.device_put(x, NamedSharding(mesh, spec(x)))
                 for x in arrays)


def prepare_sharded(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    shape: Tuple[int, int],
    mesh: Any,
    config: SpmmConfig = SpmmConfig(),
    cost_model: Optional[EngineCostModel] = None,
    shard_axis: str = "auto",
    axis_name: Optional[str] = None,
) -> ShardedPlan:
    """Partition the SpMM across ``mesh`` and build per-shard plans.

    ``shard_axis="auto"`` lets cost_model.select_shard_axis pick between
    sharding output rows (balanced window lists, plan state fully
    distributed) and replicating the plan while sharding RHS columns
    (perfectly balanced but plan-replicated; chosen when window costs are
    too skewed or too few).  The returned plan executes via
    ``execute_sharded``.
    """
    m, k = shape
    rows, cols, vals = plan_ir.validate_coo(rows, cols, vals, shape)
    if config.reorder_cols:
        raise ValueError(
            "prepare_sharded does not support reorder_cols=True: per-shard "
            "column permutations cannot share one B operand"
        )
    axis_name = axis_name or mesh.axis_names[0]
    n_shards = int(mesh.shape[axis_name])
    cm = cost_model if cost_model is not None else resolve_cost_model(
        "spmm", int(m), int(k), int(rows.shape[0]), config
    )
    # tuned (bm, bk) resolves once here, at the global shape, so window
    # balancing, the per-shard sub-prepares (tuning suppressed), and the
    # mesh-uniform signature all agree on one tile shape
    if config.autotune:
        ts = cm.tile_shape(int(m), int(k), config.bn, int(rows.shape[0]))
        if ts is not None:
            config = dataclasses.replace(config, bm=int(ts[0]), bk=int(ts[1]))
    # per-shard prepares always build the general payload: structured
    # leaves would need mesh-uniform packed shapes across shards with
    # different patterns, so the fast lane stays single-device for now.
    # Only the sub-prepares see the override — the ShardedPlan keeps the
    # caller's config, so registry fingerprints keyed on it still match.
    shard_config = dataclasses.replace(config, structure_hint="general")

    wc = window_costs_from_coo(rows, m, config.bm, k, cm, alpha=config.alpha)
    decision = select_shard_axis(
        wc, n_shards, imbalance_threshold=cm.imbalance_threshold()
    )
    if shard_axis == "auto":
        shard_axis = decision.shard_axis
    if shard_axis not in ("rows", "rhs"):
        raise ValueError(f"shard_axis must be rows|rhs|auto, got {shard_axis!r}")

    base_stats = (
        ("n_shards", n_shards),
        ("shard_axis", shard_axis),
        ("auto_shard_axis", decision.shard_axis),
        ("rows_imbalance_est", decision.rows_imbalance),
        ("num_windows_global", int(wc.shape[0])),
    )

    if shard_axis == "rhs":
        plan = prepare(rows, cols, vals, shape, shard_config, cm,
                       _shard_part=True)
        um = plan.update_maps
        smaps = ShardedUpdateMaps(
            shape=tuple(shape), rows=um.rows, cols=um.cols, vals=um.vals,
            shard_of_nnz=np.zeros(um.nnz, np.int64),
            local_of_nnz=np.arange(um.nnz, dtype=np.int64),
            shard_maps=(um,),
            key_sorted=um.key_sorted, key_order=um.key_order,
        )
        return ShardedPlan(
            leaves=_place_on_mesh(plan_ir.plan_leaves(plan), mesh, axis_name,
                                  stacked=False),
            sig=plan.signature(), mesh=mesh,
            axis_name=axis_name, shard_axis="rhs", n_shards=n_shards,
            assemble=None, shape=tuple(shape), config=config,
            stats=base_stats + (("nnz", int(rows.shape[0])),),
            update_maps=smaps,
        )

    # --- rows axis: LPT-balanced window lists -> per-shard sub-problems ---
    # Zero-cost (empty) windows are spread by row load *after* the LPT pass:
    # fed to LPT directly they all tie-break onto one shard (+0 never moves
    # argmin), inflating that shard's row count — and with it m_loc_max,
    # i.e. every shard's padded problem size and the all-gather volume.
    nw = int(wc.shape[0])
    costed = np.flatnonzero(wc > 0)
    empty = np.flatnonzero(wc == 0)
    assign_costed = balance_row_window_list(wc[costed], n_shards)
    lists = [list(costed[a]) for a in assign_costed]
    rows_w_all = np.minimum(
        (np.arange(nw, dtype=np.int64) + 1) * config.bm, m
    ) - np.arange(nw, dtype=np.int64) * config.bm
    row_loads = np.array([int(rows_w_all[li].sum()) for li in lists])
    for w in empty:
        s = int(np.argmin(row_loads))
        lists[s].append(int(w))
        row_loads[s] += int(rows_w_all[w])
    assignment = [np.asarray(li, np.int64) for li in lists]
    imbalance = list_imbalance(assignment, wc) if nw else 1.0
    shard_of_window = np.zeros(nw, np.int64)
    local_window_start = np.zeros(nw, np.int64)
    m_loc = np.zeros(n_shards, np.int64)
    for s, wins in enumerate(assignment):
        wins = np.sort(wins)  # ascending original order within the shard
        sizes = np.minimum((wins + 1) * config.bm, m) - wins * config.bm
        starts = np.cumsum(sizes) - sizes
        shard_of_window[wins] = s
        local_window_start[wins] = starts
        m_loc[s] = int(sizes.sum())
    m_loc_max = int(m_loc.max()) if n_shards else 0

    # per-shard prepare: every shard is a self-contained (m_loc_max, k)
    # problem over locally-relabeled rows.  The per-shard fringe dispatch
    # tier is forced off (budget 0) because the mesh-uniform tier is chosen
    # below from the *largest* shard and re-bucketed once for all shards.
    sub_cfg = dataclasses.replace(shard_config, fringe_vmem_budget=0)
    row_window = rows // config.bm if rows.size else rows
    plans: List[NeutronPlan] = []
    shard_idx: List[np.ndarray] = []  # global nnz ids per shard
    for s in range(n_shards):
        mask = (
            shard_of_window[row_window] == s if rows.size
            else np.zeros(0, bool)
        )
        local_rows = (
            local_window_start[row_window[mask]] + rows[mask] % config.bm
        )
        shard_idx.append(np.flatnonzero(mask))
        plans.append(prepare(
            local_rows, cols[mask], vals[mask], (m_loc_max, k), sub_cfg, cm,
            _shard_part=True,
        ))

    # --- mesh-uniform static structure: pad every leaf to the max ---------
    cfg = config
    k_pad = ((k + cfg.bk - 1) // cfg.bk) * cfg.bk
    nw_max = max(p.num_windows for p in plans)
    t_max = max(int(p.step_window.shape[0]) for p in plans)
    nnzf_max = max(int(p.fringe_rows.shape[0]) for p in plans)
    nfr_max = max(int(p.fringe_row_ids.shape[0]) for p in plans)
    has_core = any(p.has_core for p in plans)
    has_fringe = any(p.has_fringe for p in plans)
    u_tier, u_bk = cm.select_fringe_tier(
        k_pad, nfr_max, cfg.bn, vmem_budget=cfg.fringe_vmem_budget,
        nnz=nnzf_max,
    )
    kb_streams = []
    for p in plans:
        if u_tier == "ksharded" and p.has_fringe and cfg.impl != "xla":
            kb_streams.append(plan_ir.bucket_fringe_kblocks(
                np.asarray(p.fringe_rows), np.asarray(p.fringe_cols),
                np.asarray(p.fringe_vals), k_pad, u_bk,
            ))
        else:
            kb_streams.append((
                np.zeros(1, np.int32), np.zeros(1, np.int32),
                np.zeros(1, np.int32), np.zeros(1, np.float32), None,
            ))
    nch_max = max(s[0].shape[0] for s in kb_streams)
    nnzkb_max = max(s[1].shape[0] for s in kb_streams)

    # the kernel window count grows by one: padded tile-stream steps target
    # the dedicated window nw_max, never a real slot (see stack_shard_leaves)
    nw_kernel = nw_max + 1
    leaves = _place_on_mesh(plan_ir.stack_shard_leaves(
        plans, kb_streams, t_max, nw_max, nnzf_max, nch_max, nnzkb_max
    ), mesh, axis_name, stacked=True)

    sig = (
        PLAN_FORMAT_VERSION,
        (m_loc_max, k), cfg.bm, cfg.bk, cfg.bn, cfg.impl, cfg.reorder_cols,
        cfg.fringe_chunk, nw_kernel, t_max, nnzf_max, nfr_max,
        has_core, has_fringe, u_tier, int(u_bk), nch_max, nnzkb_max,
        "general", (0, 0), (),
    )

    # COO->slot maps: shard-local sub-plan maps (padding is prefix-
    # preserving, so their slots stay valid in the stacked leaves), with
    # kb_pos rebucketed under the mesh-uniform tier chosen above
    shard_of_nnz = (
        shard_of_window[row_window] if rows.size else np.zeros(0, np.int64)
    )
    local_of_nnz = np.zeros(rows.shape[0], np.int64)
    shard_maps = []
    for s, (p, kb) in enumerate(zip(plans, kb_streams)):
        local_of_nnz[shard_idx[s]] = np.arange(shard_idx[s].size)
        um = p.update_maps
        if kb[4] is not None:
            kb_pos = np.where(
                um.fringe_pos >= 0,
                kb[4][np.clip(um.fringe_pos, 0, None)], -1,
            )
        else:
            kb_pos = np.full(um.nnz, -1, np.int64)
        shard_maps.append(dataclasses.replace(um, kb_pos=kb_pos))
    key_sorted, key_order = plan_ir.build_key_index(rows, cols, k)
    smaps = ShardedUpdateMaps(
        shape=tuple(shape), rows=rows, cols=cols, vals=vals.copy(),
        shard_of_nnz=shard_of_nnz, local_of_nnz=local_of_nnz,
        shard_maps=tuple(shard_maps),
        key_sorted=key_sorted, key_order=key_order,
    )

    # original row r lives in shard shard_of_window[r//bm] at local slot
    # local_window_start[..] + r%bm; the all-gathered stack is row-major in
    # (shard, local), so one flat index gathers the final C
    if m:
        rw = np.arange(m, dtype=np.int64) // cfg.bm
        assemble = (
            shard_of_window[rw] * m_loc_max
            + local_window_start[rw] + np.arange(m, dtype=np.int64) % cfg.bm
        ).astype(np.int32)
    else:
        assemble = np.zeros(0, np.int32)

    stats = base_stats + (
        ("rows_imbalance", float(imbalance)),
        ("shard_rows", tuple(int(x) for x in m_loc)),
        ("shard_nnz", tuple(int(p.stats_dict["nnz"]) for p in plans)),
        ("rows_per_shard_padded", m_loc_max),
        ("fringe_tier", u_tier),
        ("fringe_bk", int(u_bk)),
    )
    return ShardedPlan(
        leaves=leaves, sig=sig, mesh=mesh, axis_name=axis_name,
        shard_axis="rows", n_shards=n_shards,
        assemble=_place_on_mesh((assemble,), mesh, axis_name,
                                stacked=False)[0],
        shape=tuple(shape), config=config,
        stats=stats, update_maps=smaps, rows_per_shard=m_loc_max,
    )
