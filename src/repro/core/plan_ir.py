"""Plan IR: the pytree-centric representation every execution layer shares.

This module owns the *static* side of NeutronSparse execution — the leaf
layout, signatures, padding rules, and COO->slot inverse maps of the three
plan families:

- :class:`NeutronPlan` — a single-device prepared plan (flat tile stream for
  the matrix engine, packed fringe COO + optional k-bucketed stream for the
  vector engine, inverse row maps for the scatter-free gather merge);
- :class:`ShardedPlan` — per-shard ``NeutronPlan`` leaves stacked along a
  leading mesh axis (``shard_axis="rows"``) or one replicated plan with the
  RHS column-sharded (``shard_axis="rhs"``);
- :class:`DeltaFringe` / :class:`ShardedDeltaFringe` — the capacity-padded
  structural-delta sidecar the dynamic subsystem merges additively into the
  fused program (the sharded form routes every delta row to its owning
  shard so the merge happens *inside* the ``shard_map`` body).

The executor pipeline (``repro.exec``) consumes only what is defined here:
``plan_leaves`` ordering, ``LEAF_RANKS``, signature tuples, and the padding
invariants (padded tile steps carry zero values into a dedicated extra
window; padded fringe/kb entries are accumulate-inert; padded gather slots
are -1).  Plan *construction* lives in ``core.spmm``; this module has no
knowledge of meshes beyond leaf stacking and never imports upward
(``exec``/``dynamic``/``serve`` — enforced by ``tools/check_layers.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import PlanBuildError
from ..kernels import ops
from .cost_model import select_fringe_tier

# Plan-format version: the leading element of every plan signature.  Bump it
# whenever the static plan layout changes (leaf set, bucketing scheme, merge
# semantics) so (a) executor caches never alias plans built by different
# layouts within one process, and (b) the persistent plan registry
# (dynamic/registry.py) can refuse plans serialized under an older layout
# instead of misinterpreting their arrays.
# v2: structured-sparsity payload leaves (N:M + bitmap) and the trailing
# (matrix_format, format_params) signature fields.
# v3: XLA-tier fringes are stored degree-bucketed (padded, renumbered
# rows) and the signature ends with the bucket ladder.
PLAN_FORMAT_VERSION = 3

PATH_CORE = 0
PATH_FRINGE = 1

# Fixed positions inside ``NeutronPlan.signature()`` tuples that the
# exec-layer health/degradation logic keys on.  Anyone reordering the
# signature must update these (and bump PLAN_FORMAT_VERSION).
SIG_IMPL = 5
SIG_FRINGE_TIER = 14
SIG_MATRIX_FORMAT = 18
SIG_FORMAT_PARAMS = 19

# matrix-path payload encodings (core.formats pack/unpack pairs); the
# signature-carried format keeps structured and general plans from ever
# aliasing one cached executor
MATRIX_FORMATS = ("general", "nm", "bitmap")


def sig_impl(sig: Tuple) -> Optional[str]:
    """The kernel impl of a plan-style signature; None for non-plan sigs
    (sharded wrappers, delta sidecars)."""
    if isinstance(sig, tuple) and len(sig) > SIG_IMPL and \
            sig[0] == PLAN_FORMAT_VERSION:
        return sig[SIG_IMPL]
    return None


def xla_fallback_sig(sig: Tuple) -> Tuple:
    """The same plan signature demoted to the XLA reference impl.

    The fused body dispatches entirely on the signature, and ``impl ==
    "xla"`` routes both paths through the reference einsum/gather before
    any tier logic — so swapping index ``SIG_IMPL`` is a complete demotion
    that reuses the plan's existing leaves unchanged.
    """
    if sig_impl(sig) is None:
        raise ValueError(f"not a plan-style signature: {sig!r}")
    demoted = list(sig)
    demoted[SIG_IMPL] = "xla"
    return tuple(demoted)


def sig_matrix_format(sig: Tuple) -> Optional[str]:
    """The matrix-path payload format of a plan-style signature; None for
    non-plan sigs (sharded wrappers, delta sidecars)."""
    if sig_impl(sig) is not None and len(sig) > SIG_MATRIX_FORMAT:
        return sig[SIG_MATRIX_FORMAT]
    return None


def general_format_sig(sig: Tuple) -> Tuple:
    """The same plan signature demoted to the general (flat tile) payload.

    Structured plans keep their general leaves alongside the packed ones,
    so consumers that only understand the flat stream (the delta-merge
    executors, SDDMM) demote the format field rather than the whole impl.
    """
    if sig_matrix_format(sig) in (None, "general"):
        return sig
    demoted = list(sig)
    demoted[SIG_MATRIX_FORMAT] = "general"
    demoted[SIG_FORMAT_PARAMS] = (0, 0)
    return tuple(demoted)


# --- operator tagging --------------------------------------------------------
# Non-SpMM operators on the same plan structure (SDDMM today) reuse the plan
# signature with a trailing ("op", name, *extra) marker.  The suffix keeps
# every positional consumer intact — ``sig[0]`` is still PLAN_FORMAT_VERSION,
# ``sig[SIG_IMPL]`` is still the impl — so health gating, the XLA demotion,
# and the bounded executor LRU all cover tagged signatures for free, while
# ``(op, signature)`` pairs never alias each other's cached executors.

OP_TAG = "op"


def tag_op(sig: Tuple, op: str, *extra) -> Tuple:
    """Suffix a plan signature with an operator tag (hashable extras only)."""
    if sig_impl(sig) is None:
        raise ValueError(f"not a plan-style signature: {sig!r}")
    return sig + ((OP_TAG, op) + tuple(extra),)


def sig_op(sig: Tuple) -> str:
    """Operator name of a signature ("spmm" when untagged)."""
    if (
        isinstance(sig, tuple) and sig
        and isinstance(sig[-1], tuple) and sig[-1]
        and sig[-1][0] == OP_TAG
    ):
        return sig[-1][1]
    return "spmm"


def op_extra(sig: Tuple) -> Tuple:
    """The tag's extra payload (empty for untagged signatures)."""
    if (
        isinstance(sig, tuple) and sig
        and isinstance(sig[-1], tuple) and sig[-1]
        and sig[-1][0] == OP_TAG
    ):
        return tuple(sig[-1][2:])
    return ()


def untag_sig(sig: Tuple) -> Tuple:
    """The base plan signature with any operator tag stripped."""
    if (
        isinstance(sig, tuple) and sig
        and isinstance(sig[-1], tuple) and sig[-1]
        and sig[-1][0] == OP_TAG
    ):
        return sig[:-1]
    return sig


@dataclasses.dataclass(frozen=True)
class SpmmConfig:
    bm: int = 128
    bk: int = 64
    bn: int = 256
    alpha: Optional[float] = None          # override Eq. 3 threshold
    enable_global_reorder: bool = True
    enable_local_reorder: bool = True
    reorder_cols: bool = False             # requires caller to pre-permute B
    enable_col_stage: bool = True          # stage-2 column extraction
    enable_reuse_order: bool = True
    max_clusters: int = 64
    impl: ops.Impl = "xla"
    fringe_chunk: Optional[int] = None     # nonzeros per fringe grid step
    fringe_vmem_budget: Optional[int] = None  # override dispatch-tier budget
    seed: int = 0
    # capacity of the process-wide executor cache (repro.exec): plans built
    # with a set value adjust the cache when they execute; None keeps the
    # current (default generous) capacity
    executor_cache_capacity: Optional[int] = None
    # when a pallas executor fails to build/lower, demote the signature to
    # the XLA reference tier (bounded retry first — see repro.exec.health)
    # instead of raising; False surfaces a KernelLoweringError instead
    degrade_to_xla: bool = True
    # measurement-backed dispatch (core.tuner):
    #   False      — analytic cost model only (the default)
    #   True       — serve decisions from the persisted tuning table;
    #                microbenchmark inline on first sight of a shape class
    #   "offline"  — table-or-analytic, never benchmarks inline (serving
    #                processes; tables come from the offline collector or a
    #                background tune adopted by SpmmService)
    # NOT execution-only: tuned models can change plan *structure* (split,
    # tiers), so autotune stays part of the registry fingerprint.
    autotune: Union[bool, str] = False
    # structured-sparsity hint for the matrix-path payload format:
    #   None          — detect at prepare time, cost model decides
    #   "general"     — force the flat tile stream (skip detection)
    #   "nm"          — use the detected N:M packing; general if none detected
    #   ("nm", n, m)  — assert this exact N:M pattern; PlanBuildError if the
    #                   core stream does not satisfy it
    #   "bitmap"      — force the bitmap-compressed payload
    structure_hint: Optional[Any] = None
    # host-side telemetry (repro.obs): record per-request traces in the
    # obs.TRACES ring.  It makes no call synchronize, and it is never part
    # of signature() — toggling it must not retrace, re-dispatch, or
    # change any numeric output.  The phase spans and scopes that a
    # jax.profiler trace shows need no flag.
    telemetry: bool = False


@dataclasses.dataclass
class UpdateMaps:
    """Host-side COO->slot inverse maps, built once at ``prepare()`` time.

    For every input nonzero ``j`` the maps record which device-resident plan
    slot its value landed in, so the dynamic-update subsystem
    (``dynamic.delta.update_values``) can scatter new values directly into
    the prepared arrays — no re-prepare, no retrace.  ``vals`` tracks the
    *current* value of each nonzero (updates advance it), which the
    structural-delta layer also uses to negate deleted base entries.
    """

    shape: Tuple[int, int]
    rows: np.ndarray             # (nnz,) int64 original COO rows
    cols: np.ndarray             # (nnz,) int64 original COO cols
    vals: np.ndarray             # (nnz,) current values (input dtype)
    path: np.ndarray             # (nnz,) int8 PATH_CORE | PATH_FRINGE
    core_lin: np.ndarray         # (nnz,) int64 flat slot in flat_values, -1
    fringe_pos: np.ndarray       # (nnz,) int64 packed fringe slot, -1
    kb_pos: np.ndarray           # (nnz,) int64 k-bucketed stream slot, -1
    # slot->contributors CSR (duplicates accumulate into one tile cell, so a
    # touched slot is recomputed from every contributor in input order — the
    # same sequential fp32 accumulation prepare() performs, hence updated
    # plans stay bit-identical to a fresh prepare)
    core_lin_sorted: np.ndarray     # core slots sorted
    core_members_sorted: np.ndarray  # nnz ids sorted by (slot, input order)
    # (row, col) -> nnz id lookup (first occurrence wins for duplicates)
    key_sorted: np.ndarray
    key_order: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    def lookup(self, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
        """nnz ids of the given (row, col) pairs; -1 where absent."""
        keys = np.asarray(rows, np.int64) * self.shape[1] + np.asarray(
            cols, np.int64
        )
        pos = np.searchsorted(self.key_sorted, keys)
        pos = np.minimum(pos, max(self.key_sorted.size - 1, 0))
        if self.key_sorted.size == 0:
            return np.full(keys.shape, -1, np.int64)
        found = self.key_sorted[pos] == keys
        return np.where(found, self.key_order[pos], -1)


@dataclasses.dataclass
class ShardedUpdateMaps:
    """COO->slot inverse maps for a rows-sharded plan.

    Global nonzero ``j`` lives in shard ``shard_of_nnz[j]`` at position
    ``local_of_nnz[j]`` of that shard's input arrays; ``shard_maps[s]`` are
    the shard-local :class:`UpdateMaps` into the (prefix-preserving padded)
    stacked leaves.  The global ``rows/cols/vals`` mirror serves the
    structural-delta layer and compaction.
    """

    shape: Tuple[int, int]
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    shard_of_nnz: np.ndarray
    local_of_nnz: np.ndarray
    shard_maps: Tuple[UpdateMaps, ...]
    key_sorted: np.ndarray
    key_order: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    lookup = UpdateMaps.lookup


def build_key_index(
    rows: np.ndarray, cols: np.ndarray, k: int
) -> Tuple[np.ndarray, np.ndarray]:
    key = rows.astype(np.int64) * k + cols
    order = np.argsort(key, kind="stable")
    return key[order], order


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NeutronPlan:
    """Prepared execution plan (jax pytree; shapes static per plan)."""

    # matrix path: flat active-tile stream (window-major under reuse order)
    step_window: jax.Array   # (T,) int32
    step_col: jax.Array      # (T,) int32
    flat_values: jax.Array   # (T, bm, bk)
    core_row_map: jax.Array  # (num_windows*bm,) int32 -> original row (-1 pad)
    # vector path: packed row-sorted fringe COO, or its degree-bucketed
    # relayout when fringe_buckets is set (bucket_fringe_rows)
    fringe_rows: jax.Array   # (nnz_f,) int32 packed ids
    fringe_cols: jax.Array   # (nnz_f,) int32
    fringe_vals: jax.Array   # (nnz_f,)
    fringe_row_ids: jax.Array  # (n_fringe_rows,) int32 original ids; -1
    #                            for a row that only fills a bucket
    col_perm: jax.Array      # (K,) int32 — B row perm (identity unless reorder_cols)
    # scatter-free merge: inverse row maps (original row -> packed slot or -1)
    gather_src_matrix: jax.Array  # (M,) int32 -> packed matrix-path row
    gather_src_vector: jax.Array  # (M,) int32 -> packed vector-path row
    # K-sharded streaming tier: fringe COO re-bucketed by k-block (sorted by
    # (k-block, row, col), per-bucket chunk-padded, columns k-block-local);
    # 1-element dummies unless fringe_tier == "ksharded"
    fringe_kb_chunk: jax.Array  # (num_chunks,) int32, chunk -> k-block id
    fringe_kb_rows: jax.Array   # (num_chunks*chunk,) int32
    fringe_kb_cols: jax.Array   # (num_chunks*chunk,) int32
    fringe_kb_vals: jax.Array   # (num_chunks*chunk,)
    # structured matrix-path payloads (core.formats pack/unpack pairs).
    # Alternative *encodings* of flat_values — the general stream is always
    # built too, so format demotion (dynamic updates, SDDMM, sharding) never
    # needs a re-prepare.  (1, 1, 1) zero dummies unless the plan's
    # matrix_format selects them.
    nm_values: jax.Array        # (T, bm, n*gk) f32 slot-major packed values
    nm_codes: jax.Array         # (T, bm, gk) int32, 8-bit positions per slot
    bitmap_words: jax.Array     # (T, bm, ceil(bk/32)) int32 occupancy bits
    bitmap_values: jax.Array    # (T, bm, row_cap) f32 packed row values

    shape: Tuple[int, int]
    config: SpmmConfig
    stats: Tuple  # immutable (key, value) pairs
    # vector-path kernel dispatch tier chosen at prepare time from the VMEM
    # budget (cost_model.select_fringe_tier): "resident" | "ksharded" | "xla"
    fringe_tier: str = "resident"
    fringe_bk: int = 0           # k-block size of the ksharded tier (0 else)
    # matrix-path payload format chosen at prepare time
    # (cost_model.select_matrix_format): "general" | "nm" | "bitmap"
    matrix_format: str = "general"
    # (n, m) for "nm"; (num_words, row_cap) for "bitmap"; (0, 0) general
    format_params: Tuple[int, int] = (0, 0)
    # ((n_rows_b, width_b), ...) when the fringe stream is degree-bucketed
    # (bucket_fringe_rows; XLA-tier single-device plans), () otherwise
    fringe_buckets: Tuple[Tuple[int, int], ...] = ()
    # host-side COO->slot inverse maps for dynamic value updates.  Not a
    # pytree leaf and not aux data (numpy payloads are unhashable): a plan
    # round-tripped through tree operations comes back with maps=None and
    # simply loses updatability, never correctness.
    update_maps: Optional[UpdateMaps] = None

    def tree_flatten(self):
        leaves = (
            self.step_window, self.step_col, self.flat_values, self.core_row_map,
            self.fringe_rows, self.fringe_cols, self.fringe_vals,
            self.fringe_row_ids, self.col_perm,
            self.gather_src_matrix, self.gather_src_vector,
            self.fringe_kb_chunk, self.fringe_kb_rows,
            self.fringe_kb_cols, self.fringe_kb_vals,
            self.nm_values, self.nm_codes,
            self.bitmap_words, self.bitmap_values,
        )
        return leaves, (
            self.shape, self.config, self.stats,
            self.fringe_tier, self.fringe_bk,
            self.matrix_format, self.format_params, self.fringe_buckets,
        )

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def num_windows(self) -> int:
        return self.core_row_map.shape[0] // self.config.bm

    @property
    def stats_dict(self) -> Dict:
        return dict(self.stats)

    @property
    def has_core(self) -> bool:
        return bool(self.stats_dict["core_nnz"])

    @property
    def has_fringe(self) -> bool:
        return bool(self.stats_dict["fringe_nnz"])

    def signature(self) -> Tuple:
        """Static structure key: plans sharing it reuse one jitted executor.

        Includes the vector-path dispatch tier and its k-block size: two
        plans differing only in tier (e.g. from different VMEM budgets)
        must not alias one cached executor; likewise the fringe's bucket
        ladder, whose shapes the XLA gather-reduce is traced with.  The
        leading element is
        ``PLAN_FORMAT_VERSION`` so executors (and the persistent registry,
        which keys entries by signature) never cross plan-layout versions.
        """
        cfg = self.config
        return (
            PLAN_FORMAT_VERSION,
            self.shape, cfg.bm, cfg.bk, cfg.bn, cfg.impl, cfg.reorder_cols,
            cfg.fringe_chunk, self.num_windows,
            int(self.step_window.shape[0]), int(self.fringe_rows.shape[0]),
            int(self.fringe_row_ids.shape[0]), self.has_core, self.has_fringe,
            self.fringe_tier, self.fringe_bk,
            int(self.fringe_kb_chunk.shape[0]),
            int(self.fringe_kb_rows.shape[0]),
            self.matrix_format, tuple(self.format_params),
            tuple(self.fringe_buckets),
        )


@dataclasses.dataclass
class ShardedPlan:
    """Prepared multi-device execution plan.

    ``shard_axis == "rows"``: plan leaves are stacked along a leading shard
    dim; device s executes shard s's sub-plan and emits its packed
    ``(rows_per_shard, N)`` block; ``assemble`` maps original rows into the
    all-gathered stack.  ``shard_axis == "rhs"``: one replicated plan, B
    columns sharded (the cost model picks this when the row-window
    distribution is too skewed to balance, or there are fewer windows than
    devices).
    """

    leaves: Tuple[jax.Array, ...]   # fused-body args (stacked iff "rows")
    sig: Tuple                      # mesh-uniform per-shard signature
    mesh: Any
    axis_name: str
    shard_axis: str                 # "rows" | "rhs"
    n_shards: int
    assemble: Optional[jax.Array]   # (M,) int32 into stacked rows ("rows")
    shape: Tuple[int, int]
    config: SpmmConfig
    stats: Tuple
    # host-side COO->slot maps for dynamic value updates (see UpdateMaps)
    update_maps: Optional[ShardedUpdateMaps] = None
    # padded per-shard row count ("rows" axis; 0 for "rhs").  assemble[r] ==
    # shard_of(r) * rows_per_shard + local_of(r): the dynamic layer uses
    # this to route delta-sidecar rows to their owning shards.
    rows_per_shard: int = 0

    @property
    def stats_dict(self) -> Dict:
        return dict(self.stats)

    def signature(self) -> Tuple:
        """Static structure key; never collides with NeutronPlan.signature()
        (distinct leading tag + arity), so sharded executors share the same
        cache machinery as the fused ones without aliasing."""
        return (
            "sharded", self.shard_axis, self.n_shards, self.axis_name,
            tuple(self.mesh.devices.shape), self.sig,
        )


# --- executor-body leaf ordering -------------------------------------------
# Every executor flavor takes the same 17 plan leaves (then optionally the 8
# delta-sidecar leaves, then b); the pipeline builds PartitionSpecs from the
# per-leaf ranks below.  The four trailing leaves are the structured
# matrix-path payloads — (1, 1, 1) dummies on general-format plans.

N_PLAN_LEAVES = 17   # executor-body plan args (everything before b)
LEAF_RANKS = (1, 1, 3, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 3, 3, 3, 3)

# positions of the value-carrying leaves in plan_leaves order — the slots
# dynamic value updates scatter into (dynamic/delta.py patches the sharded
# stacked leaves by these indices)
LEAF_FLAT_VALUES = 2
LEAF_FRINGE_VALS = 5
LEAF_KB_VALS = 12
LEAF_COL_PERM = 6

N_DELTA_LEAVES = 8   # d_rows, d_cols, d_vals, d_gsrc, kb_chunk/rows/cols/vals
DELTA_LEAF_RANKS = (1, 1, 1, 1, 1, 1, 1, 1)


def plan_leaves(plan: NeutronPlan) -> Tuple[jax.Array, ...]:
    """Executor-body args in fused-body order (without b)."""
    return (
        plan.step_window, plan.step_col, plan.flat_values,
        plan.fringe_rows, plan.fringe_cols, plan.fringe_vals,
        plan.col_perm, plan.gather_src_matrix, plan.gather_src_vector,
        plan.fringe_kb_chunk, plan.fringe_kb_rows,
        plan.fringe_kb_cols, plan.fringe_kb_vals,
        plan.nm_values, plan.nm_codes,
        plan.bitmap_words, plan.bitmap_values,
    )


# --- validation -------------------------------------------------------------


def validate_coo(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
    shape: Tuple[int, int],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reject malformed COO input with a descriptive error.

    Out-of-range indices previously surfaced as cryptic bincount/fancy-index
    failures, and *negative* indices silently wrapped around python-style —
    aliasing nonzeros onto the wrong rows without any error at all.
    """
    m, k = shape
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    if not (rows.ndim == cols.ndim == vals.ndim == 1):
        raise ValueError(
            f"COO triplets must be 1-D; got rows.ndim={rows.ndim} "
            f"cols.ndim={cols.ndim} vals.ndim={vals.ndim}"
        )
    if not (rows.shape == cols.shape == vals.shape):
        raise ValueError(
            f"COO triplet lengths disagree: rows={rows.shape[0]} "
            f"cols={cols.shape[0]} vals={vals.shape[0]}"
        )
    for name, arr in (("rows", rows), ("cols", cols)):
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(f"{name} must be an integer array, got {arr.dtype}")
    if rows.size:
        if int(rows.min()) < 0 or int(rows.max()) >= m:
            raise ValueError(
                f"row indices out of range for shape {shape}: "
                f"[{int(rows.min())}, {int(rows.max())}]"
            )
        if int(cols.min()) < 0 or int(cols.max()) >= k:
            raise ValueError(
                f"col indices out of range for shape {shape}: "
                f"[{int(cols.min())}, {int(cols.max())}]"
            )
    return rows.astype(np.int64), cols.astype(np.int64), vals


def validate_rhs(b: jax.Array, shape: Tuple[int, int]) -> None:
    """Reject an operand whose K disagrees with the plan.

    Without this, a short b zero-pads up to the plan's k_pad inside the
    executor — every kernel shape matches and nonzeros beyond b's K
    silently multiply against zero rows (wrong output, no error).
    """
    if b.ndim not in (2, 3):
        raise ValueError(
            f"b must be (K, N) or (batch, K, N); got shape {tuple(b.shape)}"
        )
    if int(b.shape[-2]) != shape[1]:
        raise ValueError(
            f"operand K={int(b.shape[-2])} does not match the plan's "
            f"K={shape[1]} (plan shape {shape})"
        )


# --- padding + merge helpers ------------------------------------------------


def pad_to(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    """Pad axis 0 of ``a`` to length ``n`` with ``fill``."""
    if a.shape[0] == n:
        return a
    pad = np.full((n - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad])


def permute_pad_b(
    b: jax.Array, col_perm: jax.Array, reorder_cols: bool, bk: int, bn: int
) -> jax.Array:
    """Apply the column permutation to B rows and pad K/N to block multiples
    (shared by the per-path executors and every fused-body flavor)."""
    k, n = b.shape
    if reorder_cols:
        b = b[col_perm]
    k_pad = ((k + bk - 1) // bk) * bk
    n_pad = ((n + bn - 1) // bn) * bn
    if k_pad != k or n_pad != n:
        b = jnp.pad(b, ((0, k_pad - k), (0, n_pad - n)))
    return b


def gather_rows(packed: jax.Array, src: jax.Array) -> jax.Array:
    """Scatter-free merge: out[r] = packed[src[r]] where src[r] >= 0 else 0."""
    idx = jnp.clip(src, 0, packed.shape[0] - 1)
    return jnp.where((src >= 0)[:, None], packed[idx], 0.0)


# --- k-bucketed fringe stream -----------------------------------------------


def bucket_fringe_kblocks(
    pr: np.ndarray, pc: np.ndarray, pv: np.ndarray,
    k_pad: int, fringe_bk: int, chunk: int = ops.FRINGE_STEP,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Relayout packed fringe COO for the K-sharded streaming kernel.

    Nonzeros sorted by (k-block, row, col), per-bucket padded to a
    ``chunk`` multiple with zero-value entries (the kernel's grid step, so
    each chunk is one step), columns made k-block-local; empty
    k-blocks get no chunks (their B slices are never fetched).  Shared by
    ``prepare`` and ``prepare_sharded`` (which re-buckets every shard with
    one mesh-wide bk so all shards run the same kernel).  The trailing
    return is ``pos_of_packed``: the bucketed-stream slot of each packed
    fringe entry, inverted into the plan's COO->slot update maps so dynamic
    value updates can patch the bucketed stream in place.
    """
    nkb_f = (k_pad + fringe_bk - 1) // fringe_bk
    kb = pc.astype(np.int64) // fringe_bk
    order_kb = np.argsort(kb, kind="stable")  # keeps (row, col) per kb
    kbs = kb[order_kb]
    counts = np.bincount(kbs, minlength=nkb_f)
    padded = ((counts + chunk - 1) // chunk) * chunk
    src_start = np.cumsum(counts) - counts
    dst_start = np.cumsum(padded) - padded
    dest = dst_start[kbs] + np.arange(kbs.size) - src_start[kbs]
    total_kb = int(padded.sum())
    kb_rows = np.zeros(total_kb, np.int32)
    kb_rows[dest] = pr[order_kb]
    kb_cols = np.zeros(total_kb, np.int32)
    kb_cols[dest] = (pc[order_kb] % fringe_bk).astype(np.int32)
    kb_vals = np.zeros(total_kb, pv.dtype)
    kb_vals[dest] = pv[order_kb]
    kb_chunk = np.repeat(
        np.arange(nkb_f, dtype=np.int32), padded // chunk
    )
    pos_of_packed = np.empty(kbs.size, np.int64)
    pos_of_packed[order_kb] = dest
    return kb_chunk, kb_rows, kb_cols, kb_vals, pos_of_packed


# --- degree-bucketed fringe stream ------------------------------------------


# rows per bucket are a multiple of the sublane tile, so a bucket's
# (width, n_rows, N) gather and its 2-D form (width * n_rows, N) share one
# tiled layout and reshape without a relayout copy
BUCKET_ROW_ALIGN = 8


def bucket_fringe_rows(
    pr: np.ndarray, pc: np.ndarray, pv: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray,
           Tuple[Tuple[int, int], ...]]:
    """Relayout the packed row-sorted fringe as degree buckets (ELL).

    Each packed row gets a width, its degree rounded up to a power of two;
    rows are renumbered bucket-major by width ascending (original order
    within a bucket), each bucket is filled up to a multiple of
    ``BUCKET_ROW_ALIGN`` rows with rows that hold no nonzero, and each
    row's run is padded to its width with ``(row, col, 0.0)`` entries,
    ``col`` the row's first column (0 for a filler row).  Bucket ``b`` is
    the next ``n_rows_b * width_b`` slots of the stream, stored
    width-major: slot ``j * n_rows_b + i`` holds entry ``j`` of the
    bucket's row ``i``, so the XLA gather-reduce
    (``ref.bucketed_gather_spmm``) reads each width position as one
    contiguous ``(n_rows_b,)`` slice.  The result is still a COO stream
    whose padding adds zero (rows unsorted within a bucket), so every
    scatter consumer reads it unchanged.

    Returns ``(rows, cols, vals, row_order, slot_of_entry, ladder)``:
    ``row_order[new]`` is the old packed row of each new one (-1 for a
    filler row), ``slot_of_entry[i]`` the slot of packed entry ``i``, and
    ``ladder = ((n_rows_b, width_b), ...)``.
    """
    n_rows = int(pr[-1]) + 1
    deg = np.bincount(pr, minlength=n_rows)
    # 2**bit_length(deg - 1): the smallest power of two >= deg
    width = np.left_shift(1, np.frexp(deg - 1)[1].astype(np.int64))
    order = np.argsort(width, kind="stable")
    widths, counts = np.unique(width[order], return_counts=True)
    align = BUCKET_ROW_ALIGN
    n_b = -(-counts // align) * align
    first_row = np.cumsum(n_b) - n_b
    first_slot = np.cumsum(n_b * widths) - n_b * widths
    # old row -> bucket, new row
    bkt = np.empty(n_rows, np.int64)
    bkt[order] = np.repeat(np.arange(widths.size), counts)
    new_of_old = np.empty(n_rows, np.int64)
    new_of_old[order] = (np.arange(n_rows)
                         - np.repeat(np.cumsum(counts) - counts, counts)
                         + np.repeat(first_row, counts))
    row_order = np.full(int(n_b.sum()), -1, np.int64)
    row_order[new_of_old] = np.arange(n_rows)
    # every slot's row: bucket b repeats its rows width_b times
    slot_bkt = np.repeat(np.arange(widths.size), n_b * widths)
    local = np.arange(slot_bkt.size) - first_slot[slot_bkt]
    rows = (first_row[slot_bkt] + local % n_b[slot_bkt]).astype(np.int32)
    entry_start = np.cumsum(deg) - deg
    first_col = np.zeros(row_order.size, np.int32)
    first_col[new_of_old] = pc[entry_start]
    cols = first_col[rows]
    b = bkt[pr]
    slot = (first_slot[b] + (np.arange(pr.size) - entry_start[pr]) * n_b[b]
            + new_of_old[pr] - first_row[b])
    cols[slot] = pc
    vals = np.zeros(rows.size, pv.dtype)
    vals[slot] = pv
    ladder = tuple((int(n), int(w)) for n, w in zip(n_b, widths))
    return rows, cols, vals, row_order, slot, ladder


# --- update-map construction ------------------------------------------------


def build_update_maps(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
    shape: Tuple[int, int], part, core_lin: np.ndarray,
    fringe_pos: np.ndarray, kb_pos_of_packed: Optional[np.ndarray],
) -> UpdateMaps:
    """Invert prepare()'s packing into per-nonzero COO->slot maps."""
    nnz = rows.shape[0]
    path = np.full(nnz, PATH_FRINGE, np.int8)
    core_lin_of = np.full(nnz, -1, np.int64)
    fringe_pos_of = np.full(nnz, -1, np.int64)
    kb_pos_of = np.full(nnz, -1, np.int64)
    core_idx = (
        part.core_idx if part.core_idx is not None
        else np.zeros(0, np.int64)
    )
    fringe_idx = (
        part.fringe_idx if part.fringe_idx is not None
        else np.zeros(0, np.int64)
    )
    if core_idx.size:
        path[core_idx] = PATH_CORE
        core_lin_of[core_idx] = core_lin
    if fringe_idx.size:
        fringe_pos_of[fringe_idx] = fringe_pos
        if kb_pos_of_packed is not None:
            kb_pos_of[fringe_idx] = kb_pos_of_packed[fringe_pos]
    # stable sort keeps input order within a slot — the accumulation order
    # np.add.at used when the slot was first written
    cm_order = np.argsort(core_lin, kind="stable")
    key_sorted, key_order = build_key_index(rows, cols, shape[1])
    return UpdateMaps(
        shape=tuple(shape), rows=rows, cols=cols, vals=vals.copy(),
        path=path, core_lin=core_lin_of, fringe_pos=fringe_pos_of,
        kb_pos=kb_pos_of,
        core_lin_sorted=core_lin[cm_order],
        core_members_sorted=core_idx[cm_order],
        key_sorted=key_sorted, key_order=key_order,
    )


# --- SDDMM gather maps -------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SddmmMaps:
    """Device-resident index maps for SDDMM over a plan's pattern.

    SDDMM inverts the SpMM dataflow: the matrix engine computes dense
    ``X_window @ Y_kblock`` tiles for exactly the (window, k-block) pairs the
    plan's tile stream names, and each core nonzero's value is *extracted*
    from the tile stream at the linear slot ``prepare()`` scattered its
    value into (``UpdateMaps.core_lin``).  Fringe nonzeros bypass the tile
    path and compute their dot products by row gather.  Output order is the
    plan's original COO input order — layout-compatible with
    ``dynamic.update_values(plan, arange(nnz), out)``.

    The merge reads each path's values once, where that path wrote them:
    ``c_lin`` names only the core nonzeros' slots, and ``place`` — fixed
    per pattern, a permutation of ``0..nnz-1`` — gives the COO position of
    each value of ``[core values..., fringe values...]``, both subsets in
    COO order.  Extraction (unlike accumulation) is duplicate-safe:
    duplicate COO triplets share a tile slot but read the same dot product.
    """

    g_rows: jax.Array    # (nnz,) int32 original rows, every nonzero
    g_cols: jax.Array    # (nnz,) int32 original cols, every nonzero
    c_lin: jax.Array     # (n_core,) int32 flat tile slot of each core nonzero
    place: jax.Array     # (nnz,) int32 COO position of core, then fringe
    f_rows: jax.Array    # (nnz_f,) int32 fringe-subset rows (>=1, padded)
    f_cols: jax.Array    # (nnz_f,) int32 fringe-subset cols
    nnz: int
    nnz_f: int           # padded fringe-subset length

    def leaves(self) -> Tuple[jax.Array, ...]:
        return (self.g_rows, self.g_cols, self.c_lin, self.place,
                self.f_rows, self.f_cols)


N_SDDMM_MAP_LEAVES = 6
# sddmm executor-body args before the (x, y) operands: the plan-side tile
# metadata (step_window, step_col, core_row_map, col_perm) + the map leaves
N_SDDMM_BODY_LEAVES = 4 + N_SDDMM_MAP_LEAVES


def sddmm_body_leaves(
    plan: NeutronPlan, maps: "SddmmMaps"
) -> Tuple[jax.Array, ...]:
    """SDDMM executor-body args in fused-body order (without x, y)."""
    return (
        plan.step_window, plan.step_col, plan.core_row_map, plan.col_perm,
    ) + maps.leaves()


def build_sddmm_maps(plan: NeutronPlan) -> SddmmMaps:
    """Invert a plan's update maps into SDDMM extraction indices (cached on
    the maps instance — structure-only, so value updates never stale it)."""
    maps = plan.update_maps
    if maps is None:
        raise PlanBuildError(
            "sddmm needs the plan's COO->slot update maps; this plan lost "
            "them (plans round-tripped through jax tree ops come back with "
            "update_maps=None) — re-prepare from COO to use sddmm"
        )
    cached = getattr(maps, "_sddmm_maps", None)
    if cached is not None:
        return cached
    core = maps.core_lin >= 0
    c_pos = np.flatnonzero(core)
    f_pos = np.flatnonzero(~core)
    f_rows = maps.rows[f_pos]
    f_cols = maps.cols[f_pos]
    if f_rows.size == 0:  # keep the gather operand nonempty for the kernels
        f_rows = np.zeros(1, np.int64)
        f_cols = np.zeros(1, np.int64)
    built = SddmmMaps(
        g_rows=jnp.asarray(maps.rows, jnp.int32),
        g_cols=jnp.asarray(maps.cols, jnp.int32),
        c_lin=jnp.asarray(maps.core_lin[c_pos], jnp.int32),
        place=jnp.asarray(np.concatenate([c_pos, f_pos]), jnp.int32),
        f_rows=jnp.asarray(f_rows, jnp.int32),
        f_cols=jnp.asarray(f_cols, jnp.int32),
        nnz=maps.nnz, nnz_f=int(f_rows.shape[0]),
    )
    maps._sddmm_maps = built
    return built


# --- mesh-uniform leaf stacking ---------------------------------------------


def stack_shard_leaves(
    plans: Sequence[NeutronPlan],
    kb_streams: Sequence[Tuple],
    t_max: int, nw_max: int, nnzf_max: int,
    nch_max: int, nnzkb_max: int,
) -> Tuple[jax.Array, ...]:
    """Pad every shard's leaves to mesh-uniform shapes and stack them.

    Padding is inert everywhere: padded tile steps carry zero values into
    the dedicated extra window ``nw_max`` (targeting window 0 would
    duplicate a real (window, k-block) pair and break the densified GEMM's
    assume_unique index-scatter), padded fringe entries add 0.0 to packed
    row 0 (the fringe kernels accumulate, never overwrite), padded kb
    chunks target k-block 0 with zero values, and padded gather slots are
    -1 (no contribution).
    """
    stacked: List[List[np.ndarray]] = [[] for _ in range(N_PLAN_LEAVES)]
    for p, kb in zip(plans, kb_streams):
        leaves = [np.asarray(x) for x in plan_leaves(p)]
        sw, sc, fv, fr, fc, fvv, cp, gm, gv = leaves[:9]
        kbc, kbr, kbcol, kbv = kb[:4]
        padded = (
            pad_to(sw, t_max, nw_max), pad_to(sc, t_max),
            pad_to(fv, t_max, 0.0),
            pad_to(fr, nnzf_max), pad_to(fc, nnzf_max),
            pad_to(fvv, nnzf_max, 0.0),
            cp,  # identity (reorder_cols rejected for sharded); same all shards
            gm, gv,  # already (m_loc_max,) — prepared at the padded shape
            pad_to(kbc, nch_max), pad_to(kbr, nnzkb_max),
            pad_to(kbcol, nnzkb_max), pad_to(kbv, nnzkb_max, 0.0),
            # structured payloads: sharded plans always prepare general
            # format, so these are the uniform (1, 1, 1) dummies
            *leaves[13:],
        )
        for i, arr in enumerate(padded):
            stacked[i].append(arr)
    return tuple(jnp.asarray(np.stack(col)) for col in stacked)


# --- structural-delta sidecar -----------------------------------------------


def _pad_clip(a: np.ndarray, n: int) -> np.ndarray:
    if a.shape[0] >= n:
        return a[:n]
    return np.concatenate(
        [a, np.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)]
    )


@dataclasses.dataclass(frozen=True)
class DeltaFringe:
    """Capacity-padded COO sidecar, shaped for the fringe tier dispatch.

    ``leaves`` are the 8 device arrays the executor pipeline appends to the
    fused program: packed rows / k-block-relative state exactly mirror a
    plan's fringe, and padding entries (row 0, col 0, value 0) are
    accumulate-inert.  ``sig`` keys the cached executor; it changes only
    when ``capacity`` grows (powers of two).
    """

    leaves: Tuple[jax.Array, ...]
    sig: Tuple
    capacity: int
    count: int
    tier: str
    bk: int


@dataclasses.dataclass(frozen=True)
class ShardedDeltaFringe:
    """Per-shard delta sidecars stacked along a leading mesh axis.

    Built by routing every delta row to its owning shard (via a
    rows-sharded plan's ``assemble`` map) and building one
    :class:`DeltaFringe` per shard at the shard-local shape with one
    mesh-uniform capacity — so all shards share a single static signature
    and the per-shard fused body can merge its slice *inside* the
    ``shard_map`` program (one dispatch for sharded dynamic execution).
    """

    leaves: Tuple[jax.Array, ...]   # 8 arrays, each stacked (n_shards, ...)
    sig: Tuple
    capacity: int
    count: int
    tier: str
    bk: int
    n_shards: int


def build_delta_fringe(
    d_rows: np.ndarray,
    d_cols: np.ndarray,
    d_vals: np.ndarray,
    shape: Tuple[int, int],
    config: SpmmConfig,
    capacity: Optional[int] = None,
) -> DeltaFringe:
    """Materialize a delta COO into a capacity-padded sidecar stream."""
    m, k = shape
    d_rows = np.asarray(d_rows, np.int64)
    d_cols = np.asarray(d_cols, np.int64)
    d_vals = np.asarray(d_vals)
    count = int(d_rows.size)
    cap = max(8, ops.pow2_at_least(count), int(capacity or 0))

    if count:
        order = np.argsort(d_rows * np.int64(k) + d_cols, kind="stable")
        sr = d_rows[order]
        first = np.concatenate([[True], sr[1:] != sr[:-1]])
        row_ids = sr[first]
        pr = (np.cumsum(first) - 1).astype(np.int32)
        pc = d_cols[order].astype(np.int32)
        pv = d_vals[order].astype(np.float32)
    else:
        row_ids = np.zeros(0, np.int64)
        pr = np.zeros(0, np.int32)
        pc = np.zeros(0, np.int32)
        pv = np.zeros(0, np.float32)
    pr, pc, pv = _pad_clip(pr, cap), _pad_clip(pc, cap), _pad_clip(pv, cap)
    gsrc = np.full(m, -1, np.int32)
    if row_ids.size:
        gsrc[row_ids] = np.arange(row_ids.size, dtype=np.int32)

    # the sidecar flows through the same VMEM-budget tier selection as a
    # plan fringe; the packed-row bound is the capacity (static per sig)
    k_pad = ((k + config.bk - 1) // config.bk) * config.bk
    tier, dbk = select_fringe_tier(
        k_pad, cap, config.bn, vmem_budget=config.fringe_vmem_budget,
        nnz=cap,
    )
    if tier == "ksharded" and config.impl != "xla":
        step = ops.FRINGE_STEP
        kbc, kbr, kbcol, kbv, _pos = bucket_fringe_kblocks(
            pr, pc, pv, k_pad, dbk
        )
        # deterministic shapes per capacity: each nonempty bucket wastes
        # < step slots and at most min(cap, k-blocks) buckets are nonempty,
        # which bounds the bucketed stream; pad chunks target k-block 0
        # with zero values (accumulate-inert)
        n_kb = -(-k_pad // dbk)
        kb_cap = (-(-cap // step) + min(cap, n_kb)) * step
        kbc = _pad_clip(kbc, kb_cap // step)
        kbr = _pad_clip(kbr, kb_cap)
        kbcol = _pad_clip(kbcol, kb_cap)
        kbv = _pad_clip(kbv, kb_cap)
    else:
        kbc = np.zeros(1, np.int32)
        kbr = np.zeros(1, np.int32)
        kbcol = np.zeros(1, np.int32)
        kbv = np.zeros(1, np.float32)

    leaves = tuple(jnp.asarray(x) for x in (
        pr, pc, pv, gsrc, kbc, kbr, kbcol, kbv
    ))
    sig = ("delta", cap, cap, tier, int(dbk),
           int(kbc.shape[0]), int(kbr.shape[0]))
    return DeltaFringe(leaves=leaves, sig=sig, capacity=cap, count=count,
                       tier=tier, bk=int(dbk))


def build_sharded_delta_fringe(
    d_rows: np.ndarray,
    d_cols: np.ndarray,
    d_vals: np.ndarray,
    splan: ShardedPlan,
    capacity: Optional[int] = None,
) -> ShardedDeltaFringe:
    """Route a delta COO to owning shards and build stacked sidecars.

    Every delta row lands on the shard that owns its output row under the
    plan's row partition (``assemble``), relabeled to shard-local row
    coordinates — so the per-shard fused body merges its own delta slice
    and the existing assemble gather (all-gather unchanged) picks the
    contributions up with zero extra cross-device traffic.
    """
    if splan.shard_axis != "rows":
        raise ValueError(
            "build_sharded_delta_fringe routes by row ownership and needs a "
            f"rows-sharded plan; got shard_axis={splan.shard_axis!r} "
            "(rhs-sharded plans replicate a plain DeltaFringe instead)"
        )
    m_loc = splan.rows_per_shard
    n_shards = splan.n_shards
    k = splan.shape[1]
    d_rows = np.asarray(d_rows, np.int64)
    d_cols = np.asarray(d_cols, np.int64)
    d_vals = np.asarray(d_vals)
    assemble = np.asarray(splan.assemble)
    slot = assemble[d_rows] if d_rows.size else np.zeros(0, np.int64)
    shard_of = slot // max(m_loc, 1)
    local_row = slot % max(m_loc, 1)

    counts = np.bincount(shard_of, minlength=n_shards) if d_rows.size else (
        np.zeros(n_shards, np.int64)
    )
    cap = max(8, ops.pow2_at_least(int(counts.max()) if d_rows.size else 0),
              int(capacity or 0))

    per_shard: List[DeltaFringe] = []
    for s in range(n_shards):
        sel = np.flatnonzero(shard_of == s)
        per_shard.append(build_delta_fringe(
            local_row[sel], d_cols[sel], d_vals[sel], (m_loc, k),
            splan.config, capacity=cap,
        ))
    child_sig = per_shard[0].sig
    assert all(df.sig == child_sig for df in per_shard), (
        "per-shard delta sigs diverged despite the uniform capacity"
    )
    leaves = tuple(
        jnp.stack([df.leaves[i] for df in per_shard])
        for i in range(N_DELTA_LEAVES)
    )
    return ShardedDeltaFringe(
        leaves=leaves, sig=("sharded_delta", n_shards) + child_sig[1:],
        capacity=cap, count=int(d_rows.size),
        tier=per_shard[0].tier, bk=per_shard[0].bk, n_shards=n_shards,
    )


def delta_child_sig(dsig: Tuple) -> Tuple:
    """Per-shard ("delta", ...) signature of any sidecar signature."""
    if dsig[0] == "sharded_delta":
        return ("delta",) + tuple(dsig[2:])
    return dsig
