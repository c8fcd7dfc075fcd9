"""Execution entry points over the unified pipeline.

``execute`` / ``execute_sharded`` / ``execute_with_delta`` all resolve to
one :func:`repro.exec.pipeline.build_executor` call — a single jitted
dispatch whatever the flavor.  ``core.spmm`` re-exports everything here
(lazily, so the core layer's import graph stays downward), which keeps
every historical call site — ``repro.core.spmm.execute`` and friends —
working unchanged.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
import zlib
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import plan_ir, tuner
from ..core.plan_ir import (
    NeutronPlan, ShardedPlan, SpmmConfig, build_sddmm_maps, gather_rows,
    permute_pad_b, plan_leaves, sddmm_body_leaves, validate_rhs,
)
from ..errors import DispatchError, KernelLoweringError, PlanBuildError
from ..kernels import ops
from ..obs import span
from . import cache as _cache
from .cache import (  # noqa: F401  (re-exported test hooks)
    dispatch_count, fused_trace_count, sharded_trace_count,
    set_executor_cache_capacity,
)
from .health import HEALTH
from .pipeline import build_delta_only_executor, build_executor

def _apply_cache_capacity(config: SpmmConfig) -> None:
    if config.executor_cache_capacity is not None:
        _cache.EXECUTOR_CACHE.set_capacity(config.executor_cache_capacity)


def _plan_nnz(plan) -> int:
    stats = plan.stats_dict
    if "nnz" in stats:
        return int(stats["nnz"])
    if "shard_nnz" in stats:
        return int(sum(stats["shard_nnz"]))
    um = getattr(plan, "update_maps", None)
    return int(um.nnz) if um is not None else 0


def _tuned_densify(plan) -> float | None:
    """Measured densify-occupancy crossover for this plan, or None.

    Resolved through ``core.tuner`` (a no-op unless ``config.autotune``).
    The value rides the executor cache key rather than the plan signature:
    tuned and analytic processes share plan layouts (and registry entries
    keyed by signature) but never alias one lowered program.
    """
    config = plan.config
    if not getattr(config, "autotune", False):
        return None
    cm = tuner.resolve_cost_model(
        "spmm", int(plan.shape[0]), int(plan.shape[1]), _plan_nnz(plan),
        config,
    )
    return cm.densify_occupancy()


def _sig_key(sig) -> str:
    """Short deterministic key for a plan signature (warning text)."""
    return f"{zlib.crc32(repr(sig).encode()):08x}"


def _launch(fn, args, lookup: span):
    """End the caller's ``repro.lookup`` span and dispatch the executor
    under ``repro.launch``: the one ``fn(*args)``, never synchronized."""
    lookup.close()
    with span("launch"):
        return fn(*args)


def _guarded_call(sig, config: SpmmConfig, make_fn, args, kind: str, key_of,
                  lookup: span):
    """Build + dispatch with health gating and degrade-to-XLA fallback.

    ``make_fn(sig) -> fn`` builds (or fetches) the executor for a
    signature; ``key_of(sig)`` is the dispatch-counter key.  XLA-impl
    signatures take the pre-existing fast path untouched.  For pallas
    signatures the health table decides whether to attempt the accelerated
    tier; a build/lower/first-execute failure is recorded (bounded
    call-count backoff, then sticky demotion — see ``exec.health``) and
    the dispatch is retried on :func:`plan_ir.xla_fallback_sig`, which
    reuses the same plan leaves so results stay bit-identical to the
    reference.  ``SpmmConfig.degrade_to_xla=False`` turns the fallback
    into a raised :class:`KernelLoweringError`.  Failures *after* a
    successful synchronous dispatch (async device-side errors surfacing at
    a later block) are out of scope here.

    ``lookup`` is the entry point's open ``repro.lookup`` span: it holds
    the entry's validation and signature work and the executor lookup
    here, and ends where the executor launches.  After a failed
    accelerated launch the fallback's lookup opens a second one.
    """
    impl = plan_ir.sig_impl(sig)
    if impl is None or impl == "xla":
        fn = make_fn(sig)
        _cache.record_dispatch(kind, key_of(sig))
        return _launch(fn, args, lookup)
    if HEALTH.should_try_accel(sig):
        try:
            fn = make_fn(sig)
            _cache.record_dispatch(kind, key_of(sig))
            out = _launch(fn, args, lookup)
            HEALTH.record_success(sig)
            return out
        except Exception as err:  # noqa: BLE001 — any accel failure degrades
            first = HEALTH.record_failure(sig, err)
            if not config.degrade_to_xla:
                raise KernelLoweringError(
                    f"accelerated executor failed for impl={impl!r} and "
                    f"degrade_to_xla is disabled: {err}"
                ) from err
            if first:
                warnings.warn(
                    f"{kind} executor for impl={impl!r} failed and is served "
                    f"by the XLA tier (signature {_sig_key(sig)}): "
                    f"{type(err).__name__}: {err}",
                    RuntimeWarning, stacklevel=3,
                )
    fsig = plan_ir.xla_fallback_sig(sig)
    HEALTH.record_fallback(sig)
    if lookup.closed:  # the accelerated launch failed after its lookup
        lookup = span("lookup").__enter__()
    try:
        fn = make_fn(fsig)
        _cache.record_dispatch(kind + ":degraded", key_of(fsig))
        return _launch(fn, args, lookup)
    except Exception as err:
        raise DispatchError(
            f"dispatch failed on every tier (accel impl={impl!r} degraded, "
            f"then XLA fallback raised: {err})"
        ) from err
    finally:
        lookup.close()


def execute(plan: NeutronPlan, b: jax.Array) -> jax.Array:
    """Full coordinated SpMM: C = A @ B, original row order, fp32.

    ``b`` may be a single ``(K, N)`` operand or a batched ``(batch, K, N)``
    stack of right-hand sides; the batched form returns ``(batch, M, N)``
    from one vmapped dispatch compiled once per ``(signature, batch)``.
    Single end-to-end jitted dispatch either way: both engine paths plus
    the scatter-free gather merge compile into one program (empty paths
    are dropped at trace time).  Pallas-tier plans dispatch through the
    health gate: a kernel failure degrades to the XLA tier (bit-identical)
    instead of raising — see :mod:`repro.exec.health`.
    """
    with span("lookup") as lookup:
        validate_rhs(b, plan.shape)
        _apply_cache_capacity(plan.config)
        batch = int(b.shape[0]) if b.ndim == 3 else None
        docc = _tuned_densify(plan)
        return _guarded_call(
            plan.signature(), plan.config,
            lambda s: build_executor(s, batch=batch, densify_occupancy=docc),
            (*plan_leaves(plan), b), "fused", lambda s: (s, batch),
            lookup,
        )


def execute_with_delta(plan: NeutronPlan, delta, b: jax.Array) -> jax.Array:
    """C = (A_base + A_delta) @ B in one fused dispatch.

    ``delta`` is a ``plan_ir.DeltaFringe`` (duck-typed here: anything with
    ``.leaves`` — the 8 capacity-padded sidecar arrays — and ``.sig``).
    The sidecar joins the gather merge additively inside the same jitted
    program as the base plan's two engine paths.
    """
    with span("lookup") as lookup:
        validate_rhs(b, plan.shape)
        _apply_cache_capacity(plan.config)
        batch = int(b.shape[0]) if b.ndim == 3 else None
        docc = _tuned_densify(plan)
        # dynamic dispatch rides the general payload: the structured fast lane
        # serves static plans, and value churn (the reason a delta exists)
        # would stale a packed payload — same demotion update_values applies
        sig = plan_ir.general_format_sig(plan.signature())
        return _guarded_call(
            sig, plan.config,
            lambda s: build_executor(s, batch=batch, delta_sig=delta.sig,
                                     densify_occupancy=docc),
            (*plan_leaves(plan), *delta.leaves, b),
            "fused+delta", lambda s: (s, batch),
            lookup,
        )


def execute_sharded(
    splan: ShardedPlan, b: jax.Array, delta=None
) -> jax.Array:
    """Multi-device coordinated SpMM: C = A @ B across ``splan.mesh``.

    Accepts ``(K, N)`` or batched ``(batch, K, N)`` right-hand sides, like
    :func:`execute`.  Bit-identical row ownership to the single-device
    executor: every output row is computed by exactly one shard.

    ``delta`` extends the program with a structural sidecar *inside* the
    ``shard_map`` body — a ``plan_ir.ShardedDeltaFringe`` (rows axis:
    stacked per-shard sidecars in local row coordinates, merged by each
    owning shard before the all-gather) or a plain ``DeltaFringe`` (rhs
    axis: replicated sidecar over the column-sharded operand).  Either way
    sharded dynamic execution is one dispatch, not a post-pass.
    """
    with span("lookup") as lookup:
        validate_rhs(b, splan.shape)
        _apply_cache_capacity(splan.config)
        batch = int(b.shape[0]) if b.ndim == 3 else None
        if splan.shard_axis == "rhs" and b.shape[-1] % splan.n_shards:
            raise DispatchError(
                f"rhs-sharded plan needs N divisible by n_shards="
                f"{splan.n_shards}; got N={b.shape[-1]} (re-prepare with "
                f"shard_axis='rows' or pad B)"
            )
        if delta is not None:
            routed = isinstance(delta, plan_ir.ShardedDeltaFringe)
            if splan.shard_axis == "rows" and not routed:
                raise DispatchError(
                    "a rows-sharded plan needs its delta routed to owning "
                    "shards (plan_ir.build_sharded_delta_fringe), got a plain "
                    "DeltaFringe"
                )
            if splan.shard_axis == "rhs" and routed:
                raise DispatchError(
                    "an rhs-sharded plan replicates its delta; pass the plain "
                    "DeltaFringe, not a ShardedDeltaFringe"
                )
        dleaves = () if delta is None else tuple(delta.leaves)
        if splan.shard_axis == "rows":
            args = (*splan.leaves, *dleaves, splan.assemble, b)
        else:
            args = (*splan.leaves, *dleaves, b)
        docc = _tuned_densify(splan)
        return _guarded_call(
            splan.sig, splan.config,
            lambda s: build_executor(
                s, batch=batch,
                delta_sig=None if delta is None else delta.sig,
                mesh=splan.mesh, axis_name=splan.axis_name,
                shard_axis=splan.shard_axis, densify_occupancy=docc,
            ),
            args,
            "sharded" if delta is None else "sharded+delta",
            lambda s: (s, splan.shard_axis, batch),
            lookup,
        )


def validate_sddmm_operands(
    x: jax.Array, y: jax.Array, shape: Tuple[int, int]
):
    """Validate SDDMM operands against the pattern's shape; returns batch.

    ``x`` is ``(M, D)`` or ``(batch, M, D)``; ``y`` is ``(D, K)`` or
    ``(batch, D, K)``.  Mixed batching is rejected — broadcasting one
    operand silently would make the batched result's provenance ambiguous.
    """
    m, k = shape
    if x.ndim not in (2, 3) or y.ndim not in (2, 3):
        raise ValueError(
            f"sddmm operands must be (M, D)/(D, K) or batched with one "
            f"leading axis each; got x {tuple(x.shape)}, y {tuple(y.shape)}"
        )
    if x.ndim != y.ndim:
        raise ValueError(
            f"sddmm operands must be batched together; got x "
            f"{tuple(x.shape)} and y {tuple(y.shape)}"
        )
    if x.ndim == 3 and int(x.shape[0]) != int(y.shape[0]):
        raise ValueError(
            f"sddmm batch sizes disagree: x {tuple(x.shape)} vs y "
            f"{tuple(y.shape)}"
        )
    if int(x.shape[-2]) != m:
        raise ValueError(
            f"sddmm operand M={int(x.shape[-2])} does not match the "
            f"pattern's M={m} (pattern shape {shape})"
        )
    if int(y.shape[-1]) != k:
        raise ValueError(
            f"sddmm operand K={int(y.shape[-1])} does not match the "
            f"pattern's K={k} (pattern shape {shape})"
        )
    if int(x.shape[-1]) != int(y.shape[-2]):
        raise ValueError(
            f"sddmm operands disagree on D: x {tuple(x.shape)} vs y "
            f"{tuple(y.shape)}"
        )
    return int(x.shape[0]) if x.ndim == 3 else None


def execute_sddmm(plan, x: jax.Array, y: jax.Array) -> jax.Array:
    """Sampled dense-dense matmul over a plan's sparsity pattern.

    Computes ``(X @ Y)[i, j]`` for exactly the pattern's nonzero positions
    and returns them as an fp32 value vector ``(nnz,)`` (batched operands
    return ``(batch, nnz)``) in the plan's original COO input order —
    layout-compatible with ``dynamic.update_values(plan, arange(nnz), out)``
    so attention scores flow straight back into a dynamic plan.

    One fused jitted dispatch per call: the matrix engine computes dense
    products for the plan's active tiles (values extracted at the
    ``core_lin`` slots), the vector engine gathers per-nonzero dots for the
    fringe, and pallas-tier plans ride the same health gate / degrade-to-
    XLA machinery as SpMM.  ``ShardedPlan`` patterns dispatch through the
    flat global gather form (output is (nnz,) — tiny next to the operands).
    """
    if isinstance(plan, ShardedPlan):
        return _execute_sddmm_sharded(plan, x, y)
    with span("lookup") as lookup:
        smaps = build_sddmm_maps(plan)
        batch = validate_sddmm_operands(x, y, plan.shape)
        _apply_cache_capacity(plan.config)
        if smaps.nnz == 0:
            shape = (0,) if batch is None else (batch, 0)
            return jnp.zeros(shape, jnp.float32)
        vmem_budget = plan.config.fringe_vmem_budget
        cfg = plan.config
        if getattr(cfg, "autotune", False) and cfg.impl != "xla":
            cm = tuner.resolve_cost_model(
                "sddmm", int(plan.shape[0]), int(plan.shape[1]), smaps.nnz,
                plan.config,
            )
            tier = cm.select_sddmm_tier(
                int(x.shape[-1]), int(plan.shape[0]), int(plan.shape[1]),
                vmem_budget=vmem_budget,
            )
            if tier == "xla":
                # measured demotion, encoded as a zero budget in the op tag so
                # the fused body's tier="auto" resolves to the XLA gather; the
                # table can demote past the analytic budget but never promote
                vmem_budget = 0
        sig = plan_ir.tag_op(
            plan.signature(), "sddmm", smaps.nnz, smaps.nnz_f, vmem_budget,
        )
        return _guarded_call(
            sig, plan.config,
            lambda s: build_executor(s, batch=batch),
            (*sddmm_body_leaves(plan, smaps), x, y),
            "sddmm", lambda s: (s, batch),
            lookup,
        )


def _execute_sddmm_sharded(
    splan: ShardedPlan, x: jax.Array, y: jax.Array
) -> jax.Array:
    with span("lookup") as lookup:
        maps = splan.update_maps
        if maps is None:
            raise PlanBuildError(
                "sddmm on a sharded plan needs its global COO mirror "
                "(ShardedUpdateMaps); this plan lost it — re-prepare from "
                "COO"
            )
        batch = validate_sddmm_operands(x, y, splan.shape)
        _apply_cache_capacity(splan.config)
        if maps.nnz == 0:
            shape = (0,) if batch is None else (batch, 0)
            return jnp.zeros(shape, jnp.float32)
        flat = getattr(maps, "_sddmm_flat", None)
        if flat is None:  # structure-only device mirror, cached on the maps
            flat = (jnp.asarray(maps.rows, jnp.int32),
                    jnp.asarray(maps.cols, jnp.int32))
            maps._sddmm_flat = flat
        cfg = splan.config
        sig = ("sddmm_flat", cfg.impl, maps.nnz, cfg.fringe_chunk)
        return _guarded_call(
            sig, cfg,
            lambda s: build_executor(s, batch=batch),
            (*flat, x, y), "sddmm", lambda s: (s, batch),
            lookup,
        )


def execute_spspmm(a_plan, b_plan) -> Tuple:
    """Sparse x sparse matmul: ``C = A @ B`` from two prepared patterns.

    Two phases.  The *symbolic* phase runs host-side on the plans' COO
    mirrors: B's row-window occupancy (the plan IR's window metadata) is
    intersected against A's column set to discard A nonzeros that cannot
    meet any B row, survivors expand to per-term (A-nonzero, B-nonzero)
    index pairs by binary search over B's row-sorted order, and the terms
    are sorted/uniqued into C's output pattern.  The *numeric* phase is ONE
    jitted dispatch — a sorted segment sum over the expansion products —
    through the same executor cache and dispatch counters as every other
    op.  Duplicate COO triplets in either input accumulate exactly like
    the dense oracle (each triplet expands independently and the segment
    sum adds them).

    Accepts single-device or sharded plans (both keep global COO mirrors).
    Returns ``(rows, cols, vals, shape)`` — a COO triple in row-major
    order, ready for ``prepare()``/``repro.sparse.from_coo``.
    """
    ma, mb = a_plan.update_maps, b_plan.update_maps
    if ma is None or mb is None:
        raise PlanBuildError(
            "spspmm needs both plans' COO mirrors (update_maps); a plan "
            "round-tripped through jax tree ops lost them — re-prepare"
        )
    m, ka = a_plan.shape
    kb, n = b_plan.shape
    if ka != kb:
        raise ValueError(
            f"spspmm inner dimensions disagree: A is {a_plan.shape}, "
            f"B is {b_plan.shape}"
        )
    _apply_cache_capacity(a_plan.config)

    ar, ac = ma.rows, ma.cols
    br, bc = mb.rows, mb.cols
    empty = (np.zeros(0, np.int64), np.zeros(0, np.int64),
             jnp.zeros(0, jnp.float32), (m, n))
    if ar.size == 0 or br.size == 0:
        return empty

    # --- symbolic phase (host) --------------------------------------------
    # coarse row-window intersection: a B row-window with no nonzeros can
    # satisfy no A column that lands in it, so those A entries drop before
    # the exact per-row search
    bm_b = b_plan.config.bm
    n_win = (kb + bm_b - 1) // bm_b
    active_win = np.zeros(n_win, bool)
    active_win[np.unique(br // bm_b)] = True
    keep = np.flatnonzero(active_win[ac // bm_b])
    if keep.size == 0:
        return empty

    ob = np.argsort(br, kind="stable")
    brs = br[ob]
    starts = np.searchsorted(brs, ac[keep])
    deg = np.searchsorted(brs, ac[keep], side="right") - starts
    n_exp = int(deg.sum())
    if n_exp == 0:
        return empty
    ae = np.repeat(keep, deg)
    cum = np.cumsum(deg) - deg
    be = ob[np.arange(n_exp) - np.repeat(cum, deg) + np.repeat(starts, deg)]

    key = ar[ae] * np.int64(n) + bc[be]
    order = np.argsort(key, kind="stable")
    ae, be, key = ae[order], be[order], key[order]
    first = np.concatenate([[True], key[1:] != key[:-1]])
    ce = np.cumsum(first) - 1
    c_keys = key[first]
    nnz_c = int(c_keys.size)

    # --- numeric phase (one jitted dispatch) ------------------------------
    sig = ("spspmm", n_exp, nnz_c)
    with span("lookup") as lookup:
        vals = _guarded_call(
            sig, a_plan.config,
            lambda s: build_executor(s),
            (jnp.asarray(ae, jnp.int32), jnp.asarray(be, jnp.int32),
             jnp.asarray(ce, jnp.int32), jnp.asarray(ma.vals),
             jnp.asarray(mb.vals)),
            "spspmm", lambda s: s,
            lookup,
        )
    return c_keys // n, c_keys % n, vals, (m, n)


def _pad_b(plan: NeutronPlan, b: jax.Array) -> jax.Array:
    cfg = plan.config
    return permute_pad_b(b, plan.col_perm, cfg.reorder_cols, cfg.bk, cfg.bn)


def execute_matrix_path(plan: NeutronPlan, b: jax.Array) -> jax.Array:
    """Dense-core path only; returns (M, N) contribution."""
    cfg = plan.config
    m, _ = plan.shape
    n = b.shape[1]
    if not plan.has_core:  # skip the dummy zero-tile dispatch entirely
        return jnp.zeros((m, n), jnp.float32)
    bp = _pad_b(plan, b)
    packed = ops.block_stream_spmm(
        plan.step_window, plan.step_col, plan.flat_values, bp,
        num_windows=plan.num_windows, bm=cfg.bm, bk=cfg.bk, bn=cfg.bn,
        impl=cfg.impl, assume_unique=True,  # prepare() emits unique pairs
    )[:, :n]
    return gather_rows(packed, plan.gather_src_matrix)


def execute_vector_path(plan: NeutronPlan, b: jax.Array) -> jax.Array:
    """Fringe path only; returns (M, N) contribution."""
    cfg = plan.config
    m, _ = plan.shape
    n = b.shape[1]
    if not plan.has_fringe:  # skip the 1-element dummy kernel entirely
        return jnp.zeros((m, n), jnp.float32)
    bp = _pad_b(plan, b)
    packed = ops.fringe_spmm(
        plan.fringe_rows, plan.fringe_cols, plan.fringe_vals, bp,
        num_rows=int(plan.fringe_row_ids.shape[0]), bn=cfg.bn, impl=cfg.impl,
        chunk=cfg.fringe_chunk,
        tier=plan.fringe_tier, bk=plan.fringe_bk,
        kb_chunk=plan.fringe_kb_chunk, kb_rows=plan.fringe_kb_rows,
        kb_cols=plan.fringe_kb_cols, kb_vals=plan.fringe_kb_vals,
    )[:, :n]
    return gather_rows(packed, plan.gather_src_vector)


def execute_delta_contribution(
    shape: Tuple[int, int], config: SpmmConfig, delta, b: jax.Array
) -> jax.Array:
    """The delta sidecar's own (M, N) [or (batch, M, N)] contribution.

    Kept as the differential baseline for the single-dispatch sharded
    merge (and for callers that want the sidecar term alone); the serving
    path no longer uses it as a post-pass.
    """
    batch = int(b.shape[0]) if b.ndim == 3 else None
    fn = build_delta_only_executor(
        shape[0], config.bk, config.bn, config.impl, config.fringe_chunk,
        delta.sig, batch,
    )
    _cache.record_dispatch("delta_only", (shape, delta.sig, batch))
    col_perm = jax.numpy.arange(shape[1], dtype=jax.numpy.int32)
    return fn(*delta.leaves, col_perm, b)


def neutron_spmm(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    shape: Tuple[int, int],
    b: jax.Array,
    config: SpmmConfig = SpmmConfig(),
) -> jax.Array:
    """One-shot convenience: prepare + execute."""
    from ..core import spmm  # lazy: core's facade may be mid-import

    plan = spmm.prepare(rows, cols, vals, shape, config)
    return execute(plan, b)


class SpMMOperator:
    """Differentiable fixed-structure SpMM: C = A @ B with dC/dB = A^T @ g.

    Both directions run the coordinated dual-path executor (the transpose
    gets its own plan — partition/reorder of A^T).  Used by GNN training
    (examples/gcn_training.py) where A is the normalized adjacency.
    """

    def __init__(
        self,
        rows: np.ndarray,
        cols: np.ndarray,
        vals: np.ndarray,
        shape: Tuple[int, int],
        config: SpmmConfig = SpmmConfig(),
    ):
        from ..core import spmm  # lazy: core's facade may be mid-import

        self.plan = spmm.prepare(rows, cols, vals, shape, config)
        self.plan_t = spmm.prepare(
            np.asarray(cols), np.asarray(rows), np.asarray(vals),
            (shape[1], shape[0]), config,
        )

        @jax.custom_vjp
        def _f(b):
            return execute(self.plan, b)

        def _fwd(b):
            return _f(b), None

        def _bwd(_, g):
            return (execute(self.plan_t, g),)

        _f.defvjp(_fwd, _bwd)
        self._f = _f

    def __call__(self, b: jax.Array) -> jax.Array:
        return self._f(b)


class NeutronSpMM:
    """Epoch-loop operator with adaptive AIV-AIC coordination (§5.3).

    Re-prepares the plan when the coordinator migrates windows; per-epoch
    path timings come from host wall-clock around the jitted paths (the
    Ascend on-device timers' analogue).
    """

    def __init__(
        self,
        rows: np.ndarray,
        cols: np.ndarray,
        vals: np.ndarray,
        shape: Tuple[int, int],
        config: SpmmConfig = SpmmConfig(),
        cost_model=None,
        epsilon: float = 0.05,
    ):
        from ..core import spmm  # lazy: core's facade may be mid-import
        from ..core.cost_model import default_cost_model

        self.rows, self.cols, self.vals = (
            np.asarray(rows), np.asarray(cols), np.asarray(vals)
        )
        self.shape = tuple(shape)
        self.config = config
        self.cost_model = cost_model or default_cost_model(n_cols=config.bn)
        self.plan = spmm.prepare(rows, cols, vals, shape, config,
                                 self.cost_model)
        self.epsilon = epsilon
        self._alpha = self.plan.stats_dict["alpha"]
        self._needs_warmup = True
        self.epoch_log: list = []

    def run_epoch(self, b: jax.Array) -> jax.Array:
        if self._needs_warmup:  # exclude (re)compile from epoch timings
            execute_matrix_path(self.plan, b).block_until_ready()
            execute_vector_path(self.plan, b).block_until_ready()
            self._needs_warmup = False
        t0 = time.perf_counter()
        cm = execute_matrix_path(self.plan, b)
        cm.block_until_ready()
        t_matrix = time.perf_counter() - t0
        t0 = time.perf_counter()
        cv = execute_vector_path(self.plan, b)
        cv.block_until_ready()
        t_vector = time.perf_counter() - t0

        from ..core.coordinator import AdaptiveCoordinator

        skew = AdaptiveCoordinator.skew(t_matrix, t_vector)
        self.epoch_log.append(
            {"t_matrix": t_matrix, "t_vector": t_vector, "skew": skew,
             "alpha": self._alpha}
        )
        if skew > 1.0 + self.epsilon and len(self.epoch_log) >= 2:
            self._rebalance(t_matrix, t_vector)
        return cm + cv

    def _rebalance(self, t_matrix: float, t_vector: float) -> None:
        """Nudge alpha toward balanced finish time and re-prepare (Eq. 7)."""
        from ..core import spmm

        ratio = t_matrix / max(t_vector, 1e-12)
        # matrix slower -> raise alpha (send more to vector path); bisection
        new_alpha = float(np.clip(self._alpha * ratio ** 0.5, 1e-6, 1.0))
        if abs(new_alpha - self._alpha) / max(self._alpha, 1e-12) < 1e-3:
            return
        self._alpha = new_alpha
        cfg = dataclasses.replace(self.config, alpha=new_alpha)
        self.plan = spmm.prepare(
            self.rows, self.cols, self.vals, self.shape, cfg, self.cost_model
        )
        self._needs_warmup = True
