"""Unified sparse-operator facade over the NeutronSparse plan IR.

One handle, one kwarg set, every operator::

    import repro.sparse as sp

    A = sp.from_coo(rows, cols, vals, shape, impl="pallas")
    C = sp.spmm(A, B)              # (M, N) dense        = A @ B
    C = sp.bspmm(A, Bb)            # (batch, M, N)       = A @ B per batch
    w = sp.sddmm(A, X, Y)          # (nnz,) values of (X @ Y) at A's pattern
    P = sp.spspmm(A, B)            # SparseMatrix        = A @ B, sparse

The surface mirrors ``dgl.mock_sparse`` (``SparseMatrix`` + free-function
operators) but every operator lowers onto the *same* prepared
:class:`~repro.core.plan_ir.NeutronPlan` machinery: window/tile streams on
the matrix engine, COO fringe on the vector engine, cost-model dispatch
tiers, the bounded executor LRU, and health-gated degrade-to-XLA.  A
``SparseMatrix`` wraps one of the three plan flavors —

- :class:`~repro.core.plan_ir.NeutronPlan` (single device),
- :class:`~repro.core.plan_ir.ShardedPlan` (``mesh=`` at construction),
- :class:`~repro.dynamic.DynamicPlan`     (``dynamic=True``; mutable),

and the operators pick the matching executor automatically.  All
operators accept ``deadline=`` (seconds): the dispatch is blocked on and
:class:`~repro.errors.DeadlineExceeded` raised if it finished too late —
the same post-hoc contract the serving layer uses for drains.

``sddmm`` returns a flat value vector in the *original COO input order*
of the pattern, which is exactly the layout ``SparseMatrix.with_values``
/ ``dynamic.update_values`` consume — so GAT-style attention is three
facade calls: ``sddmm`` -> ``with_values`` -> ``spmm``.

This module is the TOP of the layer stack (``tools/check_layers.py``):
it may import everything; nothing below may import it.
"""
from __future__ import annotations

import time
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .core import spmm as core_spmm
from .core.plan_ir import NeutronPlan, ShardedPlan, SpmmConfig
from .dynamic import DynamicPlan
from .dynamic import update_values as _dynamic_update_values
from .errors import DeadlineExceeded, PlanBuildError
from .exec import api as _exec
from .obs import TRACES, span

__all__ = [
    "SparseMatrix", "from_coo", "from_plan",
    "spmm", "bspmm", "sddmm", "spspmm",
]

PlanLike = Union[NeutronPlan, ShardedPlan, DynamicPlan]


def _telemetry_on(plan: PlanLike) -> bool:
    """Whether the plan's config opts into host-side tracing."""
    p = plan.plan if isinstance(plan, DynamicPlan) else plan
    return bool(getattr(p.config, "telemetry", False))


def _traced_call(name: str, plan: PlanLike, attrs, fn):
    """Run ``fn()`` under the ``repro.call`` phase span; when the plan
    opts in, also record an obs trace around it.

    The trace wraps the dispatch *and* the deadline await in a single
    ``dispatch`` span — host-side bookkeeping only.
    """
    with span("call"):
        return _request_traced(name, plan, attrs, fn)


def _request_traced(name: str, plan: PlanLike, attrs, fn):
    if not _telemetry_on(plan):
        return fn()
    tr = TRACES.begin(f"facade:{name}", **attrs)
    t0 = TRACES.now_us()
    try:
        out = fn()
    except BaseException as err:
        TRACES.add_span(tr, "dispatch", t0, TRACES.now_us())
        tr.attrs["outcome"] = type(err).__name__
        TRACES.end(tr)
        raise
    TRACES.add_span(tr, "dispatch", t0, TRACES.now_us())
    tr.attrs["outcome"] = "ok"
    TRACES.end(tr)
    return out


def _await(out: Any, deadline: Optional[float], t0: float, what: str):
    """Post-hoc deadline: block on ``out``, raise if it landed too late."""
    if deadline is None:
        return out
    jax.block_until_ready(out)
    elapsed = time.monotonic() - t0
    if elapsed > deadline:
        raise DeadlineExceeded(
            f"{what} finished {elapsed - deadline:.3f}s past its "
            f"{deadline:.3f}s deadline"
        )
    return out


class SparseMatrix:
    """A prepared sparse matrix: thin, typed handle over one plan flavor.

    Construct via :func:`from_coo` (or :func:`from_plan` to adopt an
    already-prepared plan).  The handle is cheap — all state lives in the
    wrapped plan — and immutable unless the plan is dynamic.
    """

    __slots__ = ("plan",)

    def __init__(self, plan: PlanLike):
        if not isinstance(plan, (NeutronPlan, ShardedPlan, DynamicPlan)):
            raise TypeError(
                "SparseMatrix wraps a NeutronPlan, ShardedPlan or "
                f"DynamicPlan; got {type(plan).__name__}"
            )
        self.plan = plan

    # -- flavor probes ------------------------------------------------------
    @property
    def is_dynamic(self) -> bool:
        return isinstance(self.plan, DynamicPlan)

    @property
    def is_sharded(self) -> bool:
        p = self.plan
        return isinstance(
            p.plan if isinstance(p, DynamicPlan) else p, ShardedPlan
        )

    def _static_plan(self, what: str):
        """The underlying static plan; rejects stale dynamic structure.

        A dynamic plan with pending structural deltas has diverged from
        its prepared pattern, so pattern-addressed operators (sddmm,
        spspmm) must not silently use the base plan.
        """
        p = self.plan
        if isinstance(p, DynamicPlan):
            if p.delta_nnz:
                raise PlanBuildError(
                    f"{what} on a dynamic matrix with {p.delta_nnz} pending "
                    "structural delta(s): call .compact() first so the "
                    "prepared pattern matches the logical matrix"
                )
            p = p.plan
        return p

    # -- introspection ------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        return self.plan.shape

    @property
    def nnz(self) -> int:
        if isinstance(self.plan, DynamicPlan):
            return self.plan.to_coo()[0].shape[0]
        maps = self.plan.update_maps
        if maps is None:
            raise PlanBuildError("plan was built without update maps")
        return maps.nnz

    @property
    def dtype(self):
        return jnp.float32  # kernels accumulate and emit fp32

    @property
    def row(self) -> np.ndarray:
        return self.coo()[0]

    @property
    def col(self) -> np.ndarray:
        return self.coo()[1]

    @property
    def val(self) -> np.ndarray:
        return self.coo()[2]

    def coo(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Host ``(rows, cols, vals)`` triplets of the logical matrix."""
        if isinstance(self.plan, DynamicPlan):
            return self.plan.to_coo()
        maps = self.plan.update_maps
        if maps is None:
            raise PlanBuildError("plan was built without update maps")
        return maps.rows, maps.cols, maps.vals

    def dense(self) -> np.ndarray:
        """Dense fp64 mirror (duplicates accumulate). Debug/test sized."""
        rows, cols, vals = self.coo()
        out = np.zeros(self.shape, np.float64)
        np.add.at(out, (rows, cols), vals.astype(np.float64))
        return out

    # -- value mutation -----------------------------------------------------
    def with_values(self, values) -> "SparseMatrix":
        """Same pattern, new per-nonzero values (original COO order).

        This is the landing pad for :func:`sddmm` output.  Functional:
        returns a new handle, the original is untouched, and the plan
        signature — and therefore the cached executor — is unchanged
        (``dynamic.update_values`` underneath, retrace-free).
        """
        p = self._static_plan("with_values")
        nnz = p.update_maps.nnz
        values = np.asarray(values)
        if values.ndim != 1 or values.shape[0] != nnz:
            raise ValueError(
                f"with_values needs one value per nonzero: got shape "
                f"{values.shape} for nnz={nnz}"
            )
        return SparseMatrix(
            _dynamic_update_values(p, np.arange(nnz), values)
        )

    # -- operator sugar -----------------------------------------------------
    def __matmul__(self, other):
        if isinstance(other, SparseMatrix):
            return spspmm(self, other)
        return spmm(self, other)

    def __repr__(self) -> str:
        kind = type(self.plan).__name__
        try:
            nnz = self.nnz
        except PlanBuildError:
            nnz = "?"
        return f"SparseMatrix(shape={self.shape}, nnz={nnz}, plan={kind})"


def from_coo(
    rows,
    cols,
    vals,
    shape: Tuple[int, int],
    *,
    impl: str = "xla",
    mesh: Any = None,
    dynamic: bool = False,
    config: Optional[SpmmConfig] = None,
    **config_overrides,
) -> SparseMatrix:
    """Prepare a sparse matrix from COO triplets.

    ``impl`` picks the kernel tier (``"xla"`` | ``"pallas"`` |
    ``"pallas_interpret"``), ``mesh`` shards the plan across devices,
    ``dynamic=True`` wraps the plan for in-place mutation.  Pass a full
    :class:`SpmmConfig` via ``config`` for exact control, or individual
    config fields as keyword overrides (``bn=...``, ``alpha=...``, ...);
    mixing ``config`` with overrides or with ``impl`` is rejected so one
    call site never says the same thing twice.
    """
    if config is not None and config_overrides:
        raise ValueError(
            "pass either config= or individual config overrides, not both"
        )
    if config is None:
        config = SpmmConfig(impl=impl, **config_overrides)
    elif impl != "xla":
        raise ValueError("impl= is part of config= when one is passed")
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    if mesh is not None:
        plan: PlanLike = core_spmm.prepare_sharded(
            rows, cols, vals, shape, mesh, config=config
        )
    else:
        plan = core_spmm.prepare(rows, cols, vals, shape, config=config)
    if dynamic:
        plan = DynamicPlan(plan)
    return SparseMatrix(plan)


def from_plan(plan: PlanLike) -> SparseMatrix:
    """Adopt an already-prepared plan (any flavor) into the facade."""
    return SparseMatrix(plan)


def _as_matrix(a, what: str) -> SparseMatrix:
    if isinstance(a, SparseMatrix):
        return a
    if isinstance(a, (NeutronPlan, ShardedPlan, DynamicPlan)):
        return SparseMatrix(a)
    raise TypeError(f"{what} wants a SparseMatrix, got {type(a).__name__}")


def spmm(a, b, *, deadline: Optional[float] = None) -> jax.Array:
    """Dense ``C = A @ B``; single fused jitted dispatch, fp32.

    ``b`` is ``(K, N)``.  Batched operands go through :func:`bspmm`.
    """
    a = _as_matrix(a, "spmm")
    b = jnp.asarray(b)
    p = a.plan

    def run():
        t0 = time.monotonic()
        if isinstance(p, DynamicPlan):
            out = p.execute(b)
        elif isinstance(p, ShardedPlan):
            out = _exec.execute_sharded(p, b)
        else:
            out = _exec.execute(p, b)
        return _await(out, deadline, t0, "spmm")

    return _traced_call(
        "bspmm" if b.ndim == 3 else "spmm", p,
        {"shape": a.shape, "n": int(b.shape[-1])}, run,
    )


def bspmm(a, b, *, deadline: Optional[float] = None) -> jax.Array:
    """Batched SpMM: ``b`` is ``(batch, K, N)`` -> ``(batch, M, N)``.

    One vmapped dispatch compiled once per ``(signature, batch)``; the
    sparse operand is shared across the batch.
    """
    b = jnp.asarray(b)
    if b.ndim != 3:
        raise ValueError(
            f"bspmm wants a (batch, K, N) operand, got ndim={b.ndim} "
            "(use spmm for a single right-hand side)"
        )
    return spmm(a, b, deadline=deadline)


def sddmm(a, x, y, *, deadline: Optional[float] = None) -> jax.Array:
    """Sampled dense-dense matmul: values of ``X @ Y`` at A's pattern.

    ``x`` is ``(M, D)``, ``y`` is ``(D, K)`` (or both with a leading
    batch axis).  Returns ``(nnz,)`` fp32 values (``(batch, nnz)`` when
    batched) in the *original COO input order* of ``a`` — feed them
    straight to ``a.with_values`` (GAT-style attention) or
    ``dynamic.update_values``.
    """
    a = _as_matrix(a, "sddmm")
    plan = a._static_plan("sddmm")

    def run():
        t0 = time.monotonic()
        out = _exec.execute_sddmm(plan, jnp.asarray(x), jnp.asarray(y))
        return _await(out, deadline, t0, "sddmm")

    return _traced_call("sddmm", plan, {"shape": a.shape}, run)


def spspmm(a, b, *, deadline: Optional[float] = None) -> SparseMatrix:
    """Sparse x sparse: ``C = A @ B`` as a new prepared SparseMatrix.

    The symbolic phase intersects the two plans' row-window/tile metadata
    on the host; numeric accumulation is one jitted dispatch.  The result
    is prepared with A's config (single-device), so it immediately
    supports the whole operator family.
    """
    a = _as_matrix(a, "spspmm")
    b = _as_matrix(b, "spspmm")
    a_plan = a._static_plan("spspmm")
    b_plan = b._static_plan("spspmm")

    def run():
        t0 = time.monotonic()
        out = _exec.execute_spspmm(a_plan, b_plan)
        _await(out[2], deadline, t0, "spspmm")
        return out

    cr, cc, cv, cshape = _traced_call(
        "spspmm", a_plan, {"shape": a.shape}, run
    )
    cfg = a_plan.config
    if isinstance(a_plan, ShardedPlan) or isinstance(b_plan, ShardedPlan):
        # the product pattern has no window assignment yet — prepare it
        # single-device; the caller can re-shard via from_coo(mesh=...)
        cfg = b_plan.config if isinstance(a_plan, ShardedPlan) else cfg
    return from_coo(cr, cc, np.asarray(cv), cshape, config=cfg)
