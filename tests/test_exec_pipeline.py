"""Differential parity + invariants for the unified executor pipeline.

The refactor contract: every executor flavor the old five hand-rolled
factories produced — fused, batched, all three fringe dispatch tiers,
delta-extended, sharded rows/rhs, sharded+delta — now comes out of one
``exec.pipeline.build_executor`` and must (a) match the fp64 dense oracle,
(b) introduce zero extra retraces over the pre-refactor cache behavior,
and (c) execute sharded dynamic plans as a single dispatch with bit-parity
to the legacy two-dispatch post-pass.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_ir, spmm
from repro.core.cost_model import fringe_resident_bytes
from repro.dynamic import DynamicPlan, GraphDelta, build_delta_fringe
from repro.exec import (
    EXECUTOR_CACHE, build_executor, dispatch_count, fused_trace_count,
    sharded_trace_count,
)
from repro.distributed.sharding import make_spmm_mesh
from conftest import make_sparse

BN = 128  # narrow n-blocks keep interpret-mode grids small


def _force_tier_budget(tier, k_pad, num_rows):
    if tier == "resident":
        return None
    if tier == "ksharded":
        return fringe_resident_bytes(k_pad, num_rows, BN) - 1
    return 16  # xla: nothing fits


def _dense(rows, cols, vals, shape):
    a = np.zeros(shape, np.float64)
    if len(rows):
        np.add.at(a, (rows, cols), np.asarray(vals, np.float64))
    return a


def _check(out, expect):
    scale = np.abs(expect).max() + 1e-9
    assert np.abs(np.asarray(out) - expect).max() / scale < 1e-4


# ---------------------------------------------------------------------------
# differential parity: every flavor against the dense oracle
# ---------------------------------------------------------------------------
def test_fused_and_batched_flavors_match_oracle(rng):
    a, rows, cols, vals = make_sparse(rng, 96, 80, 0.07, n_dense_rows=4)
    plan = spmm.prepare(rows, cols, vals, a.shape, spmm.SpmmConfig(impl="xla"))
    dense = _dense(rows, cols, vals, a.shape)
    b = rng.randn(80, 16).astype(np.float32)
    _check(spmm.execute(plan, jnp.asarray(b)), dense @ b)
    b3 = rng.randn(3, 80, 16).astype(np.float32)
    out = np.asarray(spmm.execute(plan, jnp.asarray(b3)))
    for i in range(3):
        _check(out[i], dense @ b3[i])


@pytest.mark.parametrize("tier", ["resident", "ksharded", "xla"])
def test_fringe_tiers_match_oracle(rng, tier):
    """All three vector-path dispatch tiers through the unified builder,
    forced by derived VMEM budgets, in interpret mode."""
    m, k = 72, 128
    nnz = 500
    rows = rng.randint(0, m, nnz).astype(np.int64)
    cols = rng.randint(0, k, nnz).astype(np.int64)
    vals = rng.randn(nnz)
    cfg = spmm.SpmmConfig(
        impl="pallas_interpret", bn=BN, alpha=1.0,
        fringe_vmem_budget=_force_tier_budget(tier, k, m),
    )
    plan = spmm.prepare(rows, cols, vals, (m, k), cfg)
    if rows.size:
        assert plan.fringe_tier == tier
    b = rng.randn(k, 32).astype(np.float32)
    _check(spmm.execute(plan, jnp.asarray(b)),
           _dense(rows, cols, vals, (m, k)) @ b)


def test_delta_flavors_match_oracle(rng):
    a, rows, cols, vals = make_sparse(rng, 80, 64, 0.06, n_dense_rows=3)
    plan = spmm.prepare(rows, cols, vals, a.shape, spmm.SpmmConfig(impl="xla"))
    dense = _dense(rows, cols, vals, a.shape)
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 12, replace=False)
    dv = rng.randn(12)
    delta = build_delta_fringe(zr[pick], zc[pick], dv, a.shape, plan.config)
    dense[zr[pick], zc[pick]] += dv
    b = rng.randn(64, 8).astype(np.float32)
    _check(spmm.execute_with_delta(plan, delta, jnp.asarray(b)), dense @ b)
    b3 = rng.randn(2, 64, 8).astype(np.float32)
    out = np.asarray(spmm.execute_with_delta(plan, delta, jnp.asarray(b3)))
    for i in range(2):
        _check(out[i], dense @ b3[i])
    # the standalone contribution (legacy post-pass term) is the difference
    contrib = spmm.execute_delta_contribution(
        a.shape, plan.config, delta, jnp.asarray(b)
    )
    _check(np.asarray(spmm.execute(plan, jnp.asarray(b))) + contrib,
           dense @ b)


@pytest.mark.parametrize("shard_axis", ["rows", "rhs"])
def test_sharded_flavors_match_oracle(rng, shard_axis):
    a, rows, cols, vals = make_sparse(rng, 96, 64, 0.07, n_dense_rows=4)
    mesh = make_spmm_mesh(1)
    splan = spmm.prepare_sharded(rows, cols, vals, a.shape, mesh,
                                 spmm.SpmmConfig(impl="xla"),
                                 shard_axis=shard_axis)
    dense = _dense(rows, cols, vals, a.shape)
    b = rng.randn(64, 16).astype(np.float32)
    _check(spmm.execute_sharded(splan, jnp.asarray(b)), dense @ b)
    b3 = rng.randn(2, 64, 16).astype(np.float32)
    out = np.asarray(spmm.execute_sharded(splan, jnp.asarray(b3)))
    for i in range(2):
        _check(out[i], dense @ b3[i])


@pytest.mark.parametrize("shard_axis", ["rows", "rhs"])
def test_sharded_delta_matches_oracle(rng, shard_axis):
    """Sharded + structural delta through the in-body merge, both axes."""
    a, rows, cols, vals = make_sparse(rng, 96, 64, 0.07, n_dense_rows=4)
    mesh = make_spmm_mesh(1)
    splan = spmm.prepare_sharded(rows, cols, vals, a.shape, mesh,
                                 spmm.SpmmConfig(impl="xla"),
                                 shard_axis=shard_axis)
    dp = DynamicPlan(splan, auto_compact=False)
    dense = _dense(rows, cols, vals, a.shape)
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 10, replace=False)
    iv = rng.randn(10)
    dp.update(GraphDelta.inserts(zr[pick], zc[pick], iv))
    dense[zr[pick], zc[pick]] += iv
    dpick = rng.choice(rows.size, 5, replace=False)
    dp.update(GraphDelta.deletes(rows[dpick], cols[dpick]))
    dense[rows[dpick], cols[dpick]] = 0.0
    b = rng.randn(64, 16).astype(np.float32)
    _check(dp.execute(jnp.asarray(b)), dense @ b)


# ---------------------------------------------------------------------------
# single dispatch + bit-parity for the sharded delta merge
# ---------------------------------------------------------------------------
def test_sharded_delta_is_one_dispatch_with_bit_parity(rng):
    """The routed sidecar merges inside the shard_map program: exactly one
    executor dispatch, bit-identical to the legacy two-dispatch post-pass
    (execute_sharded + execute_delta_contribution).  The 2/4-way version of
    this check runs in tests/_dynamic_sharded_worker.py."""
    a, rows, cols, vals = make_sparse(rng, 96, 64, 0.07, n_dense_rows=4)
    cfg = spmm.SpmmConfig(impl="xla")
    mesh = make_spmm_mesh(1)
    splan = spmm.prepare_sharded(rows, cols, vals, a.shape, mesh, cfg,
                                 shard_axis="rows")
    dp = DynamicPlan(splan, auto_compact=False)
    dense = _dense(rows, cols, vals, a.shape)
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 9, replace=False)
    iv = rng.randn(9)
    dp.update(GraphDelta.inserts(zr[pick], zc[pick], iv))
    b = jnp.asarray(rng.randn(64, 16).astype(np.float32))

    delta = dp._materialize()
    assert isinstance(delta, plan_ir.ShardedDeltaFringe)
    dp.execute(b)  # warm the executor so the counted call is steady-state
    before = dispatch_count()
    fused = np.asarray(dp.execute(b))
    assert dispatch_count() - before == 1

    plain = build_delta_fringe(zr[pick], zc[pick], iv, a.shape, cfg)
    legacy = np.asarray(spmm.execute_sharded(splan, b)) + np.asarray(
        spmm.execute_delta_contribution(a.shape, cfg, plain, b)
    )
    assert np.array_equal(fused, legacy)


# ---------------------------------------------------------------------------
# trace-count invariants: the unified builder never adds retraces
# ---------------------------------------------------------------------------
def test_unified_builder_zero_extra_retraces(rng):
    a, rows, cols, vals = make_sparse(rng, 120, 100, 0.06, n_dense_rows=4)
    cfg = spmm.SpmmConfig(impl="xla")
    plan = spmm.prepare(rows, cols, vals, a.shape, cfg)
    b = jnp.asarray(rng.randn(100, 24).astype(np.float32))
    spmm.execute(plan, b).block_until_ready()
    before = fused_trace_count()
    for _ in range(3):
        spmm.execute(plan, b).block_until_ready()
    # a re-prepared identical structure reuses the same compiled program
    plan2 = spmm.prepare(rows, cols, vals, a.shape, cfg)
    assert plan2.signature() == plan.signature()
    spmm.execute(plan2, b).block_until_ready()
    assert fused_trace_count() == before


def test_sharded_builder_zero_extra_retraces(rng):
    a, rows, cols, vals = make_sparse(rng, 96, 64, 0.07, n_dense_rows=3)
    cfg = spmm.SpmmConfig(impl="xla")
    mesh = make_spmm_mesh(1)
    splan = spmm.prepare_sharded(rows, cols, vals, a.shape, mesh, cfg,
                                 shard_axis="rows")
    b = jnp.asarray(rng.randn(64, 16).astype(np.float32))
    spmm.execute_sharded(splan, b).block_until_ready()
    before = sharded_trace_count()
    splan2 = spmm.prepare_sharded(rows, cols, vals, a.shape, mesh, cfg,
                                  shard_axis="rows")
    spmm.execute_sharded(splan2, b).block_until_ready()
    assert sharded_trace_count() == before


def test_delta_capacity_bounds_retraces(rng):
    """Sidecar capacity growth (pow2) is the only retrace driver for a
    mutation stream through the unified builder."""
    a, rows, cols, vals = make_sparse(rng, 80, 64, 0.08, n_dense_rows=3)
    plan = spmm.prepare(rows, cols, vals, a.shape, spmm.SpmmConfig(impl="xla"))
    dp = DynamicPlan(plan, auto_compact=False)
    dense = _dense(rows, cols, vals, a.shape)
    zr, zc = np.nonzero(dense == 0)
    order = rng.permutation(zr.size)[:24]
    b = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    dp.update(GraphDelta.inserts(zr[order[:1]], zc[order[:1]],
                                 np.ones(1)))
    dp.execute(b)
    before = fused_trace_count()
    caps = set()
    for j in order[1:]:
        dp.update(GraphDelta.inserts(zr[j:j + 1], zc[j:j + 1], np.ones(1)))
        dp.execute(b)
        caps.add(dp._materialize().capacity)
    assert fused_trace_count() - before <= len(caps)


# ---------------------------------------------------------------------------
# bounded cache
# ---------------------------------------------------------------------------
def test_executor_cache_is_bounded_and_evicts(rng):
    """The per-signature executor cache is one bounded LRU: capacity set
    through SpmmConfig caps it, and evicted structures retrace on return
    (bounded memory in long-lived services, correctness preserved)."""
    prev_capacity = EXECUTOR_CACHE.capacity
    try:
        plans = []
        for m in (64, 80, 96):  # three distinct structures
            a, rows, cols, vals = make_sparse(rng, m, 48, 0.08)
            cfg = spmm.SpmmConfig(impl="xla", executor_cache_capacity=2)
            plans.append(spmm.prepare(rows, cols, vals, a.shape, cfg))
        b = jnp.asarray(rng.randn(48, 8).astype(np.float32))
        dense_b = np.asarray(b, np.float64)
        for p in plans:
            spmm.execute(p, b)
        assert EXECUTOR_CACHE.capacity == 2
        assert len(EXECUTOR_CACHE) <= 2
        # plans[0] was evicted (LRU): executing it again retraces — and is
        # still correct
        before = fused_trace_count()
        out = spmm.execute(plans[0], b)
        assert fused_trace_count() == before + 1
        m0 = plans[0].shape[0]
        expect = _dense(*map(np.asarray, (
            plans[0].update_maps.rows, plans[0].update_maps.cols,
            plans[0].update_maps.vals)), (m0, 48)) @ dense_b
        _check(out, expect)
        # the still-cached newest structure does not retrace
        before = fused_trace_count()
        spmm.execute(plans[2], b)
        assert fused_trace_count() == before
    finally:
        EXECUTOR_CACHE.set_capacity(prev_capacity)


def test_build_executor_identity_per_flavor(rng):
    """One cache entry per (sig, batch, delta, mesh) tuple; no aliasing."""
    a, rows, cols, vals = make_sparse(rng, 64, 48, 0.08)
    plan = spmm.prepare(rows, cols, vals, a.shape, spmm.SpmmConfig(impl="xla"))
    sig = plan.signature()
    assert build_executor(sig) is build_executor(sig)
    assert build_executor(sig) is not build_executor(sig, batch=2)
    delta = build_delta_fringe(np.array([0]), np.array([0]), np.array([1.0]),
                               a.shape, plan.config)
    assert build_executor(sig, delta_sig=delta.sig) is not build_executor(sig)
    with pytest.raises(ValueError, match="need a mesh"):
        build_executor(sig, shard_axis="rows")
