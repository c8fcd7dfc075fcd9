"""Operator family on the plan IR: SDDMM + SpGEMM + the repro.sparse facade.

Contract under test: ``sddmm`` and ``spspmm`` are *fused-body stages* of
the unified executor pipeline, not new executor families — dense numpy
parity in every dispatch flavor (forced fringe tiers, interpret-mode
pallas, batched, sharded), one jitted dispatch per call, zero extra
retraces per ``(op, signature)``, and SDDMM output feeding
``dynamic.update_values`` unchanged (the GAT round trip).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_ir, spmm
from repro.core.cost_model import (
    FRINGE_VMEM_BUDGET, assert_vmem_claim, fringe_resident_bytes,
    sddmm_resident_bytes, select_sddmm_tier,
)
from repro.dynamic import DynamicPlan, GraphDelta, update_values
from repro.errors import PlanBuildError
from repro.exec import (
    dispatch_count, execute_sddmm, execute_spspmm, fused_trace_count,
)
from repro.kernels import ops
from repro.distributed.sharding import make_spmm_mesh
from repro.obs import REGISTRY
import repro.sparse as sp
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


def _check(out, expect, tol=1e-4):
    scale = np.abs(expect).max() + 1e-9
    assert np.abs(np.asarray(out) - expect).max() / scale < tol


def _coo(rng, m, k, nnz):
    rows = rng.randint(0, m, nnz).astype(np.int64)
    cols = rng.randint(0, k, nnz).astype(np.int64)
    return rows, cols, rng.randn(nnz)


# ---------------------------------------------------------------------------
# SDDMM vs the dense oracle, every dispatch flavor
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tier", ["resident", "ksharded", "xla"])
def test_sddmm_all_fringe_tiers_match_oracle(rng, tier):
    """Forced-budget plans (interpret-mode pallas) across all tiers.

    The tier budget also flows into the SDDMM gather tier (it is part of
    the tagged signature), so tier='xla' exercises the reference gather
    and tier='resident' the pallas lane-select kernel.
    """
    m, k, d = 72, 128, 16
    rows, cols, vals = _coo(rng, m, k, 500)
    cfg = spmm.SpmmConfig(
        impl="pallas_interpret", bn=BN, alpha=1.0,
        fringe_vmem_budget=_force_tier_budget(tier, k, m),
    )
    plan = spmm.prepare(rows, cols, vals, (m, k), cfg)
    assert plan.fringe_tier == tier
    x = rng.randn(m, d).astype(np.float32)
    y = rng.randn(d, k).astype(np.float32)
    out = execute_sddmm(plan, jnp.asarray(x), jnp.asarray(y))
    _check(out, (x.astype(np.float64) @ y.astype(np.float64))[rows, cols])


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_sddmm_mixed_core_fringe(rng, impl):
    """Default alpha: both engine paths active, dense rows in the core."""
    a, rows, cols, vals = make_sparse(rng, 96, 80, 0.07, n_dense_rows=4)
    plan = spmm.prepare(rows, cols, vals, a.shape,
                        spmm.SpmmConfig(impl=impl, bn=BN))
    d = 12
    x = rng.randn(96, d).astype(np.float32)
    y = rng.randn(d, 80).astype(np.float32)
    out = execute_sddmm(plan, jnp.asarray(x), jnp.asarray(y))
    _check(out, (x.astype(np.float64) @ y.astype(np.float64))[rows, cols])


def test_sddmm_reorder_cols(rng):
    """Column-reordered plans address Y through col_perm correctly."""
    m, k, d = 64, 96, 8
    rows, cols, vals = _coo(rng, m, k, 400)
    for impl in ("xla", "pallas_interpret"):
        plan = spmm.prepare(
            rows, cols, vals, (m, k),
            spmm.SpmmConfig(impl=impl, bn=BN, reorder_cols=True),
        )
        x = rng.randn(m, d).astype(np.float32)
        y = rng.randn(d, k).astype(np.float32)
        out = execute_sddmm(plan, jnp.asarray(x), jnp.asarray(y))
        _check(out, (x.astype(np.float64) @ y.astype(np.float64))[rows, cols])


def test_sddmm_batched_one_vmapped_dispatch(rng):
    m, k, d, batch = 80, 64, 10, 3
    rows, cols, vals = _coo(rng, m, k, 350)
    plan = spmm.prepare(rows, cols, vals, (m, k), spmm.SpmmConfig(impl="xla"))
    xb = rng.randn(batch, m, d).astype(np.float32)
    yb = rng.randn(batch, d, k).astype(np.float32)
    out = np.asarray(execute_sddmm(plan, jnp.asarray(xb), jnp.asarray(yb)))
    assert out.shape == (batch, len(rows))
    for i in range(batch):
        _check(out[i], (xb[i].astype(np.float64)
                        @ yb[i].astype(np.float64))[rows, cols])
    # mixed batching is rejected, not broadcast
    with pytest.raises(ValueError, match="batch"):
        execute_sddmm(plan, jnp.asarray(xb), jnp.asarray(yb[0]))


def test_sddmm_duplicate_coo_entries(rng):
    """Duplicate triplets share a tile slot; each gets the same dot."""
    m, k, d = 40, 32, 6
    rows = np.array([3, 3, 3, 17, 17, 39], np.int64)
    cols = np.array([5, 5, 9, 20, 20, 31], np.int64)
    vals = rng.randn(6)
    plan = spmm.prepare(rows, cols, vals, (m, k), spmm.SpmmConfig(impl="xla"))
    x = rng.randn(m, d).astype(np.float32)
    y = rng.randn(d, k).astype(np.float32)
    out = execute_sddmm(plan, jnp.asarray(x), jnp.asarray(y))
    _check(out, (x.astype(np.float64) @ y.astype(np.float64))[rows, cols])


def test_sddmm_empty_and_single_path_plans(rng):
    d = 8
    # empty pattern
    plan = spmm.prepare(np.zeros(0, np.int64), np.zeros(0, np.int64),
                        np.zeros(0), (16, 24), spmm.SpmmConfig(impl="xla"))
    out = execute_sddmm(plan, jnp.ones((16, d)), jnp.ones((d, 24)))
    assert out.shape == (0,)
    # all-fringe (alpha=1) and all-core (alpha=0) plans
    rows, cols, vals = _coo(rng, 48, 40, 200)
    x = rng.randn(48, d).astype(np.float32)
    y = rng.randn(d, 40).astype(np.float32)
    expect = (x.astype(np.float64) @ y.astype(np.float64))[rows, cols]
    for alpha in (0.0, 1.0):
        plan = spmm.prepare(rows, cols, vals, (48, 40),
                            spmm.SpmmConfig(impl="xla", alpha=alpha))
        _check(execute_sddmm(plan, jnp.asarray(x), jnp.asarray(y)), expect)


def _old_merge(plan, x, y):
    """The merge the path-ordered one replaced, kept as the oracle: one
    gather per nonzero from the flat tile stream and one from the fringe
    values, then a select on the path."""
    cfg, maps = plan.config, plan.update_maps
    k = plan.shape[1]
    core_lin = maps.core_lin
    f_sel = np.flatnonzero(core_lin < 0)
    f_idx = np.full(maps.nnz, -1)
    f_idx[f_sel] = np.arange(f_sel.size)
    core_vals = fringe_vals = None
    if plan.has_core:
        crm = plan.core_row_map
        xp = jnp.where((crm >= 0)[:, None],
                       x[jnp.clip(crm, 0, x.shape[0] - 1)], 0.0)
        yp = y[:, plan.col_perm] if cfg.reorder_cols else y
        yp = jnp.pad(yp, ((0, 0), (0, -k % cfg.bk)))
        tiles = ops.sddmm_block_stream(plan.step_window, plan.step_col, xp,
                                       yp, bm=cfg.bm, bk=cfg.bk,
                                       impl=cfg.impl)
        core_vals = np.asarray(tiles).reshape(-1)[np.clip(core_lin, 0, None)]
    if plan.has_fringe:
        fv = ops.sddmm_gather(
            jnp.asarray(maps.rows[f_sel], jnp.int32),
            jnp.asarray(maps.cols[f_sel], jnp.int32), x, y.T,
            impl=cfg.impl, chunk=cfg.fringe_chunk,
            vmem_budget=cfg.fringe_vmem_budget)
        fringe_vals = np.asarray(fv)[np.clip(f_idx, 0, None)]
    if core_vals is None:
        return fringe_vals
    if fringe_vals is None:
        return core_vals
    return np.where(core_lin >= 0, core_vals, fringe_vals)


def _merge_case(rng, case):
    """(rows, cols, SpmmConfig overrides, batch) of one merge case."""
    _, rows, cols, _ = make_sparse(rng, 96, 80, 0.07, n_dense_rows=4)
    if case == "duplicates":
        dup = rng.choice(rows.size, 40, replace=False)
        rows = np.concatenate([rows, rows[dup]])
        cols = np.concatenate([cols, cols[dup]])
    if case != "row_sorted":  # COO in shuffled order, not row-sorted
        order = rng.permutation(rows.size)
        rows, cols = rows[order], cols[order]
    # alpha 0.5 sends the sparse tail to the fringe: both paths carry work
    over = {"core_only": dict(alpha=0.0), "fringe_only": dict(alpha=1.0),
            "reorder_cols": dict(alpha=0.5, reorder_cols=True),
            "xla_fringe_tier": dict(alpha=0.5, fringe_vmem_budget=16),
            }.get(case, dict(alpha=0.5))
    return rows, cols, over, 3 if case == "batched" else None


@pytest.mark.parametrize("case", [
    "row_sorted", "shuffled", "duplicates", "core_only", "fringe_only",
    "reorder_cols", "batched", "xla_fringe_tier"])
def test_sddmm_path_ordered_merge_bit_identical_to_old_merge(rng, case):
    """The path-ordered merge (compact core gather, then one key-value
    sort into COO order) places the very values the per-nonzero gathers
    did, bit for bit, and they match ``X @ Y`` at the pattern.  Every case
    runs the Pallas tile stream in interpret mode: impl "xla" takes the
    reference gather and never reaches the merge."""
    rows, cols, over, batch = _merge_case(rng, case)
    m, k, d = 96, 80, 12
    plan = spmm.prepare(rows, cols, np.ones(rows.size), (m, k),
                        spmm.SpmmConfig(impl="pallas_interpret", bn=BN,
                                        **over))
    assert (plan.has_core, plan.has_fringe) == {
        "core_only": (True, False), "fringe_only": (False, True),
    }.get(case, (True, True))
    if case == "xla_fringe_tier":
        assert plan.fringe_tier == "xla"
    place = np.asarray(plan_ir.build_sddmm_maps(plan).place)
    np.testing.assert_array_equal(np.sort(place), np.arange(rows.size))
    lead = () if batch is None else (batch,)
    x = jnp.asarray(rng.randn(*lead, m, d).astype(np.float32))
    y = jnp.asarray(rng.randn(*lead, d, k).astype(np.float32))
    out = np.asarray(execute_sddmm(plan, x, y))
    for i in range(batch or 1):
        xi, yi = (x, y) if batch is None else (x[i], y[i])
        oi = out if batch is None else out[i]
        np.testing.assert_array_equal(oi, _old_merge(plan, xi, yi))
        _check(oi, (np.asarray(xi, np.float64)
                    @ np.asarray(yi, np.float64))[rows, cols])


def _merge_placements():
    snap = REGISTRY.snapshot().get("exec_sddmm_merge_total", {})
    return {s["labels"]["placement"]: s["value"]
            for s in snap.get("series", ())}


@pytest.mark.parametrize("impl,alpha,placement", [
    ("pallas_interpret", 0.5, "sorted"),
    ("pallas_interpret", 0.0, "core_only"),
    ("pallas_interpret", 1.0, "fringe_only"),
    ("xla", None, "reference"),
])
def test_sddmm_merge_counter(rng, impl, alpha, placement):
    """One count per fused-body trace, under the placement it traced, and
    none on a warm call; a width of its own per case forces a fresh
    trace."""
    a, rows, cols, vals = make_sparse(rng, 96, 80, 0.07, n_dense_rows=4)
    plan = spmm.prepare(rows, cols, vals, a.shape,
                        spmm.SpmmConfig(impl=impl, bn=BN, alpha=alpha))
    d = {"sorted": 19, "core_only": 21, "fringe_only": 23,
         "reference": 25}[placement]
    x = jnp.asarray(rng.randn(96, d).astype(np.float32))
    y = jnp.asarray(rng.randn(d, 80).astype(np.float32))
    before = _merge_placements()
    execute_sddmm(plan, x, y).block_until_ready()
    after = _merge_placements()
    grew = {p: after[p] - before.get(p, 0) for p in after}
    assert {p: n for p, n in grew.items() if n} == {placement: 1}
    execute_sddmm(plan, x, y).block_until_ready()
    assert _merge_placements() == after


def test_sddmm_operand_validation(rng):
    rows, cols, vals = _coo(rng, 32, 24, 100)
    plan = spmm.prepare(rows, cols, vals, (32, 24), spmm.SpmmConfig())
    with pytest.raises(ValueError, match="M="):
        execute_sddmm(plan, jnp.ones((31, 4)), jnp.ones((4, 24)))
    with pytest.raises(ValueError, match="K="):
        execute_sddmm(plan, jnp.ones((32, 4)), jnp.ones((4, 23)))
    with pytest.raises(ValueError, match="disagree on D"):
        execute_sddmm(plan, jnp.ones((32, 4)), jnp.ones((5, 24)))


# ---------------------------------------------------------------------------
# retrace / dispatch invariants
# ---------------------------------------------------------------------------
def test_sddmm_zero_extra_retraces(rng):
    m, k, d = 64, 48, 8
    rows, cols, vals = _coo(rng, m, k, 300)
    plan = spmm.prepare(rows, cols, vals, (m, k), spmm.SpmmConfig(impl="xla"))
    x = jnp.asarray(rng.randn(m, d).astype(np.float32))
    y = jnp.asarray(rng.randn(d, k).astype(np.float32))
    execute_sddmm(plan, x, y)  # warm
    t0, d0 = fused_trace_count(), dispatch_count()
    for _ in range(4):
        execute_sddmm(plan, x, y)
    assert fused_trace_count() - t0 == 0  # cached executor, no retrace
    assert dispatch_count() - d0 == 4     # exactly one dispatch per call
    # value-updated plan: same signature -> same executor, still no retrace
    plan2 = update_values(plan, np.arange(len(rows)), rng.randn(len(rows)))
    assert plan2.signature() == plan.signature()
    execute_sddmm(plan2, x, y)
    assert fused_trace_count() - t0 == 0


def test_sddmm_and_spmm_executors_never_alias(rng):
    """Same plan signature, different op tag -> distinct cache entries."""
    m, k = 48, 40
    rows, cols, vals = _coo(rng, m, k, 200)
    plan = spmm.prepare(rows, cols, vals, (m, k), spmm.SpmmConfig(impl="xla"))
    sig = plan.signature()
    tagged = plan_ir.tag_op(sig, "sddmm", 1, 2, 3)
    assert plan_ir.sig_op(sig) == "spmm"
    assert plan_ir.sig_op(tagged) == "sddmm"
    assert plan_ir.op_extra(tagged) == (1, 2, 3)
    assert plan_ir.untag_sig(tagged) == sig
    assert tagged != sig
    # impl helpers see through the tag (health gating + degrade path)
    assert plan_ir.sig_impl(tagged) == plan_ir.sig_impl(sig)
    fall = plan_ir.xla_fallback_sig(tagged)
    assert plan_ir.sig_impl(fall) == "xla" and plan_ir.sig_op(fall) == "sddmm"
    # spmm then sddmm on the same plan: the sddmm call must trace fresh
    b = jnp.asarray(rng.randn(k, 8).astype(np.float32))
    spmm.execute(plan, b)
    t0 = fused_trace_count()
    execute_sddmm(plan, jnp.ones((m, 4)), jnp.ones((4, k)))
    assert fused_trace_count() - t0 == 1


# ---------------------------------------------------------------------------
# SDDMM -> update_values -> SpMM (the GAT round trip)
# ---------------------------------------------------------------------------
def test_sddmm_feeds_update_values_round_trip(rng):
    m, k, d = 72, 64, 8
    rows, cols, vals = _coo(rng, m, k, 320)
    plan = spmm.prepare(rows, cols, vals, (m, k), spmm.SpmmConfig(impl="xla"))
    x = rng.randn(m, d).astype(np.float32)
    y = rng.randn(d, k).astype(np.float32)
    w = np.asarray(execute_sddmm(plan, jnp.asarray(x), jnp.asarray(y)))
    plan2 = update_values(plan, np.arange(len(rows)), w)
    b = rng.randn(k, 16).astype(np.float32)
    dense_w = np.zeros((m, k))
    np.add.at(dense_w, (rows, cols), w.astype(np.float64))
    _check(spmm.execute(plan2, jnp.asarray(b)), dense_w @ b)


# ---------------------------------------------------------------------------
# SpGEMM vs the dense oracle
# ---------------------------------------------------------------------------
def test_spspmm_matches_oracle(rng):
    m, k, n = 64, 56, 48
    ar, ac, av = _coo(rng, m, k, 300)
    br, bc, bv = _coo(rng, k, n, 250)
    pa = spmm.prepare(ar, ac, av, (m, k), spmm.SpmmConfig(impl="xla"))
    pb = spmm.prepare(br, bc, bv, (k, n), spmm.SpmmConfig(impl="xla"))
    cr, cc, cv, cshape = execute_spspmm(pa, pb)
    assert cshape == (m, n)
    ref = _dense(ar, ac, av, (m, k)) @ _dense(br, bc, bv, (k, n))
    got = np.zeros(cshape)
    got[cr, cc] = np.asarray(cv, np.float64)
    _check(got, ref)
    # row-major output, unique pattern: ready for prepare() directly
    key = cr * n + cc
    assert np.all(np.diff(key) > 0)


def test_spspmm_duplicates_accumulate_like_dense(rng):
    """Duplicate COO triplets in BOTH inputs expand independently."""
    ar = np.array([0, 0, 1, 1], np.int64)
    ac = np.array([2, 2, 3, 0], np.int64)
    av = rng.randn(4)
    br = np.array([2, 2, 3, 0, 0], np.int64)
    bc = np.array([1, 1, 4, 2, 2], np.int64)
    bv = rng.randn(5)
    pa = spmm.prepare(ar, ac, av, (2, 4), spmm.SpmmConfig())
    pb = spmm.prepare(br, bc, bv, (4, 6), spmm.SpmmConfig())
    cr, cc, cv, cshape = execute_spspmm(pa, pb)
    ref = _dense(ar, ac, av, (2, 4)) @ _dense(br, bc, bv, (4, 6))
    got = np.zeros(cshape)
    got[cr, cc] = np.asarray(cv, np.float64)
    _check(got, ref)


def test_spspmm_empty_and_disjoint(rng):
    empty = spmm.prepare(np.zeros(0, np.int64), np.zeros(0, np.int64),
                         np.zeros(0), (8, 8), spmm.SpmmConfig())
    pa = spmm.prepare(np.array([0]), np.array([1]), np.array([2.0]),
                      (8, 8), spmm.SpmmConfig())
    for a, b in ((empty, pa), (pa, empty)):
        cr, cc, cv, cshape = execute_spspmm(a, b)
        assert cr.size == 0 and cc.size == 0 and cv.shape == (0,)
    # structurally disjoint: A's columns never meet a B row
    pb = spmm.prepare(np.array([5]), np.array([3]), np.array([1.0]),
                      (8, 8), spmm.SpmmConfig())
    cr, cc, cv, _ = execute_spspmm(pa, pb)
    assert cr.size == 0
    with pytest.raises(ValueError, match="inner"):
        execute_spspmm(pa, spmm.prepare(np.array([0]), np.array([0]),
                                        np.array([1.0]), (9, 4),
                                        spmm.SpmmConfig()))


def test_spspmm_one_dispatch_zero_retrace(rng):
    m, k, n = 48, 40, 32
    ar, ac, av = _coo(rng, m, k, 200)
    br, bc, bv = _coo(rng, k, n, 180)
    pa = spmm.prepare(ar, ac, av, (m, k), spmm.SpmmConfig())
    pb = spmm.prepare(br, bc, bv, (k, n), spmm.SpmmConfig())
    execute_spspmm(pa, pb)  # warm
    t0, d0 = fused_trace_count(), dispatch_count()
    execute_spspmm(pa, pb)
    execute_spspmm(pa, pb)
    assert fused_trace_count() - t0 == 0
    assert dispatch_count() - d0 == 2


# ---------------------------------------------------------------------------
# sharded flavors (1-way in-process; 2/4-way in the forced-mesh worker)
# ---------------------------------------------------------------------------
def test_sddmm_sharded_matches_single_device(rng):
    m, k, d = 96, 64, 12
    rows, cols, vals = _coo(rng, m, k, 400)
    cfg = spmm.SpmmConfig(impl="xla")
    plan = spmm.prepare(rows, cols, vals, (m, k), cfg)
    splan = spmm.prepare_sharded(rows, cols, vals, (m, k),
                                 make_spmm_mesh(1), cfg)
    x = jnp.asarray(rng.randn(m, d).astype(np.float32))
    y = jnp.asarray(rng.randn(d, k).astype(np.float32))
    ref = np.asarray(execute_sddmm(plan, x, y))
    np.testing.assert_allclose(np.asarray(execute_sddmm(splan, x, y)),
                               ref, rtol=1e-5, atol=1e-5)
    xb = jnp.asarray(rng.randn(2, m, d).astype(np.float32))
    yb = jnp.asarray(rng.randn(2, d, k).astype(np.float32))
    refb = np.asarray(execute_sddmm(plan, xb, yb))
    np.testing.assert_allclose(np.asarray(execute_sddmm(splan, xb, yb)),
                               refb, rtol=1e-5, atol=1e-5)


def test_spspmm_sharded_inputs(rng):
    m, k, n = 80, 64, 48
    ar, ac, av = _coo(rng, m, k, 300)
    br, bc, bv = _coo(rng, k, n, 250)
    cfg = spmm.SpmmConfig(impl="xla")
    sa = spmm.prepare_sharded(ar, ac, av, (m, k), make_spmm_mesh(1), cfg)
    pb = spmm.prepare(br, bc, bv, (k, n), cfg)
    cr, cc, cv, cshape = execute_spspmm(sa, pb)
    ref = _dense(ar, ac, av, (m, k)) @ _dense(br, bc, bv, (k, n))
    got = np.zeros(cshape)
    got[cr, cc] = np.asarray(cv, np.float64)
    _check(got, ref)


def test_forced_mesh_operator_family(forced_mesh_run):
    """2/4-way sharded SDDMM + spspmm parity in a forced-device subprocess."""
    import os

    worker = os.path.join(os.path.dirname(__file__),
                          "_operator_family_worker.py")
    out = forced_mesh_run(worker, n_devices=8)
    assert "OPERATORS OK" in out.stdout


# ---------------------------------------------------------------------------
# cost model: sddmm tier + the consolidated VMEM claim helper
# ---------------------------------------------------------------------------
def test_select_sddmm_tier_budget_boundary():
    d, ns, nd = 64, 512, 512
    need = sddmm_resident_bytes(d, ns, nd)
    assert select_sddmm_tier(d, ns, nd, vmem_budget=need) == "resident"
    assert select_sddmm_tier(d, ns, nd, vmem_budget=need - 1) == "xla"
    assert select_sddmm_tier(16, 64, 64) == "resident"  # default budget


def test_assert_vmem_claim():
    assert_vmem_claim(FRINGE_VMEM_BUDGET, "fits")  # no raise
    with pytest.raises(ValueError, match="VMEM"):
        assert_vmem_claim(2**31, "too big")


# ---------------------------------------------------------------------------
# the repro.sparse facade
# ---------------------------------------------------------------------------
def test_facade_surface(rng):
    m, k, n, d = 48, 40, 24, 8
    rows, cols, vals = _coo(rng, m, k, 200)
    A = sp.from_coo(rows, cols, vals, (m, k), impl="xla")
    assert A.shape == (m, k) and A.nnz == 200 and not A.is_dynamic
    dense = _dense(rows, cols, vals, (m, k))
    np.testing.assert_allclose(A.dense(), dense)
    b = rng.randn(k, n).astype(np.float32)
    _check(sp.spmm(A, b), dense @ b)
    _check(A @ b, dense @ b)
    b3 = rng.randn(2, k, n).astype(np.float32)
    out = np.asarray(sp.bspmm(A, b3))
    for i in range(2):
        _check(out[i], dense @ b3[i])
    with pytest.raises(ValueError, match="batch"):
        sp.bspmm(A, b)
    x = rng.randn(m, d).astype(np.float32)
    y = rng.randn(d, k).astype(np.float32)
    w = sp.sddmm(A, x, y, deadline=60.0)
    _check(w, (x.astype(np.float64) @ y.astype(np.float64))[rows, cols])
    # with_values: functional, same executor, new values
    A2 = A.with_values(np.asarray(w))
    np.testing.assert_allclose(A2.dense(),
                               _dense(rows, cols, np.asarray(w), (m, k)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(A.dense(), dense)  # original untouched
    with pytest.raises(ValueError, match="nonzero"):
        A.with_values(np.ones(3))
    # spspmm returns a prepared SparseMatrix (operator sugar: A @ B)
    br, bc, bv = _coo(rng, k, n, 150)
    B = sp.from_coo(br, bc, bv, (k, n))
    C = A @ B
    assert isinstance(C, sp.SparseMatrix) and C.shape == (m, n)
    _check(C.dense(), dense @ _dense(br, bc, bv, (k, n)))


def test_facade_config_handling(rng):
    rows, cols, vals = _coo(rng, 32, 24, 80)
    cfg = spmm.SpmmConfig(impl="xla", bn=BN)
    A = sp.from_coo(rows, cols, vals, (32, 24), config=cfg)
    assert A.plan.config.bn == BN
    B = sp.from_coo(rows, cols, vals, (32, 24), alpha=1.0)
    assert B.plan.config.alpha == 1.0
    with pytest.raises(ValueError, match="not both"):
        sp.from_coo(rows, cols, vals, (32, 24), config=cfg, bn=64)
    with pytest.raises(TypeError):
        sp.spmm(np.ones((3, 3)), np.ones((3, 2)))


def test_facade_dynamic_flavor(rng):
    m, k, d = 56, 48, 8
    rows, cols, vals = _coo(rng, m, k, 250)
    A = sp.from_coo(rows, cols, vals, (m, k), dynamic=True)
    assert A.is_dynamic
    x = rng.randn(m, d).astype(np.float32)
    y = rng.randn(d, k).astype(np.float32)
    expect = (x.astype(np.float64) @ y.astype(np.float64))[rows, cols]
    _check(sp.sddmm(A, x, y), expect)
    # pending structural deltas invalidate the prepared pattern
    dense = _dense(rows, cols, vals, (m, k))
    zr, zc = np.nonzero(dense == 0)
    A.plan.update(GraphDelta(ins_rows=zr[:2], ins_cols=zc[:2],
                             ins_vals=np.ones(2)))
    with pytest.raises(PlanBuildError, match="compact"):
        sp.sddmm(A, x, y)
    A.plan.compact()
    out = sp.sddmm(A, x, y)
    rows2, cols2, _ = A.coo()
    _check(out, (x.astype(np.float64)
                 @ y.astype(np.float64))[rows2, cols2])


def test_facade_deadline(rng):
    from repro.errors import DeadlineExceeded

    rows, cols, vals = _coo(rng, 32, 24, 80)
    A = sp.from_coo(rows, cols, vals, (32, 24))
    b = rng.randn(24, 8).astype(np.float32)
    with pytest.raises(DeadlineExceeded):
        sp.spmm(A, b, deadline=0.0)
    _check(sp.spmm(A, b, deadline=120.0),
           _dense(rows, cols, vals, (32, 24)) @ b)


def test_core_spmm_forwarders_deprecated_once():
    import warnings

    import repro.core.spmm as core_spmm

    core_spmm._WARNED_FORWARD = False
    with pytest.warns(DeprecationWarning, match="repro.sparse"):
        core_spmm.__getattr__("execute")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second access stays silent
        assert core_spmm.__getattr__("execute") is not None
        assert core_spmm.__getattr__("dispatch_count") is not None
