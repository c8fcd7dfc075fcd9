"""Dynamic-sparsity subsystem: value updates, delta sidecar, compaction.

Oracle discipline mirrors test_property_oracle.py: the value-only fast path
must be *bit-identical* (f32) to a full re-prepare — not merely close —
because update_values promises the executor cache sees indistinguishable
plans; the structural layers are checked against the fp64 dense oracle
across all three fringe dispatch tiers in interpret mode.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spmm
from repro.core.cost_model import (
    default_cost_model, fringe_resident_bytes, should_compact,
)
from repro.data import graphs
from repro.dynamic import DynamicPlan, GraphDelta, update_values
from repro.distributed.sharding import make_spmm_mesh
from _hyp import HAVE_HYPOTHESIS, given, settings, st

BN = 128


def _random_coo(seed, m, k, density):
    rng = np.random.RandomState(seed)
    mask = rng.rand(m, k) < density
    rows, cols = np.nonzero(mask)
    vals = rng.randn(rows.size)
    return rows.astype(np.int64), cols.astype(np.int64), vals


def _force_tier_budget(tier, k_pad, num_rows):
    if tier == "resident":
        return None
    if tier == "ksharded":
        return fringe_resident_bytes(k_pad, num_rows, BN) - 1
    return 16


def _tier_cfg(tier, rows, k, impl="pallas_interpret", alpha=1.0):
    num_rows = max(np.unique(rows).size, 1)
    k_pad = ((k + 63) // 64) * 64
    return spmm.SpmmConfig(
        impl=impl, bn=BN, alpha=alpha,
        fringe_vmem_budget=_force_tier_budget(tier, k_pad, num_rows),
    )


def _dense(rows, cols, vals, shape):
    a = np.zeros(shape, np.float64)
    if rows.size:
        np.add.at(a, (rows, cols), np.asarray(vals, np.float64))
    return a


def _assert_value_update_matches_reprepare(rows, cols, vals, shape, cfg,
                                           seed=0):
    """update_values ≡ re-prepare, bit for bit, on every value leaf."""
    rng = np.random.RandomState(seed + 100)
    plan = spmm.prepare(rows, cols, vals, shape, cfg)
    n_upd = max(1, rows.size // 3)
    idx = rng.choice(max(rows.size, 1), min(n_upd, max(rows.size, 1)),
                     replace=False)
    if not rows.size:
        return
    new_vals = rng.randn(idx.size)
    updated = update_values(plan, idx, new_vals)
    vals2 = np.asarray(vals).copy()
    vals2[idx] = new_vals.astype(vals2.dtype)
    ref = spmm.prepare(rows, cols, vals2, shape, cfg)
    for leaf in ("flat_values", "fringe_vals", "fringe_kb_vals"):
        assert np.array_equal(
            np.asarray(getattr(updated, leaf)),
            np.asarray(getattr(ref, leaf)),
        ), leaf
    b = jnp.asarray(rng.randn(shape[1], 16).astype(np.float32))
    assert np.array_equal(
        np.asarray(spmm.execute(updated, b)),
        np.asarray(spmm.execute(ref, b)),
    )
    assert updated.signature() == plan.signature()


# ---------------------------------------------------------------------------
# value-only fast path
# ---------------------------------------------------------------------------
@given(
    st.integers(0, 2**31 - 1) if HAVE_HYPOTHESIS else None,
    st.integers(1, 80) if HAVE_HYPOTHESIS else None,
    st.integers(1, 80) if HAVE_HYPOTHESIS else None,
    st.sampled_from([0.02, 0.12, 0.5]) if HAVE_HYPOTHESIS else None,
    st.sampled_from([None, 1.0, 1e-9]) if HAVE_HYPOTHESIS else None,
)
@settings(max_examples=12, deadline=None, derandomize=True)
def test_property_update_values_matches_reprepare(seed, m, k, density,
                                                  alpha):
    rows, cols, vals = _random_coo(seed, m, k, density)
    cfg = spmm.SpmmConfig(impl="xla", alpha=alpha,
                          enable_col_stage=alpha is None)
    _assert_value_update_matches_reprepare(rows, cols, vals, (m, k), cfg,
                                           seed=seed)


PINNED_VALUE = [
    # (seed, m, k, density, alpha, impl, tier)
    (0, 64, 64, 0.10, None, "xla", None),
    (1, 96, 48, 0.02, 1.0, "xla", None),          # all-fringe
    (2, 96, 48, 0.50, 1e-9, "xla", None),         # all-core
    (3, 40, 48, 0.15, 1.0, "pallas_interpret", "resident"),
    (4, 40, 48, 0.15, 1.0, "pallas_interpret", "ksharded"),
    (5, 40, 48, 0.15, 1.0, "pallas_interpret", "xla"),
]


@pytest.mark.parametrize("seed,m,k,density,alpha,impl,tier", PINNED_VALUE)
def test_pinned_update_values_matches_reprepare(seed, m, k, density, alpha,
                                                impl, tier):
    rows, cols, vals = _random_coo(seed, m, k, density)
    if tier is not None:
        cfg = _tier_cfg(tier, rows, k, impl=impl, alpha=alpha)
    else:
        cfg = spmm.SpmmConfig(impl=impl, alpha=alpha,
                              enable_col_stage=alpha is None)
    plan = spmm.prepare(rows, cols, vals, (m, k), cfg)
    if tier is not None and rows.size:
        assert plan.fringe_tier == tier
    _assert_value_update_matches_reprepare(rows, cols, vals, (m, k), cfg,
                                           seed=seed)


def test_update_values_bit_exact_on_extreme_magnitudes():
    """A scatter-ADD of value deltas would fail this: fp32 a + (b - a) loses
    b entirely once |a| >> |b|.  The set/recompute path must not."""
    rows = np.array([0, 1], np.int64)
    cols = np.array([0, 1], np.int64)
    vals = np.array([1e8, 2.0], np.float32)
    cfg = spmm.SpmmConfig(impl="xla")
    plan = spmm.prepare(rows, cols, vals, (4, 4), cfg)
    updated = update_values(plan, np.array([0]), np.array([1.0], np.float32))
    ref = spmm.prepare(rows, cols, np.array([1.0, 2.0], np.float32), (4, 4),
                       cfg)
    assert np.array_equal(np.asarray(updated.fringe_vals),
                          np.asarray(ref.fringe_vals))
    assert np.array_equal(np.asarray(updated.flat_values),
                          np.asarray(ref.flat_values))


def test_update_values_handles_duplicate_coo():
    """Duplicates accumulate into one tile cell; updating one of them
    recomputes the cell with the other contributors intact."""
    rows = np.array([0, 0, 0], np.int64)
    cols = np.array([0, 0, 1], np.int64)
    vals = np.array([1.0, 2.0, 3.0], np.float32)
    cfg = spmm.SpmmConfig(impl="xla", alpha=1e-9, enable_col_stage=False)
    plan = spmm.prepare(rows, cols, vals, (2, 2), cfg)
    updated = update_values(plan, np.array([1]), np.array([5.0], np.float32))
    ref = spmm.prepare(rows, cols, np.array([1.0, 5.0, 3.0], np.float32),
                       (2, 2), cfg)
    assert np.array_equal(np.asarray(updated.flat_values),
                          np.asarray(ref.flat_values))
    assert np.array_equal(np.asarray(updated.fringe_vals),
                          np.asarray(ref.fringe_vals))


def test_value_updates_never_retrace(rng):
    """The acceptance bar: a stream of value updates reuses one compiled
    executor — fused_trace_count is flat after the first execute."""
    rows, cols, vals = _random_coo(7, 72, 60, 0.1)
    plan = spmm.prepare(rows, cols, vals, (72, 60),
                        spmm.SpmmConfig(impl="xla"))
    b = jnp.asarray(rng.randn(60, 8).astype(np.float32))
    spmm.execute(plan, b).block_until_ready()
    before = spmm.fused_trace_count()
    for step in range(5):
        idx = rng.choice(rows.size, 9, replace=False)
        plan = update_values(plan, idx, rng.randn(9))
        spmm.execute(plan, b).block_until_ready()
    assert spmm.fused_trace_count() == before


def test_update_values_validation(rng):
    rows, cols, vals = _random_coo(3, 30, 30, 0.1)
    plan = spmm.prepare(rows, cols, vals, (30, 30),
                        spmm.SpmmConfig(impl="xla"))
    with pytest.raises(ValueError, match="out of range"):
        update_values(plan, np.array([rows.size]), np.array([1.0]))
    with pytest.raises(ValueError, match="disagree"):
        update_values(plan, np.array([0, 1]), np.array([1.0]))
    # a plan that lost its maps (pytree round trip) refuses updates
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    bare = jax.tree_util.tree_unflatten(treedef, leaves)
    assert bare.update_maps is None
    with pytest.raises(ValueError, match="update maps"):
        update_values(bare, np.array([0]), np.array([1.0]))


# ---------------------------------------------------------------------------
# structural delta sidecar + compaction
# ---------------------------------------------------------------------------
def _apply_delta_dense(dense, delta):
    for r, c, v in zip(delta.ins_rows, delta.ins_cols, delta.ins_vals):
        dense[r, c] += v
    for r, c in zip(delta.del_rows, delta.del_cols):
        dense[r, c] = 0.0
    for r, c, v in zip(delta.upd_rows, delta.upd_cols, delta.upd_vals):
        dense[r, c] = v


def _check_against_dense(dp, dense, b, tol=1e-4):
    out = np.asarray(dp.execute(b))
    expect = dense @ np.asarray(b, np.float64)
    scale = np.abs(expect).max() + 1e-9
    assert np.abs(out - expect).max() / scale < tol


@pytest.mark.parametrize("tier", ["resident", "ksharded", "xla"])
def test_structural_delta_matches_dense_all_tiers(tier, rng):
    rows, cols, vals = _random_coo(11, 48, 56, 0.12)
    cfg = _tier_cfg(tier, rows, 56)
    plan = spmm.prepare(rows, cols, vals, (48, 56), cfg)
    assert plan.fringe_tier == tier
    dp = DynamicPlan(plan, auto_compact=False)
    dense = _dense(rows, cols, vals, (48, 56))
    b = jnp.asarray(rng.randn(56, 24).astype(np.float32))

    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 12, replace=False)
    ins = GraphDelta.inserts(zr[pick], zc[pick], rng.randn(12))
    dp.update(ins)
    _apply_delta_dense(dense, ins)
    _check_against_dense(dp, dense, b)

    dpick = rng.choice(rows.size, 8, replace=False)
    dele = GraphDelta.deletes(rows[dpick], cols[dpick])
    dp.update(dele)
    _apply_delta_dense(dense, dele)
    rest = np.setdiff1d(np.arange(rows.size), dpick)[:10]
    upd = GraphDelta.updates(rows[rest], cols[rest], rng.randn(10))
    dp.update(upd)
    _apply_delta_dense(dense, upd)
    _check_against_dense(dp, dense, b)

    # forced compaction folds the sidecar into a fresh plan — same answer
    assert dp.delta_nnz > 0
    dp.compact()
    assert dp.delta_nnz == 0 and dp.compactions == 1
    _check_against_dense(dp, dense, b)


def test_delta_roundtrip_delete_reinstate(rng):
    rows = np.array([0, 1, 2], np.int64)
    cols = np.array([0, 1, 2], np.int64)
    vals = np.array([1.0, 2.0, 3.0])
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, (4, 4),
                                  spmm.SpmmConfig(impl="xla")),
                     auto_compact=False)
    dense = _dense(rows, cols, vals, (4, 4))
    b = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    # delete -> reinstate -> re-delete one base entry
    dp.update(GraphDelta.deletes([1], [1]))
    dense[1, 1] = 0
    _check_against_dense(dp, dense, b)
    dp.update(GraphDelta.inserts([1], [1], [7.0]))
    dense[1, 1] = 7.0
    _check_against_dense(dp, dense, b)
    dp.update(GraphDelta.updates([1], [1], [-2.5]))
    dense[1, 1] = -2.5
    _check_against_dense(dp, dense, b)
    dp.update(GraphDelta.deletes([1], [1]))
    dense[1, 1] = 0
    _check_against_dense(dp, dense, b)
    # insert onto a live base entry accumulates (COO-duplicate semantics)
    dp.update(GraphDelta.inserts([0], [0], [0.5]))
    dense[0, 0] += 0.5
    _check_against_dense(dp, dense, b)
    # sidecar-only insert deletes cleanly back out
    dp.update(GraphDelta.inserts([3], [3], [4.0]))
    dp.update(GraphDelta.deletes([3], [3]))
    _check_against_dense(dp, dense, b)


def test_delta_error_cases(rng):
    rows = np.array([0], np.int64)
    cols = np.array([0], np.int64)
    dp = DynamicPlan(spmm.prepare(rows, cols, np.array([1.0]), (4, 4),
                                  spmm.SpmmConfig(impl="xla")),
                     auto_compact=False)
    with pytest.raises(ValueError, match="absent"):
        dp.update(GraphDelta.deletes([2], [2]))
    with pytest.raises(ValueError, match="absent"):
        dp.update(GraphDelta.updates([2], [2], [1.0]))
    dp.update(GraphDelta.deletes([0], [0]))
    with pytest.raises(ValueError, match="deleted"):
        dp.update(GraphDelta.updates([0], [0], [1.0]))
    with pytest.raises(ValueError, match="already deleted"):
        dp.update(GraphDelta.deletes([0], [0]))
    with pytest.raises(ValueError, match="out of range"):
        dp.update(GraphDelta.inserts([9], [0], [1.0]))


def test_update_of_duplicate_base_entry_sets_logical_value(rng):
    """Duplicate COO triplets are one logical entry: a (row, col) update
    must set their SUM to the new value, not just the first occurrence."""
    rows = np.array([0, 0, 1], np.int64)
    cols = np.array([0, 0, 1], np.int64)
    vals = np.array([1.0, 2.0, 3.0])
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, (4, 4),
                                  spmm.SpmmConfig(impl="xla")),
                     auto_compact=False)
    b = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    stats = dp.update(GraphDelta.updates([0], [0], [5.0]))
    assert stats["delta_nnz"] == 0  # pure fast path
    dense = np.array([[5.0, 0, 0, 0], [0, 3.0, 0, 0],
                      [0, 0, 0, 0], [0, 0, 0, 0]])
    _check_against_dense(dp, dense, b)
    # insert onto the duplicated entry accumulates onto the logical sum
    dp.update(GraphDelta.inserts([0], [0], [1.5]))
    dense[0, 0] += 1.5
    _check_against_dense(dp, dense, b)
    # and deleting it negates the whole duplicate sum
    dp.update(GraphDelta.deletes([0], [0]))
    dense[0, 0] = 0
    _check_against_dense(dp, dense, b)


def test_repeated_inserts_in_one_batch_accumulate(rng):
    """Two inserts hitting one existing entry within a single GraphDelta
    must both land (last-write-wins would silently drop one)."""
    rows = np.array([0, 1], np.int64)
    cols = np.array([0, 1], np.int64)
    vals = np.array([5.0, 1.0])
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, (4, 4),
                                  spmm.SpmmConfig(impl="xla")),
                     auto_compact=False)
    b = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    dp.update(GraphDelta.inserts([0, 0], [0, 0], [1.0, 2.0]))
    dense = np.zeros((4, 4))
    dense[0, 0] = 8.0  # 5 + 1 + 2
    dense[1, 1] = 1.0
    _check_against_dense(dp, dense, b)
    # same guarantee on absent keys (overlay route)
    dp.update(GraphDelta.inserts([2, 2], [2, 2], [1.0, 2.0]))
    dense[2, 2] = 3.0
    _check_against_dense(dp, dense, b)


def test_replace_style_batch_applies_in_order(rng):
    """Within one GraphDelta, deletes apply first, then inserts, then
    updates — so delete+insert of one key is a replacement (the insert must
    not be silently discarded) and insert+update of a new key lands on the
    update."""
    rows = np.array([1], np.int64)
    cols = np.array([1], np.int64)
    dp = DynamicPlan(spmm.prepare(rows, cols, np.array([2.0]), (4, 4),
                                  spmm.SpmmConfig(impl="xla")),
                     auto_compact=False)
    b = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    dp.update(GraphDelta(
        del_rows=np.array([1]), del_cols=np.array([1]),
        ins_rows=np.array([1]), ins_cols=np.array([1]),
        ins_vals=np.array([9.0]),
    ))
    dense = np.zeros((4, 4))
    dense[1, 1] = 9.0
    _check_against_dense(dp, dense, b)
    dp.update(GraphDelta(
        ins_rows=np.array([2]), ins_cols=np.array([2]),
        ins_vals=np.array([1.0]),
        upd_rows=np.array([2]), upd_cols=np.array([2]),
        upd_vals=np.array([5.0]),
    ))
    dense[2, 2] = 5.0
    _check_against_dense(dp, dense, b)


def test_compaction_resets_sidecar_capacity(rng):
    """After a fold the sidecar must not stay padded to its historical
    maximum — the next single-edge delta should dispatch a minimal
    sidecar, not one sized like the pre-compaction delta."""
    rows, cols, vals = _random_coo(31, 40, 40, 0.1)
    dense = _dense(rows, cols, vals, (40, 40))
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, (40, 40),
                                  spmm.SpmmConfig(impl="xla")),
                     auto_compact=False)
    b = jnp.asarray(rng.randn(40, 8).astype(np.float32))
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 40, replace=False)
    iv = rng.randn(40)
    dp.update(GraphDelta.inserts(zr[pick], zc[pick], iv))
    dense[zr[pick], zc[pick]] += iv
    dp.execute(b)
    assert dp._capacity == 64
    dp.compact()
    more = np.setdiff1d(np.flatnonzero((dense == 0).ravel()),
                        zr[pick] * 40 + zc[pick])[:1]
    dp.update(GraphDelta.inserts(more // 40, more % 40, [1.0]))
    dense[more // 40, more % 40] += 1.0
    _check_against_dense(dp, dense, b)
    assert dp._capacity == 8  # minimal again, not the historical 64


def test_failed_update_batch_leaves_state_untouched(rng):
    """update() is atomic: a batch with one invalid mutation raises before
    ANY of its valid mutations are applied."""
    rows = np.array([0, 1], np.int64)
    cols = np.array([0, 1], np.int64)
    vals = np.array([1.0, 2.0])
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, (4, 4),
                                  spmm.SpmmConfig(impl="xla")),
                     auto_compact=False)
    b = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    dp.update(GraphDelta.inserts([2], [2], [9.0]))  # sidecar materializes
    before = np.asarray(dp.execute(b))
    overlay_before = dict(dp._overlay)
    bad = GraphDelta(
        ins_rows=np.array([3]), ins_cols=np.array([3]),
        ins_vals=np.array([4.0]),                      # valid insert...
        del_rows=np.array([3]), del_cols=np.array([0]),  # ...absent delete
    )
    with pytest.raises(ValueError, match="absent"):
        dp.update(bad)
    assert dp._overlay == overlay_before  # insert did not leak in
    assert np.array_equal(np.asarray(dp.execute(b)), before)
    # retrying a corrected batch applies exactly once
    dp.update(GraphDelta.inserts([3], [3], [4.0]))
    dense = np.zeros((4, 4))
    dense[0, 0], dense[1, 1], dense[2, 2], dense[3, 3] = 1.0, 2.0, 9.0, 4.0
    _check_against_dense(dp, dense, b)


def test_value_only_mutations_stay_on_fast_path(rng):
    """Weight changes on live structure never grow the sidecar."""
    rows, cols, vals = _random_coo(5, 50, 50, 0.1)
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, (50, 50),
                                  spmm.SpmmConfig(impl="xla")))
    idx = rng.choice(rows.size, 10, replace=False)
    stats = dp.update(GraphDelta.updates(rows[idx], cols[idx],
                                         rng.randn(10)))
    assert stats["fast_path"] == 10
    assert stats["delta_nnz"] == 0
    assert dp.delta_nnz == 0


def test_auto_compaction_triggers_and_preserves_answer(rng):
    rows, cols, vals = _random_coo(13, 40, 40, 0.1)
    dense = _dense(rows, cols, vals, (40, 40))
    dp = DynamicPlan(
        spmm.prepare(rows, cols, vals, (40, 40),
                     spmm.SpmmConfig(impl="xla")),
        max_delta_fraction=0.02,  # tiny budget: first real batch folds
    )
    b = jnp.asarray(rng.randn(40, 8).astype(np.float32))
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 20, replace=False)
    ins = GraphDelta.inserts(zr[pick], zc[pick], rng.randn(20))
    stats = dp.update(ins)
    _apply_delta_dense(dense, ins)
    assert stats["compacted"] == 1
    assert dp.delta_nnz == 0 and dp.compactions == 1
    assert dp.last_decision is not None and dp.last_decision.compact
    _check_against_dense(dp, dense, b)


def test_grow_from_empty_never_churns_compaction(rng):
    """Bug regression: a plan prepared with zero edges has base_cost == 0,
    and the old slowdown trigger computed inf -> a full fold on every
    update batch.  Growing a graph from empty must ride the sidecar until
    the floored nnz-fraction budget is actually exceeded."""
    m = k = 40
    empty = np.array([], np.int64)
    dp = DynamicPlan(
        spmm.prepare(empty, empty, np.array([], np.float64), (m, k),
                     spmm.SpmmConfig(impl="xla")),
    )
    dense = np.zeros((m, k), np.float64)
    b = jnp.asarray(rng.randn(k, 8).astype(np.float32))
    lin = rng.choice(m * k, 30, replace=False)
    before = spmm.prepare_call_count()
    for j in range(6):
        batch = lin[5 * j: 5 * (j + 1)]
        ins = GraphDelta.inserts(batch // k, batch % k, rng.randn(5))
        dp.update(ins)
        _apply_delta_dense(dense, ins)
        assert dp.last_decision is not None
        assert np.isfinite(dp.last_decision.est_slowdown)
    # 30 inserted edges sit far under the floored fraction budget: no fold
    assert dp.compactions == 0
    assert spmm.prepare_call_count() == before
    assert dp.delta_nnz == 30
    _check_against_dense(dp, dense, b)


def test_should_compact_policy():
    cm = default_cost_model()
    no = should_compact(cm, base_nnz=1000, delta_nnz=0, core_rows=128,
                        fringe_nnz=500, k=256)
    assert not no.compact and no.reason == "empty delta"
    frac = should_compact(cm, base_nnz=1000, delta_nnz=600, core_rows=128,
                          fringe_nnz=500, k=256)
    assert frac.compact and "fraction" in frac.reason
    slow = should_compact(cm, base_nnz=10**9, delta_nnz=10**6, core_rows=8,
                          fringe_nnz=10, k=8)
    assert slow.compact and "slowdown" in slow.reason
    ok = should_compact(cm, base_nnz=10**6, delta_nnz=10, core_rows=4096,
                        fringe_nnz=10**5, k=1024)
    assert not ok.compact


def test_delta_capacity_growth_is_logarithmic(rng):
    """One-edge-at-a-time mutation streams must not retrace per edge: the
    sidecar capacity grows in powers of two and the executor cache keys on
    capacity, so 24 single-insert batches compile at most ~log2(24) new
    delta programs."""
    rows, cols, vals = _random_coo(17, 40, 40, 0.05)
    dense = _dense(rows, cols, vals, (40, 40))
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, (40, 40),
                                  spmm.SpmmConfig(impl="xla")),
                     auto_compact=False)
    b = jnp.asarray(rng.randn(40, 8).astype(np.float32))
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 24, replace=False)
    before = spmm.fused_trace_count()
    caps = set()
    for j in range(24):
        dp.update(GraphDelta.inserts([zr[pick[j]]], [zc[pick[j]]],
                                     [float(rng.randn())]))
        dp.execute(b)
        caps.add(dp._capacity)
    assert caps <= {8, 16, 32}  # pow2, grow-only
    assert spmm.fused_trace_count() - before <= len(caps)
    expect = dense.copy()
    expect[zr[pick], zc[pick]] += 0  # structure only; values checked below
    _check_against_dense(
        dp, _dense(*dp.to_coo(), (40, 40)), b
    )


def test_mutate_stream_matches_dense_mirror(rng):
    rows, cols, vals = _random_coo(19, 60, 60, 0.08)
    dense = _dense(rows, cols, vals, (60, 60))
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, (60, 60),
                                  spmm.SpmmConfig(impl="xla")))
    b = jnp.asarray(rng.randn(60, 8).astype(np.float32))
    for step, delta in enumerate(graphs.mutate(
        rows, cols, vals, (60, 60), steps=6, insert_frac=0.05,
        delete_frac=0.04, update_frac=0.1, seed=2,
    )):
        dp.update(delta)
        _apply_delta_dense(dense, delta)
        _check_against_dense(dp, dense, b)
    assert dp.compactions >= 0  # stream survives with or without folds


# ---------------------------------------------------------------------------
# sharded plans (1-device mesh everywhere; multi-way via subprocess worker)
# ---------------------------------------------------------------------------
def test_sharded_value_update_matches_reprepare(rng):
    rows, cols, vals = _random_coo(23, 70, 50, 0.1)
    mesh = make_spmm_mesh(1)
    cfg = spmm.SpmmConfig(impl="xla")
    for axis in ("rows", "rhs"):
        splan = spmm.prepare_sharded(rows, cols, vals, (70, 50), mesh, cfg,
                                     shard_axis=axis)
        idx = rng.choice(rows.size, 14, replace=False)
        nv = rng.randn(14)
        updated = update_values(splan, idx, nv)
        vals2 = vals.copy()
        vals2[idx] = nv
        ref = spmm.prepare_sharded(rows, cols, vals2, (70, 50), mesh, cfg,
                                   shard_axis=axis)
        for i, (got, want) in enumerate(zip(updated.leaves, ref.leaves)):
            assert np.array_equal(np.asarray(got), np.asarray(want)), (
                axis, i)
        b = jnp.asarray(rng.randn(50, 16).astype(np.float32))
        assert np.array_equal(
            np.asarray(spmm.execute_sharded(updated, b)),
            np.asarray(spmm.execute_sharded(ref, b)),
        )


def test_sharded_structural_and_compact(rng):
    rows, cols, vals = _random_coo(29, 64, 48, 0.1)
    mesh = make_spmm_mesh(1)
    splan = spmm.prepare_sharded(rows, cols, vals, (64, 48), mesh,
                                 spmm.SpmmConfig(impl="xla"),
                                 shard_axis="rows")
    dp = DynamicPlan(splan, auto_compact=False)
    dense = _dense(rows, cols, vals, (64, 48))
    b = jnp.asarray(rng.randn(48, 12).astype(np.float32))
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 10, replace=False)
    ins = GraphDelta.inserts(zr[pick], zc[pick], rng.randn(10))
    dp.update(ins)
    _apply_delta_dense(dense, ins)
    dpick = rng.choice(rows.size, 6, replace=False)
    dele = GraphDelta.deletes(rows[dpick], cols[dpick])
    dp.update(dele)
    _apply_delta_dense(dense, dele)
    _check_against_dense(dp, dense, b)
    dp.compact()
    assert isinstance(dp.plan, spmm.ShardedPlan)  # stays sharded
    assert dp.delta_nnz == 0
    _check_against_dense(dp, dense, b)


def test_forced_mesh_dynamic_parity(forced_mesh_run):
    """2/4-way mesh parity for value updates + structural deltas +
    compaction (subprocess with forced host devices)."""
    import os
    forced_mesh_run(
        os.path.join(os.path.dirname(__file__),
                     "_dynamic_sharded_worker.py"),
        n_devices=4,
    )
