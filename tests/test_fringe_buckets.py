"""Degree-bucketed (ELL) XLA fringe: the layout prepare() builds, the
gather-reduce the fused body runs over it, and what follows the layout
(update maps, signatures, the formulation counter)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_ir, spmm
from repro.dynamic.delta import update_values
from repro.exec.api import execute, execute_sharded
from repro.kernels import ops, ref
from repro.distributed.sharding import make_spmm_mesh
from repro.obs import REGISTRY


def _coo(degrees, k, seed=0):
    """One row per entry of ``degrees``, each with that many distinct
    random columns and standard-normal values."""
    rng = np.random.RandomState(seed)
    rows = np.repeat(np.arange(len(degrees)), degrees)
    cols = np.concatenate([rng.choice(k, d, replace=False) for d in degrees])
    vals = rng.randn(rows.size).astype(np.float32)
    return rows, cols, vals


def _power_law(m=200, k=160, seed=0):
    rng = np.random.RandomState(seed)
    deg = np.minimum((rng.pareto(1.3, m) * 3 + 1).astype(int), k)
    return _coo(deg, k, seed)


def _dense(rows, cols, vals, shape):
    a = np.zeros(shape, np.float64)
    np.add.at(a, (rows, cols), vals)
    return a


ALL_FRINGE = dict(alpha=1.0, enable_col_stage=False)
# (coo builder, shape, SpmmConfig kwargs, batch)
CASES = {
    "power_law": (_power_law, (200, 160), dict(impl="xla", **ALL_FRINGE),
                  None),
    # width 2^k + 1 pads to 2^(k+1): the most padding a row can take
    "worst_padding": (lambda: _coo([17, 3, 1, 9], 64), (4, 64),
                      dict(impl="xla", **ALL_FRINGE), None),
    "single_bucket": (lambda: _coo([4] * 12, 40), (12, 40),
                      dict(impl="xla", **ALL_FRINGE), None),
    "batched": (_power_law, (200, 160), dict(impl="xla", **ALL_FRINGE), 3),
    # the default split: dense rows on the matrix path, the rest bucketed
    "impl_xla": (lambda: _power_law(m=300, k=256, seed=1), (300, 256),
                 dict(impl="xla"), None),
    # a pallas plan whose VMEM budget resolves its fringe tier to xla
    "pallas_xla_tier": (_power_law, (200, 160),
                        dict(impl="pallas_interpret", bn=128,
                             fringe_vmem_budget=4_096, **ALL_FRINGE), None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_bucketed_fringe_matches_oracle(case):
    build, shape, kw, batch = CASES[case]
    rows, cols, vals = build()
    plan = spmm.prepare(rows, cols, vals, shape, spmm.SpmmConfig(**kw))
    assert plan.fringe_buckets, "the XLA-tier fringe was not bucketed"
    st = plan.stats_dict
    assert st["fringe_buckets"] == len(plan.fringe_buckets)
    assert st["fringe_slots"] == int(plan.fringe_rows.shape[0])
    # under 2x the nonzeros, but for the rows that fill each bucket to 8
    fill = sum(w * (n - 1) for n, w in plan.fringe_buckets)
    assert st["fringe_nnz"] <= st["fringe_slots"] < 2 * st["fringe_nnz"] + fill
    if case == "single_bucket":
        assert plan.fringe_buckets == ((16, 4),)
    if case == "worst_padding":
        assert plan.fringe_buckets == ((8, 1), (8, 4), (8, 16), (8, 32))

    rng = np.random.RandomState(7)
    n = 48
    b = rng.randn(*(((batch,) if batch else ()) + (shape[1], n)))
    b = jnp.asarray(b.astype(np.float32))
    # the fringe alone against the scatter oracle over the same stream
    nr = int(plan.fringe_row_ids.shape[0])
    for panel in (b if batch else b[None]):
        got = ops.fringe_spmm(
            plan.fringe_rows, plan.fringe_cols, plan.fringe_vals, panel,
            num_rows=nr, impl="xla", buckets=plan.fringe_buckets)
        want = ref.ref_gather_spmm(plan.fringe_rows, plan.fringe_cols,
                                   plan.fringe_vals, panel, nr)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # the whole product, fused (and vmapped when batched), against float64
    out = np.asarray(execute(plan, b))
    a = _dense(rows, cols, vals, shape)
    want = np.einsum("mk,...kn->...mn", a, np.asarray(b, np.float64))
    assert np.abs(out - want).max() / np.abs(want).max() < 1e-5


def test_bucket_layout_is_a_relayout_of_the_packed_stream():
    """Every nonzero lands in one slot of its row's bucket, padding slots
    carry 0.0 and a column their row already has (a row that only fills a
    bucket has id -1), and the renumbered row ids and inverse row map
    follow the new order."""
    rows, cols, vals = _power_law(m=120, k=90, seed=3)
    plan = spmm.prepare(rows, cols, vals, (120, 90),
                        spmm.SpmmConfig(impl="xla", **ALL_FRINGE))
    fr = np.asarray(plan.fringe_rows)
    fc = np.asarray(plan.fringe_cols)
    fv = np.asarray(plan.fringe_vals)
    ids = np.asarray(plan.fringe_row_ids)
    pos = plan.update_maps.fringe_pos
    # each nonzero at its slot, under its renumbered row
    np.testing.assert_array_equal(ids[fr[pos]], rows)
    np.testing.assert_array_equal(fc[pos], cols)
    np.testing.assert_array_equal(fv[pos], vals)
    pad = np.ones(fr.size, bool)
    pad[pos] = False
    assert not fv[pad].any()
    have = set(zip(fr[pos].tolist(), fc[pos].tolist()))
    real = ids[fr[pad]] >= 0
    assert set(zip(fr[pad][real].tolist(), fc[pad][real].tolist())) <= have
    # buckets: contiguous, widths ascending, 8-row aligned, width-major
    start, row0 = 0, 0
    for n_rows, width in plan.fringe_buckets:
        assert n_rows % plan_ir.BUCKET_ROW_ALIGN == 0
        blk = fr[start:start + n_rows * width].reshape(width, n_rows)
        np.testing.assert_array_equal(
            blk, np.broadcast_to(np.arange(row0, row0 + n_rows), blk.shape))
        start += n_rows * width
        row0 += n_rows
    assert start == fr.size and row0 == ids.size
    widths = [w for _, w in plan.fringe_buckets]
    assert widths == sorted(widths)
    packed = np.flatnonzero(ids >= 0)
    assert packed.size == np.unique(rows).size
    gsv = np.asarray(plan.gather_src_vector)
    np.testing.assert_array_equal(gsv[ids[packed]], packed)


def test_update_values_on_bucketed_plan_matches_reprepare():
    rows, cols, vals = _power_law(seed=5)
    shape = (200, 160)
    cfg = spmm.SpmmConfig(impl="xla", **ALL_FRINGE)
    plan = spmm.prepare(rows, cols, vals, shape, cfg)
    assert plan.fringe_buckets
    rng = np.random.RandomState(11)
    idx = rng.choice(rows.size, rows.size // 3, replace=False)
    new = rng.randn(idx.size).astype(np.float32)
    updated = update_values(plan, idx, new)
    vals2 = vals.copy()
    vals2[idx] = new
    fresh = spmm.prepare(rows, cols, vals2, shape, cfg)
    assert updated.signature() == plan.signature() == fresh.signature()
    np.testing.assert_array_equal(np.asarray(updated.fringe_vals),
                                  np.asarray(fresh.fringe_vals))
    b = jnp.asarray(rng.randn(shape[1], 16).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(execute(updated, b)),
                                  np.asarray(execute(fresh, b)))


def test_signature_carries_the_bucket_ladder():
    """24 rows of degree 1 and 8 of degree 4, or 8 of degree 1 and 24 of
    degree 2: plans alike in every static field, 32 rows and 56 slots,
    but for the ladder; two executors."""
    cfg = spmm.SpmmConfig(impl="xla", **ALL_FRINGE)
    plans = [spmm.prepare(*_coo(d, 16), (32, 16), cfg)
             for d in ([1] * 24 + [4] * 8, [1] * 8 + [2] * 24)]
    assert [p.fringe_buckets for p in plans] == [((24, 1), (8, 4)),
                                                 ((8, 1), (24, 2))]
    sigs = [p.signature() for p in plans]
    assert sigs[0][:-1] == sigs[1][:-1]
    assert sigs[0] != sigs[1]
    assert sigs[0][0] == plan_ir.PLAN_FORMAT_VERSION


def _formulations():
    snap = REGISTRY.snapshot().get("exec_fringe_formulation_total", {})
    return {s["labels"]["formulation"]: s["value"]
            for s in snap.get("series", ())}


def _run_xla_tier(rows, cols, vals, shape, b):
    plan = spmm.prepare(rows, cols, vals, shape,
                        spmm.SpmmConfig(impl="xla", **ALL_FRINGE))
    execute(plan, b).block_until_ready()


def _run_ksharded(rows, cols, vals, shape, b):
    plan = spmm.prepare(rows, cols, vals, shape, spmm.SpmmConfig(
        impl="pallas_interpret", bn=128, fringe_vmem_budget=60_000,
        **ALL_FRINGE))
    assert plan.fringe_tier == "ksharded" and not plan.fringe_buckets
    execute(plan, b).block_until_ready()


def _run_sharded(rows, cols, vals, shape, b):
    splan = spmm.prepare_sharded(
        rows, cols, vals, shape, make_spmm_mesh(1),
        spmm.SpmmConfig(impl="xla", **ALL_FRINGE), shard_axis="rows")
    execute_sharded(splan, b).block_until_ready()


@pytest.mark.parametrize("run,formulation", [
    (_run_xla_tier, "bucketed"),
    (_run_ksharded, "scatter"),
    (_run_sharded, "scatter"),
])
def test_fringe_formulation_counter(run, formulation):
    """One count per fused-body trace, by the formulation it traced; a
    width of its own per case forces a fresh trace."""
    width = {"bucketed": 40, "scatter": 56}[formulation] + (
        run is _run_sharded)
    rng = np.random.RandomState(2)
    rows = rng.randint(0, 60, 400)
    cols = rng.randint(0, 96, 400)
    vals = rng.randn(400).astype(np.float32)
    b = jnp.asarray(rng.randn(96, width).astype(np.float32))
    before = _formulations()
    run(rows, cols, vals, (60, 96), b)
    after = _formulations()
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert grew.get(formulation, 0) >= 1, grew
    other = "scatter" if formulation == "bucketed" else "bucketed"
    assert grew.get(other, 0) == 0, grew


def test_fringe_spmm_rejects_a_ladder_that_does_not_cover_the_stream():
    rows, cols, vals = _coo([4] * 8, 16)
    plan = spmm.prepare(rows, cols, vals, (8, 16),
                        spmm.SpmmConfig(impl="xla", **ALL_FRINGE))
    assert plan.fringe_buckets == ((8, 4),)
    b = jnp.ones((16, 8), jnp.float32)
    with pytest.raises(ValueError, match="does not cover the stream"):
        ops.fringe_spmm(plan.fringe_rows, plan.fringe_cols, plan.fringe_vals,
                        b, num_rows=8, impl="xla", buckets=((8, 2),))
