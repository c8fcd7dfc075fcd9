"""The seams of an off-thread fold, called directly: ``DynamicPlan``'s
snapshot / build / adopt triple, and ``SpmmService``'s polls that adopt
finished folds and tunes between drains."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spmm, tuner
from repro.dynamic import DynamicPlan, GraphDelta
from repro.exec.api import execute
from repro.serve import SpmmService

XLA = spmm.SpmmConfig(impl="xla")
SHAPE = (24, 20)


def _base(seed=0):
    rng = np.random.RandomState(seed)
    mask = rng.rand(*SHAPE) < 0.2
    rows, cols = np.nonzero(mask)
    return rows.astype(np.int64), cols.astype(np.int64), rng.randn(rows.size)


def _dense(rows, cols, vals):
    a = np.zeros(SHAPE)
    np.add.at(a, (rows, cols), vals)
    return a


def _mutated(seed=0):
    """A DynamicPlan with a live sidecar, and its logical dense matrix."""
    rows, cols, vals = _base(seed)
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, SHAPE, XLA),
                     auto_compact=False)
    dense = _dense(rows, cols, vals)
    zr, zc = np.nonzero(dense == 0)
    dp.update(GraphDelta.inserts(zr[:5], zc[:5], np.arange(1.0, 6.0)))
    dense[zr[:5], zc[:5]] = np.arange(1.0, 6.0)
    dp.update(GraphDelta.deletes(rows[:3], cols[:3]))
    dense[rows[:3], cols[:3]] = 0.0
    assert dp.delta_nnz > 0
    return dp, dense


def _product(plan_or_dp, b):
    if isinstance(plan_or_dp, DynamicPlan):
        return np.asarray(plan_or_dp.execute(b))
    return np.asarray(execute(plan_or_dp, b))


B = jnp.asarray(np.random.RandomState(9).randn(SHAPE[1], 8)
                .astype(np.float32))


def test_snapshot_for_compaction_is_the_logical_matrix():
    dp, dense = _mutated()
    version = dp.version
    snap_version, rows, cols, vals = dp.snapshot_for_compaction()
    assert snap_version == version and dp.version == version
    np.testing.assert_allclose(_dense(rows, cols, vals), dense,
                               rtol=1e-6, atol=1e-6)
    assert dp.delta_nnz > 0  # a snapshot folds nothing


def test_build_compacted_leaves_the_plan_serving():
    dp, dense = _mutated()
    before = (dp.plan, dp.version, dp.delta_nnz, dp.compactions)
    _, rows, cols, vals = dp.snapshot_for_compaction()
    folded = dp.build_compacted(rows, cols, vals)
    assert (dp.plan, dp.version, dp.delta_nnz, dp.compactions) == before
    want = dense @ np.asarray(B, np.float64)
    np.testing.assert_allclose(_product(folded, B), want,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(_product(dp, B), want, rtol=1e-4, atol=1e-4)


def test_adopt_compacted_refuses_a_stale_snapshot():
    dp, dense = _mutated()
    version, rows, cols, vals = dp.snapshot_for_compaction()
    folded = dp.build_compacted(rows, cols, vals)
    r0, c0 = int(rows[-1]), int(cols[-1])
    dp.update(GraphDelta.updates([r0], [c0], [7.25]))  # lands mid-fold
    dense[r0, c0] = 7.25
    plan_before = dp.plan
    assert dp.adopt_compacted(folded, expected_version=version) is False
    assert dp.plan is plan_before and dp.compactions == 0
    # a fresh snapshot adopts: sidecar gone, version and count bumped
    version, rows, cols, vals = dp.snapshot_for_compaction()
    assert dp.adopt_compacted(dp.build_compacted(rows, cols, vals),
                              expected_version=version) is True
    assert dp.delta_nnz == 0 and dp.compactions == 1
    assert dp.version == version + 1
    np.testing.assert_allclose(_product(dp, B),
                               dense @ np.asarray(B, np.float64),
                               rtol=1e-4, atol=1e-4)


# --- SpmmService polls ------------------------------------------------------


def _overload(svc, name):
    """Inserts enough zero positions to make the service schedule a fold."""
    rows, cols, vals = svc.plan(name).to_coo()
    dense = _dense(rows, cols, vals)
    zr, zc = np.nonzero(dense == 0)
    n = max(1, int(rows.size * 0.4))
    svc.update_matrix(name, GraphDelta.inserts(zr[:n], zc[:n],
                                               np.ones(n)))


def _poll_until(poll, deadline_s=60.0):
    """Call ``poll`` until it reports something; returns that report (0
    when the deadline passes first)."""
    end = time.time() + deadline_s
    while time.time() < end:
        got = poll()
        if got:
            return got
        time.sleep(0.01)
    return 0


def test_poll_compactions_swaps_a_finished_fold_once():
    with SpmmService(XLA, max_batch=2) as svc:
        svc.register("g", *_base(), SHAPE)
        assert svc.poll_compactions() == 0  # nothing in flight
        _overload(svc, "g")
        assert svc.stats.compactions_scheduled == 1
        assert _poll_until(svc.poll_compactions) == 1
        assert svc.poll_compactions() == 0
        assert svc.plan("g").compactions == 1
        assert svc.plan("g").delta_nnz == 0
        assert svc.stats.compactions_applied == 1
        assert svc.fold_errors() == {}


def test_fold_errors_surface_once_and_clear_on_read(monkeypatch):
    import repro.serve.spmm_service as svc_mod

    def failing_build(name, dplan, rows, cols, vals):
        raise RuntimeError(f"fold of {name} failed")

    monkeypatch.setattr(svc_mod, "_compact_build", failing_build)
    with SpmmService(XLA, max_batch=2) as svc:
        svc.register("g", *_base(), SHAPE)
        _overload(svc, "g")
        end = time.time() + 60
        while svc.stats.compactions_failed == 0 and time.time() < end:
            assert svc.poll_compactions() == 0  # a failure never raises
            time.sleep(0.01)
        errors = svc.fold_errors()
        assert list(errors) == ["g"]
        assert str(errors["g"]) == "fold of g failed"
        assert svc.fold_errors() == {}
        # the matrix keeps serving through its sidecar
        assert svc.plan("g").compactions == 0 and svc.plan("g").delta_nnz


@pytest.fixture
def _fake_tuner():
    tuner.reset_for_tests()
    tuner.set_timer(lambda fn: 1e-3)  # never runs fn: no compiles
    yield
    tuner.reset_for_tests()


def test_poll_tunings_adopts_a_finished_tune_once(_fake_tuner):
    cfg = spmm.SpmmConfig(impl="xla", autotune=True)
    with SpmmService(cfg, max_batch=2) as svc:
        assert svc.poll_tunings() == 0  # nothing in flight
        svc.register("g", *_base(), SHAPE)
        assert svc.stats.tunings_scheduled == 1
        assert _poll_until(svc.poll_tunings) == 1
        assert svc.poll_tunings() == 0
        assert svc.stats.tunings_applied == 1
        assert svc.stats.tunings_failed == 0
        assert svc.health()["stats"]["tuner_records"] == 1
