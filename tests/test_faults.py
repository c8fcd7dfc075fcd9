"""Fault-injection harness + degrade-to-XLA dispatch + registry seams.

Covers the robustness acceptance criteria that live below the serving
layer: harness determinism, per-signature health gating (bounded retry,
sticky demotion, bit-identical XLA fallback), and registry read/write
faults resolving to generational fallback or clean RegistryErrors.

Plans get unique shapes per test: the executor cache and jit trace caches
are process-wide, and the ``executor_build`` / ``pallas_lowering`` seams
fire per *build* / per *trace* — a shape reused from another test would
hit those caches and never reach the seam.
"""
import os
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_ir, spmm
from repro.dynamic import DynamicPlan, PlanRegistry
from repro.errors import (
    DispatchError, FaultInjected, KernelLoweringError, RegistryError,
    ReproError,
)
from repro.exec.health import HEALTH
from repro.exec.pipeline import build_executor
from repro.robust.faults import HARNESS, SEAMS, armed, chaos_schedule
from conftest import make_sparse

CFG_KW = dict(bm=32, bk=16, bn=32)


@pytest.fixture(autouse=True)
def _clean_harness():
    HARNESS.reset()
    HEALTH.reset()
    yield
    HARNESS.reset()
    HEALTH.reset()


def _plan(rng, m, k, impl="xla", **cfg_kw):
    a, rows, cols, vals = make_sparse(rng, m, k, 0.08, n_dense_rows=2)
    cfg = spmm.SpmmConfig(impl=impl, **{**CFG_KW, **cfg_kw})
    return a, spmm.prepare(rows, cols, vals, a.shape, cfg)


def _xla_tier_ref(plan, b):
    """What the XLA fallback tier computes for this exact plan's leaves."""
    fsig = plan_ir.xla_fallback_sig(plan.signature())
    return build_executor(fsig, batch=None)(*plan_ir.plan_leaves(plan), b)


def _is_accel_sig(s):
    return isinstance(s, tuple) and plan_ir.sig_impl(s) not in (None, "xla")


# ---------------------------------------------------------------------------
# harness mechanics
# ---------------------------------------------------------------------------
def test_unknown_seam_rejected():
    with pytest.raises(ValueError, match="unknown fault seam"):
        HARNESS.arm("not_a_seam")
    with pytest.raises(ValueError, match="unknown fault seam"):
        HARNESS.calls("not_a_seam")
    assert "executor_build" in SEAMS and len(SEAMS) == 6


def test_disarmed_fire_only_counts():
    before = HARNESS.calls("dispatch")
    HARNESS.fire("dispatch", context="m")
    assert HARNESS.calls("dispatch") == before + 1
    assert HARNESS.fired("dispatch") == 0


def test_fail_once_fail_n_and_after_policies():
    HARNESS.arm("dispatch", times=2, after=1)
    HARNESS.fire("dispatch")  # after=1: first matching call passes
    for _ in range(2):
        with pytest.raises(FaultInjected):
            HARNESS.fire("dispatch")
    HARNESS.fire("dispatch")  # budget (times=2) exhausted
    assert HARNESS.fired("dispatch") == 2

    HARNESS.arm("dispatch", times=None)  # fail forever
    for _ in range(3):
        with pytest.raises(FaultInjected):
            HARNESS.fire("dispatch")


def test_match_predicate_filters_context_without_consuming_budget():
    HARNESS.arm("fold_build", times=1, match=lambda ctx: ctx == "bad")
    HARNESS.fire("fold_build", context="good")  # filtered: no fire
    HARNESS.fire("fold_build", context="good")
    with pytest.raises(FaultInjected):
        HARNESS.fire("fold_build", context="bad")
    HARNESS.fire("fold_build", context="bad")  # fail-once budget spent


def test_custom_exception_and_message():
    HARNESS.arm("registry_write", exc=OSError, message="disk full")
    with pytest.raises(OSError, match="disk full"):
        HARNESS.fire("registry_write")


def test_armed_context_manager_disarms_on_exit():
    with armed("dispatch"):
        assert "dispatch" in HARNESS.armed_seams()
        with pytest.raises(FaultInjected):
            HARNESS.fire("dispatch")
    assert "dispatch" not in HARNESS.armed_seams()
    HARNESS.fire("dispatch")  # disarmed again


def test_chaos_schedule_is_deterministic():
    s1 = chaos_schedule(1234)
    HARNESS.reset()
    s2 = chaos_schedule(1234)
    assert s1 == s2 and set(s1) == set(SEAMS)
    assert set(HARNESS.armed_seams()) == set(SEAMS)  # all armed fail-once
    counters = HARNESS.counters()
    assert set(counters) == {"calls", "fired"}


# ---------------------------------------------------------------------------
# degrade-to-XLA dispatch (acceptance: pallas failure -> bit-identical XLA)
# ---------------------------------------------------------------------------
def test_pallas_build_failure_degrades_bit_identically(rng):
    a, plan = _plan(rng, 72, 56, impl="pallas_interpret")
    b = jnp.asarray(rng.randn(56, 8).astype(np.float32))
    ref = _xla_tier_ref(plan, b)  # the tier the fallback must hit exactly
    np.testing.assert_allclose(  # and the tier itself is not vacuous
        np.asarray(ref, np.float64), a.astype(np.float64) @ np.asarray(b),
        rtol=1e-4, atol=1e-4)

    sig = plan.signature()
    with armed("executor_build", times=None, match=_is_accel_sig):
        out = spmm.execute(plan, b)  # serving never raises
        assert bool(jnp.array_equal(out, ref))  # bit-identical fallback
        assert HEALTH.state(sig) == "retrying"
        for _ in range(40):  # exhaust the bounded retry schedule
            assert bool(jnp.array_equal(spmm.execute(plan, b), ref))
    assert HEALTH.state(sig) == "demoted"  # sticky even once disarmed
    assert bool(jnp.array_equal(spmm.execute(plan, b), ref))
    snap = HEALTH.snapshot()
    assert snap["demotions"] == 1 and snap["fallbacks"] >= 41


def test_pallas_lowering_failure_degrades(rng):
    _, plan = _plan(rng, 68, 52, impl="pallas_interpret")
    b = jnp.asarray(rng.randn(52, 8).astype(np.float32))
    ref = _xla_tier_ref(plan, b)
    with armed("pallas_lowering", times=None):
        out = spmm.execute(plan, b)
    assert bool(jnp.array_equal(out, ref))
    assert HEALTH.is_degraded(plan.signature())


def test_failed_launch_and_fallback_each_time_their_spans(rng):
    """A failed accelerated launch and its XLA fallback record two
    ``repro.lookup`` and two ``repro.launch`` spans; nothing leaks into the
    next call's count."""
    from repro.obs import SPAN_TIMES

    _, plan = _plan(rng, 71, 45, impl="pallas_interpret")
    b = jnp.asarray(rng.randn(45, 8).astype(np.float32))
    SPAN_TIMES.reset()
    with armed("pallas_lowering", times=1):
        spmm.execute(plan, b)
    assert len(SPAN_TIMES.durations_ns("lookup")) == 2
    assert len(SPAN_TIMES.durations_ns("launch")) == 2


def test_transient_failure_recovers_inside_retry_window(rng):
    _, plan = _plan(rng, 60, 44, impl="pallas_interpret")
    b = jnp.asarray(np.random.RandomState(7).randn(44, 8).astype(np.float32))
    sig = plan.signature()
    with armed("pallas_lowering", times=1):  # single transient failure
        spmm.execute(plan, b)  # degrades this dispatch
        assert HEALTH.state(sig) == "retrying"
        # drive dispatches until the backoff window re-attempts the accel
        # tier; the seam is spent, so the retry succeeds and heals the sig
        for _ in range(6):
            spmm.execute(plan, b)
    assert HEALTH.state(sig) == "healthy"
    assert HEALTH.snapshot()["recoveries"] == 1


def test_degrade_disabled_surfaces_kernel_lowering_error(rng):
    _, plan = _plan(rng, 76, 40, impl="pallas_interpret",
                    degrade_to_xla=False)
    b = jnp.asarray(np.random.RandomState(3).randn(40, 8).astype(np.float32))
    with armed("pallas_lowering", times=None):
        with pytest.raises(KernelLoweringError, match="degrade_to_xla"):
            spmm.execute(plan, b)
    # KernelLoweringError is catchable as the taxonomy root
    assert issubclass(KernelLoweringError, ReproError)


def test_xla_plan_build_failure_propagates_fault(rng):
    """XLA-impl plans have no tier below them: a build fault propagates
    (typed), it cannot silently degrade to itself."""
    _, plan = _plan(rng, 84, 36, impl="xla")
    b = jnp.asarray(np.random.RandomState(5).randn(36, 8).astype(np.float32))
    with armed("executor_build", times=1):
        with pytest.raises(FaultInjected):
            spmm.execute(plan, b)
    out = spmm.execute(plan, b)  # failed builds are not cached: retry works
    assert out.shape == (84, 8)


def test_dispatch_error_when_every_tier_fails(rng):
    _, plan = _plan(rng, 92, 48, impl="pallas_interpret")
    b = jnp.asarray(np.random.RandomState(9).randn(48, 8).astype(np.float32))
    with armed("executor_build", times=None):  # no match: xla fails too
        with pytest.raises(DispatchError, match="every tier"):
            spmm.execute(plan, b)


# ---------------------------------------------------------------------------
# registry seams: write faults stay clean, read faults fall back a generation
# ---------------------------------------------------------------------------
def _dplan(rng, m=64, k=48):
    a, rows, cols, vals = make_sparse(rng, m, k, 0.08, n_dense_rows=2)
    cfg = spmm.SpmmConfig(impl="xla", **CFG_KW)
    return a, DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, cfg))


def test_registry_write_fault_is_a_clean_registry_error(rng, tmp_path):
    a, dp = _dplan(rng)
    reg = PlanRegistry(str(tmp_path))
    reg.save("g", dp)
    with armed("registry_write"):
        with pytest.raises(RegistryError, match="persist"):
            reg.save("g", dp)
    # the previous generation still loads (atomic layout untouched)
    restored = reg.load("g")
    assert restored.plan.shape == a.shape
    assert reg.generation_fallbacks == 0


def test_registry_read_fault_falls_back_one_generation(rng, tmp_path):
    _, dp = _dplan(rng)
    reg = PlanRegistry(str(tmp_path), keep=2)
    reg.save("g", dp)
    reg.save("g", dp)  # two retained generations
    with armed("registry_read", times=1):  # newest read dies
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            restored = reg.load("g")
    assert restored is not None
    assert reg.generation_fallbacks == 1
    assert any("serving step_" in str(w.message) for w in caught)


def test_registry_read_fault_on_all_generations_aggregates(rng, tmp_path):
    _, dp = _dplan(rng)
    reg = PlanRegistry(str(tmp_path), keep=2)
    reg.save("g", dp)
    reg.save("g", dp)
    with armed("registry_read", times=None):
        with pytest.raises(RegistryError, match="every retained generation"):
            reg.load("g")


def test_chaos_seeded_schedule_smoke(rng):
    """The CI chaos leg's schedule builder composes with real dispatches:
    whatever fires surfaces as a typed ReproError, never a bare crash."""
    seed = int(os.environ.get("REPRO_CHAOS_SEED", "0")) % (2 ** 31)
    schedule = chaos_schedule(seed, max_offset=3)
    assert set(schedule) == set(SEAMS)
    _, plan = _plan(rng, 44, 28, impl="xla")
    b = jnp.asarray(np.random.RandomState(2).randn(28, 4).astype(np.float32))
    for _ in range(6):
        try:
            spmm.execute(plan, b)
        except ReproError:
            pass  # injected faults must surface typed
