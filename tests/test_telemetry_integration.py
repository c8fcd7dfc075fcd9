"""Telemetry end-to-end: zero-cost guarantee, phase spans, service tracing.

The contract under test: turning ``SpmmConfig.telemetry`` on is host-side
only — bit-identical numeric output, zero plan-signature changes, zero
extra retraces, zero extra device dispatches, and no call that
synchronizes — while the ``repro.obs`` snapshot gains per-request traces.
Every dispatch opens one ``repro.lookup`` and one ``repro.launch`` phase
span, telemetry or not.  Also pins the legacy counter surfaces
(``SpmmService.health()`` schema, the ``fused_trace_count`` /
``dispatch_count`` / ``prepare_call_count`` hooks) that now ride on the
shared registry, and regression-tests the health-table snapshot/reset
race the migration fixed.
"""
import dataclasses
import threading

import numpy as np

import repro.obs as obs
import repro.sparse as sp
from repro.core import spmm
from repro.exec.health import HealthTable
from repro.obs import SPAN_TIMES, TRACES
from repro.serve import SpmmService
from conftest import make_sparse


def _counter_clock(step=0.001):
    state = {"t": 0.0}
    lock = threading.Lock()

    def clock():
        with lock:
            state["t"] += step
            return state["t"]

    return clock


def _prepare_pair(rng, m=96, k=80, **overrides):
    """The same matrix prepared with telemetry off and on."""
    a, rows, cols, vals = make_sparse(rng, m, k, 0.08, n_dense_rows=3)
    cfg_off = spmm.SpmmConfig(impl="xla", **overrides)
    cfg_on = dataclasses.replace(cfg_off, telemetry=True)
    p_off = spmm.prepare(rows, cols, vals, a.shape, config=cfg_off)
    p_on = spmm.prepare(rows, cols, vals, a.shape, config=cfg_on)
    return a, p_off, p_on


# ---------------------------------------------------------------------------
# the zero-cost guarantee
# ---------------------------------------------------------------------------


def test_telemetry_is_signature_invisible(rng):
    _, p_off, p_on = _prepare_pair(rng)
    assert p_off.signature() == p_on.signature()


def test_telemetry_bit_identical_no_extra_traces_or_dispatches(rng):
    a, p_off, p_on = _prepare_pair(rng)
    b = rng.randn(a.shape[1], 16).astype(np.float32)
    # warm both paths: same signature -> one shared cached executor, so
    # the steady-state deltas below measure exactly one dispatch each
    spmm.execute(p_off, b)
    traces0 = spmm.fused_trace_count()
    disp0 = spmm.dispatch_count()
    out_off = np.asarray(spmm.execute(p_off, b))
    traces_off = spmm.fused_trace_count() - traces0
    disp_off = spmm.dispatch_count() - disp0

    traces0 = spmm.fused_trace_count()
    disp0 = spmm.dispatch_count()
    out_on = np.asarray(spmm.execute(p_on, b))
    traces_on = spmm.fused_trace_count() - traces0
    disp_on = spmm.dispatch_count() - disp0

    np.testing.assert_array_equal(out_off, out_on)  # bit-identical
    assert traces_off == traces_on == 0  # zero extra retraces
    assert disp_off == disp_on == 1  # zero extra device dispatches


def test_telemetry_off_records_nothing(rng):
    a, p_off, _ = _prepare_pair(rng)
    b = rng.randn(a.shape[1], 8).astype(np.float32)
    A = sp.from_plan(p_off)
    TRACES.reset()
    spmm.execute(p_off, b)
    sp.spmm(A, b)
    svc = SpmmService(p_off.config, max_batch=2)
    _, rows, cols, vals = make_sparse(rng, 40, 30, 0.1)
    svc.register("g", rows, cols, vals, (40, 30))
    ticket = svc.submit("g", rng.randn(30, 8).astype(np.float32))
    svc.flush()
    svc.fetch(ticket)
    assert len(TRACES) == 0


def test_telemetry_on_never_synchronizes(rng, monkeypatch):
    import jax

    def no_sync(*_a, **_k):
        raise AssertionError("telemetry synchronized a dispatch")

    _, p_off, p_on = _prepare_pair(rng)
    b = rng.randn(p_on.shape[1], 8).astype(np.float32)
    spmm.execute(p_on, b)  # compile outside the patch
    svc = SpmmService(p_on.config, max_batch=2)
    _, rows, cols, vals = make_sparse(rng, 40, 30, 0.1)
    svc.register("g", rows, cols, vals, (40, 30))
    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    TRACES.reset()
    spmm.execute(p_on, b)
    sp.spmm(sp.from_plan(p_on), b)
    ticket = svc.submit("g", rng.randn(30, 8).astype(np.float32))
    svc.flush()
    out = svc.fetch(ticket)
    monkeypatch.undo()
    assert np.asarray(out).shape == (40, 8)
    assert [t["name"] for t in TRACES.snapshot()] == ["facade:spmm",
                                                     "spmm:g"]


def test_dispatch_spans_one_each_per_call(rng):
    a, rows, cols, vals = make_sparse(rng, 64, 48, 0.1, n_dense_rows=2)
    A = sp.from_coo(rows, cols, vals, a.shape, impl="xla")
    b = rng.randn(48, 8).astype(np.float32)
    x = rng.randn(64, 8).astype(np.float32)
    y = rng.randn(8, 48).astype(np.float32)
    sp.spmm(A, b)
    sp.sddmm(A, x, y)
    SPAN_TIMES.reset()
    for _ in range(3):
        sp.spmm(A, b)
        sp.sddmm(A, x, y)
    for name in ("call", "lookup", "launch"):
        assert len(SPAN_TIMES.durations_ns(name)) == 6, name


# ---------------------------------------------------------------------------
# facade + service tracing
# ---------------------------------------------------------------------------


def test_facade_trace_spans(rng):
    a, rows, cols, vals = make_sparse(rng, 64, 48, 0.1)
    A = sp.from_coo(rows, cols, vals, a.shape, impl="xla", telemetry=True)
    b = rng.randn(48, 8).astype(np.float32)
    TRACES.reset()
    out = sp.spmm(A, b)
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)
    (tr,) = TRACES.snapshot()
    assert tr["name"] == "facade:spmm"
    assert tr["attrs"]["outcome"] == "ok"
    assert [s["name"] for s in tr["spans"]] == ["dispatch"]


def test_facade_without_telemetry_traces_nothing(rng):
    a, rows, cols, vals = make_sparse(rng, 64, 48, 0.1)
    A = sp.from_coo(rows, cols, vals, a.shape, impl="xla")
    TRACES.reset()
    sp.spmm(A, rng.randn(48, 8).astype(np.float32))
    assert len(TRACES) == 0


def test_service_span_structure_pinned(rng):
    """An injected deterministic clock pins the traced request's spans."""
    cfg = spmm.SpmmConfig(impl="xla", telemetry=True)
    svc = SpmmService(cfg, max_batch=4)
    svc._clock = _counter_clock()
    a, rows, cols, vals = make_sparse(rng, 90, 70, 0.08)
    svc.register("g", rows, cols, vals, a.shape)
    TRACES.reset()
    b = rng.randn(70, 8).astype(np.float32)
    ticket = svc.submit("g", b)
    svc.flush()
    out = np.asarray(svc.fetch(ticket))
    np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)

    (tr,) = TRACES.snapshot()
    assert tr["name"] == "spmm:g"
    assert tr["attrs"]["ticket"] == ticket
    assert tr["attrs"]["outcome"] == "ok"
    assert [s["name"] for s in tr["spans"]] == [
        "admit", "queue_wait", "batch_assembly", "dispatch", "fetch",
    ]
    # the counter clock ticks monotonically, so the spans chain in order
    for s in tr["spans"]:
        assert s["end_us"] >= s["start_us"]
    assert tr["end_us"] >= tr["start_us"]
    assert tr["spans"][2]["attrs"] == {"batch": 1, "bucket": 1}


def test_service_failure_outcomes_traced(rng):
    cfg = spmm.SpmmConfig(impl="xla", telemetry=True)
    svc = SpmmService(cfg, max_batch=2, max_queue=1,
                      admission_policy="shed-oldest")
    svc._clock = _counter_clock()
    a, rows, cols, vals = make_sparse(rng, 90, 70, 0.08)
    svc.register("g", rows, cols, vals, a.shape)
    TRACES.reset()
    b = rng.randn(70, 8).astype(np.float32)
    t_shed = svc.submit("g", b)
    svc.submit("g", b, timeout=1e-9)  # expires before the drain
    svc.flush()
    outcomes = {t["attrs"]["ticket"]: t["attrs"]["outcome"]
                for t in TRACES.snapshot()}
    assert outcomes[t_shed] == "shed"
    assert "expired" in outcomes.values()


def test_untraced_service_output_matches_traced(rng):
    a, rows, cols, vals = make_sparse(rng, 90, 70, 0.08)
    b = rng.randn(70, 8).astype(np.float32)
    outs = []
    for telemetry in (False, True):
        cfg = spmm.SpmmConfig(impl="xla", telemetry=telemetry)
        svc = SpmmService(cfg, max_batch=4)
        svc.register("g", rows, cols, vals, a.shape)
        t = svc.submit("g", b)
        svc.flush()
        outs.append(np.asarray(svc.fetch(t)))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# legacy counter surfaces on the shared registry
# ---------------------------------------------------------------------------

_LEGACY_STATS_KEYS = {
    "requests", "flushes", "dispatches", "padded_slots", "updates",
    "warm_starts", "compactions_scheduled", "compactions_applied",
    "compactions_stale", "compactions_failed", "admission_rejected",
    "admission_shed", "deadline_expired", "quarantines",
    "tunings_scheduled", "tunings_applied", "tunings_failed",
    # executor health table, folded in with the executor_ prefix
    "executor_signatures", "executor_demoted", "executor_retrying",
    "executor_failures", "executor_fallbacks", "executor_demotions",
    "executor_recoveries",
    "faults_fired",
    # autotuner counters, folded in with the tuner_ prefix
    "tuner_tune_calls", "tuner_table_hits", "tuner_cold_misses",
    "tuner_measured", "tuner_store_errors", "tuner_records",
}


def test_health_schema_byte_compatible(rng):
    """The registry migration must not change a single health() key."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=2)
    a, rows, cols, vals = make_sparse(rng, 90, 70, 0.08)
    svc.register("g", rows, cols, vals, a.shape)
    t = svc.submit("g", rng.randn(70, 8).astype(np.float32))
    svc.flush()
    svc.fetch(t)
    h = svc.health()
    assert set(h) == {"closed", "matrices", "stats"}
    assert set(h["matrices"]["g"]) == {
        "state", "queue_depth", "fold_failures", "fold_in_flight"}
    assert set(h["stats"]) == _LEGACY_STATS_KEYS
    assert h["stats"]["requests"] == 1
    assert h["stats"]["dispatches"] == 1
    assert h["stats"]["flushes"] == 1


def test_hook_wrappers_still_count(rng):
    a, rows, cols, vals = make_sparse(rng, 64, 48, 0.1)
    p0 = spmm.prepare_call_count()
    cfg = spmm.SpmmConfig(impl="xla", bn=32)  # distinct sig: fresh trace
    plan = spmm.prepare(rows, cols, vals, a.shape, config=cfg)
    assert spmm.prepare_call_count() == p0 + 1
    b = np.random.RandomState(1).randn(48, 8).astype(np.float32)
    t0, d0 = spmm.fused_trace_count(), spmm.dispatch_count()
    spmm.execute(plan, b)
    spmm.execute(plan, b)
    assert spmm.fused_trace_count() == t0 + 1  # traced once, reused once
    assert spmm.dispatch_count() == d0 + 2
    # the hooks are views over the shared registry
    reg = obs.REGISTRY
    assert reg.get("exec_traces_total").value(kind="fused") == (
        spmm.fused_trace_count())
    assert reg.get("exec_dispatches_total").total() == spmm.dispatch_count()
    assert reg.get("core_prepares_total").total() == (
        spmm.prepare_call_count())


# ---------------------------------------------------------------------------
# concurrency: the registry under threaded load + the snapshot/reset race
# ---------------------------------------------------------------------------


def test_registry_survives_concurrent_services(rng):
    """Several services submit/flush/fetch in parallel; every per-instance
    stat stays exact even though all series live in one registry."""
    a, rows, cols, vals = make_sparse(rng, 64, 48, 0.1)
    n_services, n_requests = 4, 6
    services = []
    for _ in range(n_services):
        svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
        svc.register("g", rows, cols, vals, a.shape)
        services.append(svc)
    b = rng.randn(48, 8).astype(np.float32)
    errors = []

    def drive(svc):
        try:
            for _ in range(n_requests):
                t = svc.submit("g", b)
                svc.flush()
                np.asarray(svc.fetch(t))
        except BaseException as err:  # surfaced after join
            errors.append(err)

    threads = [threading.Thread(target=drive, args=(s,)) for s in services]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for svc in services:
        assert svc.stats.requests == n_requests
        assert svc.stats.flushes == n_requests
        assert svc.stats.dispatches == n_requests


def test_health_table_snapshot_reset_race():
    """Regression: snapshot()/reset() used to read counters outside the
    table lock, so a concurrent record_* could be half-visible.

    With ``max_retries=0`` the first failure of a fresh signature bumps
    the failure *and* demotion counters inside one lock acquisition, and
    marks the signature demoted in the same critical section — so every
    atomic snapshot must observe ``failures == demotions == demoted``.
    ``reset()`` clears signatures and counters together, preserving the
    invariant; the pre-fix code could tear any of the three apart.
    """
    table = HealthTable(max_retries=0)
    n_threads, n_iter = 4, 200
    stop = threading.Event()
    torn = []

    def record(tid):
        for i in range(n_iter):
            table.record_failure((tid, i), RuntimeError("x"))

    def observe():
        while not stop.is_set():
            snap = table.snapshot()
            if not (snap["failures"] == snap["demotions"]
                    == snap["demoted"]):
                torn.append(snap)
            table.reset()

    workers = [threading.Thread(target=record, args=(t,))
               for t in range(n_threads)]
    watcher = threading.Thread(target=observe)
    watcher.start()
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    stop.set()
    watcher.join()
    assert not torn, f"torn snapshots: {torn[:3]}"
    table.reset()
    snap = table.snapshot()
    assert snap["failures"] == 0 and snap["demotions"] == 0
