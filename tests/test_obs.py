"""Unit tests for the repro.obs telemetry layer.

Covers the metrics registry (types, labels, cardinality caps, thread
safety, Prometheus round-trip), the trace store (deterministic clock,
ring bound) and the program's phase spans (host durations, early close,
ring bound) — all host-side, no device work.
"""
import threading

import pytest

import repro.obs as obs
from repro.obs import (
    SPAN_TIMES,
    MetricsRegistry,
    SpanTimes,
    TraceStore,
    format_sample,
    instance_label,
    parse_prometheus_text,
    span,
)
from repro.obs.metrics import OVERFLOW_LABEL


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_basics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests", labelnames=("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3
    assert c.value(kind="b") == 1
    assert c.value(kind="absent") == 0
    assert c.total() == 4
    assert c.series() == {("a",): 3.0, ("b",): 1.0}


def test_counter_monotone():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_counter_label_validation():
    reg = MetricsRegistry()
    c = reg.counter("y_total", labelnames=("kind",))
    with pytest.raises(ValueError):
        c.inc()  # missing label
    with pytest.raises(ValueError):
        c.inc(kind="a", extra="b")  # unknown label


def test_gauge():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value() == 6
    g.set(-3)
    assert g.value() == -3  # gauges may go negative


def test_histogram_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat_us", buckets=(10.0, 100.0))
    for v in (1, 10, 50, 1000):
        h.observe(v)
    snap = h.snapshot()["series"][0]["value"]
    # cumulative: <=10 holds {1, 10}, <=100 adds {50}, +Inf adds {1000}
    assert snap["buckets"] == {"10.0": 2, "100.0": 3, "+Inf": 4}
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(1061.0)


def test_idempotent_registration():
    reg = MetricsRegistry()
    a = reg.counter("same_total", labelnames=("k",))
    b = reg.counter("same_total", labelnames=("k",))
    assert a is b
    with pytest.raises(ValueError):
        reg.counter("same_total", labelnames=("other",))
    with pytest.raises(ValueError):
        reg.gauge("same_total", labelnames=("k",))


def test_cardinality_cap_collapses_to_overflow():
    reg = MetricsRegistry()
    c = reg.counter("capped_total", labelnames=("id",), max_series=3)
    for i in range(10):
        c.inc(id=str(i))
    # 3 real series at the cap; the rest collapsed into __other__
    series = c.series()
    assert len(series) == 4
    assert series[(OVERFLOW_LABEL,)] == 7.0
    assert reg.dropped_series() == {"capped_total": 7}
    assert reg.snapshot()["__dropped_series__"] == {"capped_total": 7}


def test_reset_values_keeps_registration():
    reg = MetricsRegistry()
    c = reg.counter("r_total")
    c.inc(5)
    reg.reset_values()
    assert c.total() == 0
    assert reg.get("r_total") is c  # object survives, only values reset
    c.inc()
    assert c.total() == 1


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("threaded_total", labelnames=("t",))
    h = reg.histogram("threaded_us", buckets=(10.0,))
    n_threads, n_iter = 8, 500

    def work(tid):
        for _ in range(n_iter):
            c.inc(t=str(tid % 2))
            h.observe(1.0)
            reg.snapshot()  # snapshots interleave with mutation

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.total() == n_threads * n_iter
    snap = h.snapshot()["series"][0]["value"]
    assert snap["count"] == n_threads * n_iter


def test_instance_label_unique():
    a, b = instance_label("svc"), instance_label("svc")
    assert a != b and a.startswith("svc") and b.startswith("svc")


# ---------------------------------------------------------------------------
# Prometheus text round-trip
# ---------------------------------------------------------------------------


def test_format_sample_escaping():
    line = format_sample("m", {"k": 'va"l\\ue\n'}, 1)
    parsed = parse_prometheus_text(line)
    assert parsed == {"m": {(("k", 'va"l\\ue\n'),): 1.0}}


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    c = reg.counter("rt_total", "help with\nnewline", labelnames=("kind",))
    c.inc(3, kind="a")
    c.inc(kind="b")
    g = reg.gauge("rt_depth")
    g.set(2.5)
    h = reg.histogram("rt_us", buckets=(10.0, 100.0))
    h.observe(5)
    h.observe(500)

    parsed = parse_prometheus_text(reg.to_prometheus())
    assert parsed["rt_total"] == {(("kind", "a"),): 3.0, (("kind", "b"),): 1.0}
    assert parsed["rt_depth"] == {(): 2.5}
    assert parsed["rt_us_bucket"] == {
        (("le", "10.0"),): 1.0, (("le", "100.0"),): 1.0, (("le", "+Inf"),): 2.0,
    }
    assert parsed["rt_us_sum"] == {(): 505.0}
    assert parsed["rt_us_count"] == {(): 2.0}


# ---------------------------------------------------------------------------
# trace store
# ---------------------------------------------------------------------------


def _counter_clock(step=0.001):
    state = {"t": 0.0}

    def clock():
        state["t"] += step
        return state["t"]

    return clock


def test_trace_deterministic_clock():
    store = TraceStore(capacity=8, clock=_counter_clock())
    tr = store.begin("req", ticket=7)
    store.add_span(tr, "admit", 100.0, 200.0, deadline=None)
    with store.span(tr, "dispatch"):
        pass
    store.end(tr)
    assert len(store) == 1
    snap = store.snapshot()[0]
    assert snap["name"] == "req"
    assert snap["attrs"]["ticket"] == 7
    assert [s["name"] for s in snap["spans"]] == ["admit", "dispatch"]
    assert snap["spans"][0]["duration_us"] == pytest.approx(100.0)
    # counter clock ticks 1000us per read: dispatch span is exactly one tick
    assert snap["spans"][1]["duration_us"] == pytest.approx(1000.0)


def test_trace_ring_bounded():
    store = TraceStore(capacity=4, clock=_counter_clock())
    for i in range(10):
        store.end(store.begin(f"t{i}"))
    assert len(store) == 4
    assert [t["name"] for t in store.snapshot()] == ["t6", "t7", "t8", "t9"]
    assert [t["name"] for t in store.snapshot(2)] == ["t8", "t9"]


# ---------------------------------------------------------------------------
# program phase spans
# ---------------------------------------------------------------------------


def test_span_records_host_duration():
    SPAN_TIMES.reset()
    with span("test_phase") as sp:
        assert not sp.closed
        sum(range(10_000))
    assert sp.closed
    (ns,) = SPAN_TIMES.durations_ns("test_phase")
    assert ns > 0
    assert SPAN_TIMES.durations_ns("never_opened") == []
    snap = obs.snapshot()["spans"]["test_phase"]
    assert snap["count"] == 1
    assert snap["p50_us"] == snap["max_us"] == pytest.approx(ns * 1e-3)


def test_span_close_ends_it_once():
    SPAN_TIMES.reset()
    with span("early") as sp:
        sp.close()
        sp.close()
        with span("after"):
            pass
    assert len(SPAN_TIMES.durations_ns("early")) == 1
    assert len(SPAN_TIMES.durations_ns("after")) == 1
    # an exception still ends the span, and is not swallowed
    with pytest.raises(ValueError):
        with span("raising"):
            raise ValueError("boom")
    assert len(SPAN_TIMES.durations_ns("raising")) == 1


def test_span_times_ring_bounded():
    times = SpanTimes(capacity=3)
    for ns in (5, 1, 4, 2, 3):
        times.record("x", ns)
    assert times.durations_ns("x") == [4, 2, 3]
    assert times.snapshot() == {"x": {"count": 3, "p50_us": 3e-3,
                                      "max_us": 4e-3}}
    times.reset()
    assert times.snapshot() == {}
