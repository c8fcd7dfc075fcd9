"""Telemetry-smoke gate: schema-validate an obs snapshot artifact.

``collect_fused_json --telemetry-out obs_snapshot.json`` runs the exec
panel with ``SpmmConfig.telemetry`` enabled and dumps the full
``repro.obs.snapshot()`` (plus the Prometheus text exposition).  This
gate fails CI (exit 1) when that artifact is malformed: missing
sections, counters absent from the registry snapshot, no dispatch's
``repro.lookup`` / ``repro.launch`` phase spans recorded, or a Prometheus
export that doesn't carry the registry's metrics.

    PYTHONPATH=src python -m benchmarks.check_telemetry obs_snapshot.json
"""
import argparse
import json
import sys

from repro.obs import parse_prometheus_text

#: Registry metrics the instrumented exec panel must have populated.
REQUIRED_METRICS = (
    "core_prepares_total",
    "exec_dispatches_total",
    "exec_traces_total",
    "exec_cache_events_total",
)

#: Phase spans every dispatch opens.
REQUIRED_SPANS = ("lookup", "launch")
SPAN_KEYS = {"count", "p50_us", "max_us"}


def _fail(msg: str) -> None:
    raise SystemExit(f"FAIL: {msg}")


def check_metrics(metrics: dict) -> None:
    for name in REQUIRED_METRICS:
        m = metrics.get(name)
        if m is None:
            _fail(f"metric {name!r} missing from the registry snapshot")
        if not m.get("series"):
            _fail(f"metric {name!r} has no series — the instrumented "
                  "panel recorded nothing")
    if float(sum(s["value"]
                 for s in metrics["exec_dispatches_total"]["series"])) <= 0:
        _fail("exec_dispatches_total is zero — no dispatches counted")


def check_spans(spans: dict) -> None:
    for name in REQUIRED_SPANS:
        s = spans.get(name)
        if s is None:
            _fail(f"phase span {name!r} missing — no dispatch recorded it")
        if SPAN_KEYS - set(s):
            _fail(f"phase span {name!r} missing {SPAN_KEYS - set(s)}")
        if s["count"] < 1 or not 0 <= s["p50_us"] <= s["max_us"]:
            _fail(f"phase span {name!r} has no consistent durations: {s}")


def check_prometheus(text: str) -> None:
    parsed = parse_prometheus_text(text)
    for name in REQUIRED_METRICS:
        if not any(n == name or n.startswith(name + "_") for n in parsed):
            _fail(f"Prometheus export missing registry metric {name}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("snapshot", help="obs snapshot JSON from "
                                    "collect_fused_json --telemetry-out")
    args = p.parse_args(argv)

    with open(args.snapshot) as f:
        snap = json.load(f)

    for key in ("metrics", "traces", "spans", "prometheus"):
        if key not in snap:
            _fail(f"snapshot missing top-level {key!r}")
    check_metrics(snap["metrics"])
    check_spans(snap["spans"])
    check_prometheus(snap["prometheus"])

    print(f"OK: telemetry snapshot valid — {len(snap['traces'])} trace(s), "
          f"{len(snap['metrics'])} registry metric(s), "
          f"{snap['spans']['launch']['count']} launch span(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
