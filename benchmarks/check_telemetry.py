"""Telemetry-smoke gate: schema-validate an obs snapshot artifact.

``collect_fused_json --telemetry-out obs_snapshot.json`` runs the exec
panel with ``SpmmConfig.telemetry`` enabled and dumps the full
``repro.obs.snapshot()`` (plus the Prometheus text exposition).  This
gate fails CI (exit 1) when that artifact is malformed: missing
sections, roofline rows without both engine paths, attribution that
doesn't add up, counters absent from the registry snapshot, or a
Prometheus export that doesn't round-trip against the roofline rows.

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
    "obs_profiled_dispatches_total",
    "obs_dispatch_us",
)

ROW_KEYS = {"op", "tier", "sig", "calls", "measured_us", "paths", "peaks",
            "mean_us", "utilization"}
PATH_KEYS = {"flops", "bytes", "bound_us", "share", "attributed_us", "bound"}
TOTAL_KEYS = {"flops", "bytes", "bound_us", "share", "attributed_us"}


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


def check_roofline(attr: dict) -> None:
    for key in ("rows", "matrix_path", "fringe_path", "measured_us_total",
                "utilization", "skipped_traced"):
        if key not in attr:
            _fail(f"roofline attribution missing {key!r}")
    rows = attr["rows"]
    if not rows:
        _fail("roofline attribution has no rows — profiler saw no "
              "telemetry-enabled dispatches")
    attributed = 0.0
    priced_measured = 0.0
    for row in rows:
        missing = ROW_KEYS - set(row)
        if missing:
            _fail(f"roofline row {row.get('sig')!r} missing {missing}")
        if set(row["paths"]) != {"matrix", "fringe"}:
            _fail(f"row {row['sig']!r} paths are {set(row['paths'])}, "
                  "want {'matrix', 'fringe'}")
        priced = bool(row["peaks"])
        for p, acc in row["paths"].items():
            if PATH_KEYS - set(acc):
                _fail(f"row {row['sig']!r} path {p!r} missing "
                      f"{PATH_KEYS - set(acc)}")
            if not priced:
                # a device kind without peaks gets no share at all
                if acc["share"] is not None or row["utilization"] is not None:
                    _fail(f"row {row['sig']!r} has no device peaks but "
                          "reports a roofline share")
                continue
            attributed += acc["attributed_us"]
        if row["calls"] < 1 or row["measured_us"] <= 0:
            _fail(f"row {row['sig']!r} has no measured work")
        if priced:
            priced_measured += row["measured_us"]
    for p in ("matrix_path", "fringe_path"):
        if TOTAL_KEYS - set(attr[p]):
            _fail(f"{p} totals missing {TOTAL_KEYS - set(attr[p])}")
    total = attr["measured_us_total"]
    if total <= 0:
        _fail("measured_us_total is zero")
    if abs(attributed - priced_measured) > 1e-6 * max(priced_measured, 1.0):
        _fail(f"attributed time {attributed:.3f}us does not add up to "
              f"measured priced total {priced_measured:.3f}us")


def check_prometheus(text: str, attr: dict) -> None:
    parsed = parse_prometheus_text(text)
    required = ["repro_roofline_calls", "repro_roofline_measured_us"]
    if any(row["peaks"] for row in attr["rows"]):
        required.append("repro_roofline_bound_us")  # priced rows only
    for name in required:
        if name not in parsed:
            _fail(f"Prometheus export missing {name}")
    for row in attr["rows"]:
        key = tuple(sorted((("op", row["op"]), ("tier", row["tier"]),
                            ("sig", row["sig"]))))
        calls = parsed["repro_roofline_calls"].get(key)
        if calls != float(row["calls"]):
            _fail(f"Prometheus round-trip mismatch for {key}: "
                  f"calls {calls} != {row['calls']}")
    for name in REQUIRED_METRICS:
        if not any(n == name or n.startswith(name + "_") for n in parsed):
            _fail(f"Prometheus export missing registry metric {name}")


def _pct(x) -> str:
    return "not priced (device kind has no peaks)" if x is None \
        else f"{100.0 * x:.1f}%"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("snapshot", help="obs snapshot JSON from "
                                    "collect_fused_json --telemetry-out")
    args = p.parse_args(argv)

    with open(args.snapshot) as f:
        snap = json.load(f)

    for key in ("metrics", "traces", "roofline", "prometheus"):
        if key not in snap:
            _fail(f"snapshot missing top-level {key!r}")
    check_metrics(snap["metrics"])
    check_roofline(snap["roofline"])
    check_prometheus(snap["prometheus"], snap["roofline"])

    rows = snap["roofline"]["rows"]
    print(f"OK: telemetry snapshot valid — {len(rows)} roofline row(s), "
          f"{len(snap['traces'])} trace(s), "
          f"{len(snap['metrics'])} registry metric(s), "
          f"utilization {_pct(snap['roofline']['utilization'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
