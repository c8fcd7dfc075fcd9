"""The harness end to end at a tiny size on the CPU (interpret mode)."""
import json
import os
import subprocess
import sys

import pytest

from chipbench_tiny import BENCH, TINY, run_tiny, tiny_root  # noqa: F401


def test_run_refuses_without_a_tpu():
    """No TPU: exit code 2, nothing on standard output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "arxiv.spmm",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("workload", sorted(TINY))
def test_each_mix_runs_and_checks(run_tiny, workload):
    r = run_tiny(workload)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "call_ms" if workload != "tiny.serve"
                                 else "req_p95_ms"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    # the numbers compared come last, each beside its limit
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"rel_err", "rms_err"}
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    json.dumps(r)


@pytest.mark.parametrize("workload", ["tiny.spmm", "tiny.serve"])
def test_traced_run_reports_per_layer_metrics(run_tiny, workload):
    """The CPU has no device plane and no roofline: the host and counter
    metrics are there, the device ones are left out, never 0."""
    r = run_tiny(workload, trace=True)
    assert r["correct"] is True
    got = set(r["metrics"])
    if workload == "tiny.spmm":
        assert {"prepare_s", "dispatch_us.call", "retraces.call"} <= got
        assert r["metrics"]["retraces.call"]["value"] == 0
    else:
        assert {"prepare_s", "queue_wait_ms.serve", "batch_fill.serve"} <= got
        assert 0 < r["metrics"]["batch_fill.serve"]["value"] <= 100
    assert not any(k.endswith("roofline") for k in got)
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_added_files_are_found_by_name(tmp_path, run_tiny):
    """A new configuration, mix, metric and cell, added as new files and
    new entries in BENCHMARK.json, run with no edit to an existing file."""
    from chipbench_tiny import make_root

    root = make_root(tmp_path)
    cb = root / "chipbench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((cb / "configs" / "nd12k.json").read_text())
    cfg["generator"].update(m=384, k=384, band=9)
    (cb / "configs" / "band-tiny.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "closed-spmm-2p.json").write_text(json.dumps(
        {"kind": "closed", "op": "spmm", "panels": 2, "samples": 2}))
    (cb / "metrics" / "panels_seen.call.py").write_text(
        "def read(ctx):\n    return ctx['calls']\n")
    (cb / "limits" / "band.p2.json").write_text(
        (cb / "limits" / "nd12k.spmm.json").read_text())
    spec["configs"].append({"name": "band-tiny", "source": "test",
                            "file": "chipbench/configs/band-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "band.p2", "config": "band-tiny",
                              "traffic": "closed-spmm-2p", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][1]["workloads"].append("band.p2")
    spec["per_layer"].append({"name": "panels_seen.call", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "dispatch", "moves": "call_ms",
                              "workloads": ["band.p2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    r = run_tiny("band.p2", root=root)
    assert r["correct"] is True and set(r["metrics"]) == {"setup_s", "call_ms"}
    assert r["info"]["plan"]["nnz"] > 0
    r = run_tiny("band.p2", root=root, trace=True)
    assert r["metrics"]["panels_seen.call"]["value"] == r["attempted"]


def test_serving_loop_hands_over_one_batch_at_a_time(run_tiny, monkeypatch):
    """However far the loop falls behind, the service never holds more
    than one batch: a backlog flushed at once would hold its panels and
    outputs on the device together.  Every flush stands still for 0.2 s,
    as a host does now and then, so that requests pile up."""
    import time

    from repro.serve import SpmmService

    queued = []
    flush = SpmmService.flush

    def counting_flush(self, *args, **kwargs):
        queued.append(self.pending())
        time.sleep(0.2)
        return flush(self, *args, **kwargs)

    monkeypatch.setattr(SpmmService, "flush", counting_flush)
    r = run_tiny("tiny.serve", seconds=0.5)
    assert r["correct"] is True
    assert queued and max(queued) <= 4
