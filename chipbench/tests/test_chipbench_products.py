"""The ogbn-products configuration and the host prepare readers."""
import re

import pytest

import chipbench_tiny  # noqa: F401  (CPU platform, paths)
from chipbench_tiny import BENCH, run_tiny, tiny_root  # noqa: F401
import graphs
import harness

PHASES = ("partition_s", "pack_s")


def test_prepare_phase_readers_report_on_a_traced_closed_run(run_tiny):
    r = run_tiny("tiny.spmm", trace=True)
    assert r["correct"] is True
    got = r["metrics"]
    assert set(PHASES) <= set(got)
    assert all(got[m]["unit"] == "s" and got[m]["value"] > 0
               for m in PHASES)
    # phases of the prepare the benchmark's own span encloses
    assert (got["partition_s"]["value"] + got["pack_s"]["value"]
            <= got["prepare_s"]["value"])


@pytest.mark.parametrize("metric", PHASES)
def test_prepare_phase_readers_read_nothing_without_the_span(
        metric, monkeypatch):
    """A program that never opened the span (the parent's) reads None."""
    import repro.obs
    from repro.obs import SpanTimes

    monkeypatch.setattr(repro.obs, "SPAN_TIMES", SpanTimes())
    read = harness.Bench(BENCH.parent).metric_reader(metric)
    assert read({"kind": "closed", "calls": 3}) is None


def _count(text):
    return int(text.replace(",", ""))


def test_products_configuration_loads_at_its_published_size():
    """Loads through ``Bench`` with a generator kind ``graphs`` knows, at
    the sizes its ``source`` states; the 62M-nonzero pattern is not
    generated here."""
    bench = harness.Bench(BENCH.parent)
    cfg = bench.config("ogbn-products")
    entry = next(c for c in bench.spec["configs"]
                 if c["name"] == "ogbn-products")
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == ["adjacency"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    nodes, edges, width = re.search(
        r"([\d,]+) nodes, ([\d,]+) edges, (\d+)-d", cfg["source"]).groups()
    pub = cfg["published"]
    assert (pub["nodes"], pub["edges"], pub["feature_width"]) == (
        _count(nodes), _count(edges), int(width))
    gen = cfg["generator"]
    assert gen["kind"] == "power_law"
    assert gen["m"] == gen["k"] == pub["nodes"]
    assert cfg["width"] == pub["feature_width"] == 100
    # a kind the copied generators make (at a tiny size)
    rows, cols = graphs.structure(dict(gen, m=64, k=64))
    assert rows.size > 0 and cols.max() < 64
    wl = bench.workload("products.spmm")
    assert (wl["config"], wl["chips"]) == ("ogbn-products", 1)
    assert bench.mix(wl["traffic"])["op"] == "spmm"
    assert set(bench.limits("products.spmm")) == {"rel_err", "rms_err"}
