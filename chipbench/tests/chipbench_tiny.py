"""Shared set-up of the benchmark's CPU tests: a tiny benchmark root and
the fixtures that run it.

Imported first by every test file.  It is not a ``conftest.py``: the
repository's own tests import ``make_sparse`` from a module named
``conftest``, and a second conftest module would shadow it.
"""
import json
import os
import shutil
import sys
import time
from pathlib import Path

import pytest

# the benchmark's own tests run on the CPU, Pallas kernels in interpret mode
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


# each tiny cell stands for the real cell whose mix and limits it uses
TINY = {"tiny.spmm": "arxiv.spmm", "tiny.sddmm": "arxiv.sddmm",
        "tiny.serve": "arxiv.serve"}


def make_root(dst: Path) -> Path:
    """A benchmark root holding BENCHMARK.json and a copy of chipbench/,
    with a tiny configuration and one tiny cell per real mix added the way
    a later change adds them: new files and new entries only."""
    shutil.copytree(BENCH, dst / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "ogbn-arxiv.json").read_text())
    cfg["generator"].update(m=512, k=512, avg_degree=6.0)
    (dst / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "chipbench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    for tiny, real in TINY.items():
        w = dict(spec_workload(spec, real), name=tiny, config="tiny")
        spec["workloads"].append(w)
        shutil.copy(BENCH / "limits" / f"{real}.json",
                    dst / "chipbench" / "limits" / f"{tiny}.json")
        for m in spec["end_to_end"] + spec["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(tiny)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


def spec_workload(spec, name):
    return next(w for w in spec["workloads"] if w["name"] == name)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def run_tiny(tiny_root):
    """Run a tiny cell once on the CPU, Pallas kernels in interpret mode."""
    import harness

    def run(workload, seed=2**31 + 11, seconds=0.2, trace=False,
            root=tiny_root):
        return harness.run_cell(root, workload, seed, seconds, trace,
                                time.perf_counter(), impl="pallas_interpret")

    return run
