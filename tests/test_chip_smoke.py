"""chip_smoke.py's phases at a tiny size on the CPU, kernels interpreted.

The script itself refuses to run without a TPU; these tests drive the same
phase code (``one_chip_phases`` / ``four_chip_phase``) so a wrong path,
argument or check is found here rather than on the chip.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.data.graphs import GraphSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_interpret(smoke):
    from repro import obs
    from repro.exec.health import HEALTH

    # the phases check process-wide health and dispatch counters, which
    # earlier tests in this worker (deliberate degrades) leave behind
    HEALTH.reset()
    obs.reset_for_tests()
    # a 1 MiB fringe budget puts the K=256 stand-in on the resident tier
    # and the K=2048 one on the K-sharded tier, as the full-size ones are
    standins = [
        (GraphSpec("small-resident", 256, 256, 8.0, "power_law", 1.1, 2),
         "resident"),
        (GraphSpec("small-ksharded", 256, 2048, 8.0, "power_law", 1.3, 3),
         "ksharded"),
    ]
    nm = GraphSpec("small-nm", 256, 256, 8.0, "nm_pruned", 1.0, 13,
                   nm=(1, 32))
    lines = list(smoke.one_chip_phases(
        "pallas_interpret", 0, arxiv_nodes=600, standins=standins,
        nm_spec=nm, vmem_budget=1 << 20))
    text = "\n".join(lines)
    assert "fringe=gather_spmm " in text
    assert "fringe=gather_spmm_ksharded " in text
    assert "'nm_tile_spmm'" in text
    assert "[d]   service closed cleanly" in text
    for tag in "abcd":
        assert f"[{tag}] health: 0 failures, 0 fallbacks" in text


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr


FOUR_CHIP_SCRIPT = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {path!r})
smoke = importlib.util.module_from_spec(spec)
sys.modules["chip_smoke"] = smoke
spec.loader.exec_module(smoke)
for line in smoke.four_chip_phase("pallas_interpret", 0, arxiv_nodes=600):
    print(line)
"""


def test_four_chip_phase_on_forced_host_devices(forced_mesh_run, tmp_path):
    """The --four-chips path, rows-sharded over 4 forced CPU devices."""
    script = tmp_path / "four_chip.py"
    script.write_text(FOUR_CHIP_SCRIPT.format(
        path=os.path.join(ROOT, "chip_smoke.py")))
    out = forced_mesh_run(str(script), n_devices=4).stdout
    assert "plan leaves on 4 distinct devices, output on 4 distinct" in out
    assert "[4chip] health: 0 failures, 0 fallbacks" in out
