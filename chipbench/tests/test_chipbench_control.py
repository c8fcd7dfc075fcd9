"""``correct`` comes out false for the control and for planted faults.

The control is the plain reference computed in bfloat16, one step below
the configurations' float32, answering in the program's place.  The faults
break the timed path underneath a run that is otherwise whole: an answer
altered where it is produced, and half of a served batch left out.  Each
is judged by the limits of the real cell its tiny cell stands for."""
import json

import pytest

from chipbench_tiny import TINY, run_tiny, tiny_root  # noqa: F401
import control  # noqa: E402  (after the paths are set)


@pytest.mark.parametrize("workload", sorted(TINY))
def test_bf16_control_is_not_correct(run_tiny, tiny_root, workload):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    w = next(x for x in spec["workloads"] if x["name"] == workload)
    mix = json.loads((tiny_root / "chipbench" / "traffic" /
                      f"{w['traffic']}.json").read_text())
    with control.in_program_place(mix["op"], mix["kind"]):
        r = run_tiny(workload)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def _zero_largest_row(out):
    """An answer altered where it is produced: the row that holds the
    largest value comes back as zeros."""
    import jax.numpy as jnp

    flat = out.reshape(-1, out.shape[-1]) if out.ndim > 1 else out[None]
    i = jnp.argmax(jnp.max(jnp.abs(flat), axis=1))
    return flat.at[i].set(0.0).reshape(out.shape)


@pytest.mark.parametrize("workload,entry", [
    ("tiny.spmm", "execute"), ("tiny.sddmm", "execute_sddmm")])
def test_altered_answer_is_not_correct(run_tiny, workload, entry):
    import repro.exec.api as api

    with control.patched(api, entry, lambda orig: (
            lambda *a: _zero_largest_row(orig(*a)))):
        r = run_tiny(workload)
    assert r["correct"] is False


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
def test_served_faults_are_not_correct(run_tiny, tiny_root, tmp_path, fault):
    """Served: at this rate most batches fill, every request is compared,
    and either each batch's answers are altered, or the second half of
    each batch is left out."""
    import jax.numpy as jnp
    from repro.serve.spmm_service import SpmmService

    from chipbench_tiny import make_root

    root = make_root(tmp_path)
    mix = root / "chipbench" / "traffic" / "open-serve.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   rate_per_s=5000.0, samples=10**6)))

    def broken(orig):
        def execute(self, name, plan, stacked):
            out = orig(self, name, plan, stacked)
            if fault == "altered":
                return jnp.stack([_zero_largest_row(o) for o in out])
            half = (out.shape[0] + 1) // 2
            return out.at[half:].set(0.0) if out.shape[0] > 1 else out
        return execute

    with control.patched(SpmmService, "_execute", broken):
        r = run_tiny("tiny.serve", root=root, seconds=0.3)
    assert r["correct"] is False
