"""ogbn-products' character at a size a test run holds: a power-law
pattern at skew 2.0 and average degree 26 (m = k = 4,096), an operand of
width 100 (not a multiple of ``bn``), through ``repro.sparse``.

On the chip nearly every nonzero of the configuration lands on the XLA
fringe with buckets far larger than one gather block, so the cases force
the same branches here: ``fringe_vmem_budget=0`` sends the fringe to the
XLA tier, a small ``GATHER_BLOCK_BYTES`` gives the large buckets one width
position per gather, and a small ``ROLL_BUCKET_BYTES`` runs them in the
rolled loop.  Every answer is compared with a float64 ``scipy`` product.
"""
import numpy as np
import pytest
import scipy.sparse

import repro.sparse as sp
from repro.core import spmm
from repro.data.graphs import GraphSpec, generate
from repro.kernels import ref
from repro.obs import SPAN_TIMES

M = K = 4096
WIDTH = 100
# float32 products summed in float32 over rows of at most a few hundred
# nonzeros (the XLA fringe), and the matrix path's tiles on the CPU at
# float32: a few float32 roundings of the largest answer, far below the
# 1e-2 that a bfloat16 rounding of the operands would give
REL_TOL = 1e-5


@pytest.fixture(scope="module")
def graph():
    rows, cols, vals = generate(
        GraphSpec("products-4k", M, K, 26.0, "power_law", 2.0, 0))
    b = np.random.default_rng(7).standard_normal((K, WIDTH), np.float32)
    want = scipy.sparse.csr_matrix(
        (vals.astype(np.float64), (rows, cols)), shape=(M, K)) @ b.astype(
            np.float64)
    return rows, cols, vals, b, want


def _config(**kw):
    return spmm.SpmmConfig(impl="pallas_interpret", degrade_to_xla=False,
                           bn=128, seed=0, **kw)


def _rel_err(got, want):
    return np.abs(np.asarray(got, np.float64) - want).max() / np.abs(
        want).max()


@pytest.mark.parametrize("gather_bytes, roll_bytes", [
    # the defaults: every bucket of this size gathers unrolled
    (ref.GATHER_BLOCK_BYTES, ref.ROLL_BUCKET_BYTES),
    # one width position per gather (step 1), still unrolled
    (1, ref.ROLL_BUCKET_BYTES),
    # one position per gather in a rolled loop, as the large buckets of
    # ogbn-products run on the chip
    (1, 1),
], ids=["default", "step1-unrolled", "step1-rolled"])
def test_xla_fringe_at_width_100_matches_float64(graph, monkeypatch,
                                                 gather_bytes, roll_bytes):
    rows, cols, vals, b, want = graph
    monkeypatch.setattr(ref, "GATHER_BLOCK_BYTES", gather_bytes)
    monkeypatch.setattr(ref, "ROLL_BUCKET_BYTES", roll_bytes)
    a = sp.from_coo(rows, cols, vals, (M, K),
                    config=_config(fringe_vmem_budget=0))
    plan = a.plan
    assert plan.fringe_tier == "xla" and plan.fringe_buckets
    # most nonzeros on the fringe (99.9% at the configuration's size,
    # where the columns spread over 600x as many k-blocks), the rest on
    # the matrix path, so the merge adds both
    assert plan.stats_dict["fringe_nnz"] > 0.5 * rows.size
    assert plan.has_core
    # narrow buckets (fewer rows than a lane tile) and wide ones both run
    assert min(n for n, _ in plan.fringe_buckets) < ref.LANES
    got = sp.spmm(a, b)
    assert got.shape == (M, WIDTH)
    assert _rel_err(got, want) < REL_TOL


def test_prepare_spans_its_phases_and_stats_read_them(graph, monkeypatch):
    """``prepare`` opens ``repro.prepare`` with ``partition``, ``reorder``
    and ``pack`` nested inside, in that order, and the plan's phase stats
    are those spans' own durations."""
    rows, cols, vals, _, _ = graph
    events = []

    class Recorded(spmm.span):
        __slots__ = ()

        def __enter__(self):
            events.append(("open", self.name))
            return super().__enter__()

        def close(self):
            if not self.closed:
                events.append(("close", self.name))
            super().close()

    monkeypatch.setattr(spmm, "span", Recorded)
    SPAN_TIMES.reset()
    plan = spmm.prepare(rows, cols, vals, (M, K), _config())
    phases = [("open", "prepare")]
    for name in ("partition", "reorder", "pack"):
        phases += [("open", name), ("close", name)]
    assert events[:len(phases)] == phases
    assert events[-1] == ("close", "prepare")
    st = plan.stats_dict
    for name in ("partition", "reorder", "pack", "prepare"):
        assert len(SPAN_TIMES.durations_ns(name)) == 1
    for name in ("partition", "reorder", "pack"):
        (ns,) = SPAN_TIMES.durations_ns(name)
        assert st[f"t_{name}_s"] == ns * 1e-9
    assert (st["t_partition_s"] + st["t_reorder_s"] + st["t_pack_s"]
            <= SPAN_TIMES.durations_ns("prepare")[0] * 1e-9)
