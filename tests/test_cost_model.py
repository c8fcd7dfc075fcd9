"""Architecture-aware cost model (paper §5.2.1, Eq. 1-3, 7) and the
vector-path VMEM dispatch-tier estimate."""
import numpy as np
import pytest

from repro.core.cost_model import (
    DELTA_BASE_NNZ_FLOOR,
    DELTA_MAX_FRACTION,
    FRINGE_STEP,
    FRINGE_VMEM_BUDGET,
    SMEM_BUDGET,
    SMEM_BYTES,
    EngineCostModel,
    default_cost_model,
    fringe_ksharded_bytes,
    fringe_resident_bytes,
    fringe_smem_bytes,
    ksharded_bk_cap,
    select_fringe_tier,
    should_compact,
)


def test_alpha_formula():
    cm = EngineCostModel(p_matrix=100.0, p_vector=10.0, r=2.0)
    assert cm.alpha == pytest.approx(0.2)  # r * Pv / Pm


def test_alpha_clipped():
    cm = EngineCostModel(p_matrix=1.0, p_vector=10.0, r=2.0)
    assert cm.alpha == 1.0


def test_cost_eq1():
    cm = EngineCostModel(p_matrix=50.0, p_vector=5.0)
    assert cm.cost_vector(10) == pytest.approx(2.0)
    assert cm.cost_matrix(10, 10) == pytest.approx(2.0)


def test_balanced_at_alpha_density():
    """At density == alpha the two engines predict equal cost (r=1)."""
    cm = EngineCostModel(p_matrix=1000.0, p_vector=10.0, r=1.0)
    m, k = 128, 256
    nnz = cm.alpha * m * k
    assert cm.cost_vector(nnz) == pytest.approx(cm.cost_matrix(m, k))


def test_split_residual_targets_alpha():
    cm = EngineCostModel(p_matrix=1000.0, p_vector=10.0, r=1.0)
    k = 512
    nnz = np.full(100, 64.0)
    rows = np.full(100, 8.0)
    c = cm.split_residual(nnz, rows, k)
    ratio = nnz[:c].sum() / max((rows[c:].sum()) * k, 1)
    # chosen prefix approximates the alpha target better than extremes
    err = abs(ratio - cm.alpha)
    err0 = abs(0.0 - cm.alpha)
    assert err <= err0


def test_measure_calibration():
    import time

    def fast():
        pass

    def slow():
        time.sleep(0.002)

    cm = EngineCostModel.measure(fast, slow, 1000.0, 1000.0, repeats=1)
    assert cm.p_matrix > cm.p_vector  # fast engine calibrates faster


def test_analytic_tpu_sane():
    cm = default_cost_model(256)
    assert 0.0 < cm.alpha < 1.0
    # vector path is memory-bound: far fewer nnz/s than matrix elements/s
    assert cm.p_matrix > cm.p_vector


def test_fringe_tier_resident_when_panel_fits():
    tier, bk = select_fringe_tier(1024, 100, 256)
    assert (tier, bk) == ("resident", 0)
    assert fringe_resident_bytes(1024, 100, 256) <= FRINGE_VMEM_BUDGET


def test_fringe_tier_ksharded_when_panel_overflows():
    k, rows, bn = 20_000, 100, 256
    assert fringe_resident_bytes(k, rows, bn) > FRINGE_VMEM_BUDGET
    tier, bk = select_fringe_tier(k, rows, bn)
    assert tier == "ksharded"
    # bk is the largest sublane multiple whose double-buffered slice fits
    assert bk >= 8 and bk % 8 == 0
    assert fringe_ksharded_bytes(bk, rows, bn) <= FRINGE_VMEM_BUDGET
    assert fringe_ksharded_bytes(bk + 8, rows, bn) > FRINGE_VMEM_BUDGET


def test_fringe_tier_xla_when_rows_alone_overflow():
    # the packed output block by itself busts the budget: no bk can help
    tier, bk = select_fringe_tier(20_000, 100_000, 256)
    assert (tier, bk) == ("xla", 0)


def test_fringe_tier_respects_budget_override():
    # same shape sweeps all three tiers as the synthetic budget shrinks
    assert select_fringe_tier(64, 16, 128)[0] == "resident"
    assert select_fringe_tier(64, 16, 128, vmem_budget=20_000)[0] == "ksharded"
    assert select_fringe_tier(64, 16, 128, vmem_budget=4_096)[0] == "xla"


@pytest.mark.parametrize("nnz", [0, 1, 80_000, 10**6, 10**8, 3 * 10**8])
def test_fringe_tier_never_claims_more_smem_than_the_chip(nnz):
    """Whatever the fringe's size, the picked tier's SMEM claim fits: the
    streamed nonzero blocks are fixed, and only the K-sharded chunk map
    (4 B per STEP-entry chunk, scalar-prefetched whole) grows with nnz."""
    for k in (1024, 20_000, 169_344, 2_000_000):
        for num_rows in (100, 2000, 8192):
            tier, bk = select_fringe_tier(k, num_rows, 256, nnz=nnz)
            assert fringe_smem_bytes(tier, k, bk, nnz) <= SMEM_BUDGET < SMEM_BYTES


def test_fringe_tier_demotes_ksharded_when_chunk_map_overflows_smem():
    k, rows, bn = 20_000, 100, 256
    assert select_fringe_tier(k, rows, bn, nnz=10**6)[0] == "ksharded"
    # ~250M nonzeros make a chunk map larger than the SMEM budget
    huge = (SMEM_BUDGET // 4) * FRINGE_STEP
    assert fringe_smem_bytes("ksharded", k, 2048, huge) > SMEM_BUDGET
    assert select_fringe_tier(k, rows, bn, nnz=huge) == ("xla", 0)


# --- bug regression: measure() must synchronize async dispatch ------------


class _Deferred:
    """Stands in for a jax.Array under async dispatch: the call returns
    immediately, the actual work only happens at block_until_ready()."""

    def __init__(self, seconds: float):
        self._seconds = seconds

    def block_until_ready(self):
        import time

        time.sleep(self._seconds)
        return self


def test_timed_best_of_synchronizes_deferred_work():
    from repro.core.tuner import timed_best_of

    t = timed_best_of(lambda: _Deferred(0.003), repeats=2, warmup=0)
    assert t >= 0.003  # pre-fix (no sync) this measured the ~0s enqueue


def test_measure_calibration_synchronizes_async_benches():
    """A bench whose cost hides behind async dispatch must still calibrate.

    The historical ``measure`` timed the bench call without synchronizing,
    so two benches of wildly different device cost both measured their
    (near-zero) enqueue time and calibrated near-equal rates."""
    cm = EngineCostModel.measure(
        lambda: _Deferred(0.0), lambda: _Deferred(0.004),
        1000.0, 1000.0, repeats=1,
    )
    # slow vector engine must calibrate a much lower rate; pre-fix the
    # ratio was ~1 (both benches measured as their enqueue)
    assert cm.p_matrix > 5 * cm.p_vector


# --- bug regression: ksharded tier must be strictly cheaper than resident --


def test_ksharded_bk_cap_small_k_has_no_legal_bk():
    # k=16: even an infinite budget admits no bk with 2*bk < k on the
    # sublane grid ((16-1)//2 = 7 < 8) — the streaming tier cannot be
    # cheaper than just keeping the 16-row panel resident
    assert ksharded_bk_cap(16, 8, 8, 10**9) == 0
    assert ksharded_bk_cap(17, 8, 8, 10**9) == 8  # first k with a legal bk


def test_ksharded_candidate_strictly_cheaper_than_resident():
    """Whenever the dispatch picks ksharded, its working set must be both
    within budget and strictly smaller than the resident tier it rejected
    (pre-fix the bk clamp allowed budget-sized bk with 2*bk >= k)."""
    for k in (16, 24, 64, 256, 1024, 4096, 20_000):
        for num_rows in (8, 100, 2000):
            for budget in (4_096, 20_000, 10**5, FRINGE_VMEM_BUDGET):
                tier, bk = select_fringe_tier(
                    k, num_rows, 256, vmem_budget=budget)
                if tier != "ksharded":
                    continue
                assert bk >= 8 and bk % 8 == 0
                ks = fringe_ksharded_bytes(bk, num_rows, 256)
                assert ks <= budget
                assert ks < fringe_resident_bytes(k, num_rows, 256)


# --- bug regression: should_compact on an empty/tiny base ------------------


def test_should_compact_empty_base_is_finite_and_fraction_only():
    """base_cost == 0 used to produce slowdown == inf -> compact on every
    update batch.  Policy: only the (floored) fraction trigger fires."""
    cm = default_cost_model()
    d = should_compact(cm, base_nnz=0, delta_nnz=8, core_rows=0,
                       fringe_nnz=0, k=64)
    assert not d.compact
    assert np.isfinite(d.est_slowdown)
    # above the floored fraction budget the fold does trigger
    big = int(DELTA_BASE_NNZ_FLOOR * DELTA_MAX_FRACTION) + 1
    d2 = should_compact(cm, base_nnz=0, delta_nnz=big, core_rows=0,
                        fringe_nnz=0, k=64)
    assert d2.compact and np.isfinite(d2.est_slowdown)


def test_should_compact_floor_protects_tiny_bases():
    cm = default_cost_model()
    # base of 100 nonzeros, delta of 30: the raw fraction (0.30) exceeds
    # DELTA_MAX_FRACTION and pre-floor would have forced a fold, but the
    # floored denominator keeps the sidecar riding (the slowdown trigger
    # stays quiet: the matrix path dominates this base's cost)
    d = should_compact(cm, base_nnz=100, delta_nnz=30, core_rows=1024,
                       fringe_nnz=100, k=64)
    assert not d.compact
    assert d.delta_fraction == pytest.approx(30 / DELTA_BASE_NNZ_FLOOR)
    assert d.est_slowdown < 1.25
