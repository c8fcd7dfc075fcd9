"""Architecture-aware cost model (paper §5.2.1), adapted to TPU.

The paper calibrates per-engine throughputs with microbenchmarks and derives
a density threshold

    alpha = r * P_AIV / P_AIC            (Eq. 3)

where the vector engine's cost is proportional to NNZ and the matrix
engine's cost is proportional to the full tile volume M*K (Eq. 1).  Tiles
with density below alpha go to the vector path; the rest to the matrix path.

TPU adaptation
--------------
- "AIC" -> MXU path (dense_tile_spmm kernel): cost ∝ tile volume, rate
  P_MXU expressed in *matrix elements / second* (each element costs 2N
  flops against the dense operand of width N, so
  P_MXU = peak_flops_effective / (2N)).
- "AIV" -> VPU/gather path (gather_spmm kernel): cost ∝ NNZ, rate P_VPU in
  *nonzeros / second*.  Each nonzero gathers one N-wide B row from HBM and
  does an N-wide FMA, so the analytic bound is memory-side:
  P_VPU = hbm_bw / (bytes_per_row_touch) with bytes = N*(sizeof in) +
  amortized output traffic.
- The capacity ratio r (2 AIV : 1 AIC on Ascend) becomes a calibration of
  how many TensorCores each stream occupies; default 1.0 and folded into
  measured throughputs when ``measure`` calibration is used.

Two calibration modes:
- ``analytic_tpu``: derive rates from roofline constants (the default,
  ``default_cost_model``; no timing, so it also holds where wall-clock
  is meaningless, as on CPU).
- ``measure``: time the two jitted paths on the current backend (used by
  the runtime coordinator, mirroring the paper's microbenchmark dry run).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np

from ..kernels.gather_spmm import STEP as FRINGE_STEP

@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip ceilings of one accelerator kind."""

    flops_per_s: float       # dense bf16 matmul
    bytes_per_s: float       # HBM bandwidth
    ici_bytes_per_s: float   # chip-to-chip interconnect, per link
    source: str


#: Roofline peaks keyed by ``jax.Device.device_kind``.  A kind that is not
#: listed has no peaks: no roofline share is computed for it.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops_per_s=197e12,
        bytes_per_s=819e9,
        # 1,600 Gbit/s of interchip interconnect per chip, over 4 links
        ici_bytes_per_s=50e9,
        source="Google Cloud documentation, 'TPU v5e' "
               "(cloud.google.com/tpu/docs/v5e)",
    ),
}


def device_peaks(device_kind: str) -> Optional[DevicePeaks]:
    """Peaks of ``device_kind``, or None when the table does not list it."""
    return DEVICE_PEAKS.get(device_kind)


# the analytic model prices the target chip, TPU v5e
_TARGET = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS_BF16 = _TARGET.flops_per_s
HBM_BW = _TARGET.bytes_per_s
VMEM_BYTES = 16 * 1024 * 1024  # default scoped VMEM limit of one kernel
# scalar memory: the v5e compiler refuses a kernel whose SMEM operands
# exceed 1 MiB ("Allocation ... would exceed memory (size=1048576)")
SMEM_BYTES = 1024 * 1024
MXU_DIM = 128  # systolic array edge; min efficient tile
VPU_LANES = 128
SUBLANES = 8


@dataclasses.dataclass
class EngineCostModel:
    """Predicts per-path execution cost and the split threshold alpha."""

    p_matrix: float  # matrix-path rate: dense tile elements / second
    p_vector: float  # vector-path rate: nonzeros / second
    r: float = 1.0   # capacity ratio (paper's r; engine-count analogue)
    n_cols: int = 256  # dense operand width N the rates were calibrated for

    # --- Eq. (1) ---
    def cost_vector(self, nnz: float) -> float:
        return nnz / self.p_vector

    def cost_matrix(self, m: float, k: float) -> float:
        return (m * k) / self.p_matrix

    # --- Eq. (3) ---
    @property
    def alpha(self) -> float:
        a = self.r * self.p_vector / self.p_matrix
        return float(np.clip(a, 1e-6, 1.0))

    def length_threshold(self, k: int) -> float:
        """Eq. (5): convert the density boundary into a row-length bound."""
        return self.alpha * k

    # --- calibration ---
    @classmethod
    def analytic_tpu(cls, n_cols: int = 256, mxu_efficiency: float = 0.7,
                     r: float = 1.0) -> "EngineCostModel":
        """Roofline-derived rates for the TPU target.

        Matrix path: each dense A element drives 2*N flops on the MXU
        (compute-bound once tiles are dense).  Vector path: each nonzero
        touches one N-wide bf16 row of B from HBM plus fp32 accumulate
        traffic amortized across the row-window (bound by HBM bandwidth).
        """
        p_matrix = mxu_efficiency * PEAK_FLOPS_BF16 / (2.0 * n_cols)
        bytes_per_nnz = n_cols * 2  # gather of one bf16 B row
        p_vector = HBM_BW / bytes_per_nnz
        return cls(p_matrix=p_matrix, p_vector=p_vector, r=r, n_cols=n_cols)

    @classmethod
    def measure(
        cls,
        matrix_bench: Callable[[], None],
        vector_bench: Callable[[], None],
        matrix_work_elems: float,
        vector_work_nnz: float,
        r: float = 1.0,
        n_cols: int = 256,
        repeats: int = 3,
    ) -> "EngineCostModel":
        """Paper-style microbenchmark calibration (§5.2.1 'dry run').

        ``*_bench`` are zero-arg callables that run one synchronized pass of
        the respective path over a workload of the given size.
        """
        # a jitted bench returns when its work is *enqueued* (JAX async
        # dispatch), so timing it without synchronization measures the
        # enqueue and calibrates near-infinite rates; route through the one
        # shared synchronized timer (function-local import: tuner imports
        # this module at top level)
        from .tuner import timed_best_of

        tm = timed_best_of(matrix_bench, repeats=repeats, warmup=1)
        tv = timed_best_of(vector_bench, repeats=repeats, warmup=1)
        return cls(
            p_matrix=matrix_work_elems / tm,
            p_vector=vector_work_nnz / tv,
            r=r,
            n_cols=n_cols,
        )

    # --- Eq. (7): residual split target ---
    def split_residual(
        self, nnz_candidates: np.ndarray, rows_candidates: np.ndarray, k: int
    ) -> int:
        """Pick a prefix count c of candidate units (sorted sparse-first) for
        the vector path so that NNZ(vec) / (M(mat) * K) ≈ alpha.

        ``nnz_candidates[i]``/``rows_candidates[i]`` describe unit i (a tile
        or row-group).  Returns the number of leading units to route to the
        vector path.
        """
        total_rows = float(rows_candidates.sum())
        csum_nnz = np.concatenate([[0.0], np.cumsum(nnz_candidates, dtype=np.float64)])
        csum_rows = np.concatenate([[0.0], np.cumsum(rows_candidates, dtype=np.float64)])
        mat_rows = np.maximum(total_rows - csum_rows, 1.0)
        ratio = csum_nnz / (mat_rows * k)
        return int(np.argmin(np.abs(ratio - self.alpha)))

    def predict_baldu(self, nnz_vec: float, m_mat: float, k: int) -> float:
        """Predicted finish-time imbalance (max/min) of a proposed split."""
        tv = self.cost_vector(max(nnz_vec, 1.0))
        tm = self.cost_matrix(max(m_mat, 1.0), k)
        return max(tv, tm) / max(min(tv, tm), 1e-12)

    # --- dispatch-decision hooks -----------------------------------------
    # prepare()/the executor consult every dispatch decision through the
    # model instance, so the measurement-backed subclass
    # (core.tuner.TunedCostModel) can override any of them; the analytic
    # base delegates to the module-level policies below.

    def select_fringe_tier(
        self, k: int, num_rows: int, bn: int,
        vmem_budget: Optional[int] = None, nnz: int = 0,
    ) -> tuple:
        return select_fringe_tier(k, num_rows, bn, vmem_budget=vmem_budget,
                                  nnz=nnz)

    def select_sddmm_tier(
        self, d: int, n_src_rows: int, n_dst_rows: int,
        vmem_budget: Optional[int] = None,
    ) -> str:
        return select_sddmm_tier(
            d, n_src_rows, n_dst_rows, vmem_budget=vmem_budget
        )

    def imbalance_threshold(self) -> float:
        """Max tolerated LPT row imbalance before rhs-sharding wins."""
        return ROWS_IMBALANCE_THRESHOLD

    def compaction_thresholds(self) -> tuple:
        """``(max_delta_fraction, max_slowdown)`` for should_compact."""
        return DELTA_MAX_FRACTION, DELTA_MAX_SLOWDOWN

    def densify_occupancy(self) -> Optional[float]:
        """Occupancy above which the core densifies (None: kernel default)."""
        return None

    def select_matrix_format(
        self, *, nm_pattern: Optional[tuple], tile_zero_fraction: float,
        num_steps: int, bm: int, bk: int, row_cap: int,
        hint=None,
    ) -> str:
        return select_matrix_format(
            nm_pattern=nm_pattern, tile_zero_fraction=tile_zero_fraction,
            num_steps=num_steps, bm=bm, bk=bk, row_cap=row_cap, hint=hint,
        )

    def tile_shape(self, m: int, k: int, n: int, nnz: int) -> Optional[tuple]:
        """Autotuned ``(bm, bk)`` for this problem, or None to keep the
        config's.  The analytic base never overrides — only the measured
        table (core.tuner.TunedCostModel) answers, demote-only validated
        against the exact plan shape and VMEM budget."""
        return None


def default_cost_model(n_cols: int = 256) -> EngineCostModel:
    return EngineCostModel.analytic_tpu(n_cols=n_cols)


# --- structured matrix-path payload format -----------------------------------
# The matrix engine pays for every byte of the A payload it streams; the
# structured encodings (core.formats) trade the padded (T, bm, bk) stream for
# packed values + metadata.  Selection is priced on modeled payload bytes
# with a conservative hysteresis so the general path keeps every workload
# that does not *clearly* win — bit-exact parity on existing panels is part
# of the contract.
STRUCTURED_BYTES_HYSTERESIS = 0.7   # packed bytes must be <= 70% of general


def matrix_payload_bytes(
    fmt: str, num_steps: int, bm: int, bk: int,
    *, nm_pattern: Optional[tuple] = None, row_cap: int = 0,
) -> int:
    """Modeled HBM bytes of the matrix-path A payload under ``fmt``."""
    if fmt == "nm":
        n_pat, m_pat = nm_pattern
        gk = bk // m_pat
        # packed fp32 values (n per group) + int32 position codes (1/group)
        return num_steps * bm * gk * (n_pat + 1) * 4
    if fmt == "bitmap":
        words = (bk + 31) // 32
        return num_steps * bm * (words + row_cap) * 4
    return num_steps * bm * bk * 4


def select_matrix_format(
    *, nm_pattern: Optional[tuple], tile_zero_fraction: float,
    num_steps: int, bm: int, bk: int, row_cap: int,
    hint=None,
) -> str:
    """Pick the matrix-path payload format: general | nm | bitmap.

    Explicit hints (``("nm", n, m)`` / ``"bitmap"``) override pricing; the
    soft ``"nm"`` hint takes any detected pattern.  Unhinted selection
    promotes only a *detected* N:M pattern with a substantial modeled-bytes
    saving — never the bitmap payload: unstructured graph panels routinely
    exceed any waste threshold (measured 0.88-0.99 on the bench panel), so
    auto-bitmap would move existing workloads off the bit-exact general
    path.  Bitmap is opt-in (hint), floored on not growing the payload.
    """
    if isinstance(hint, tuple) and hint and hint[0] == "nm":
        return "nm"
    general = matrix_payload_bytes("general", num_steps, bm, bk)
    if hint == "bitmap":
        bitmap_bytes = matrix_payload_bytes(
            "bitmap", num_steps, bm, bk, row_cap=row_cap
        )
        # honor the hint unless packing would *grow* the payload
        if bitmap_bytes <= general:
            return "bitmap"
        return "general"
    if nm_pattern is not None:
        nm_bytes = matrix_payload_bytes(
            "nm", num_steps, bm, bk, nm_pattern=nm_pattern
        )
        if hint == "nm" or nm_bytes <= STRUCTURED_BYTES_HYSTERESIS * general:
            return "nm"
    return "general"


# --- vector-path (fringe) VMEM dispatch tiers ------------------------------
# The coordinator's matrix/vector split is only meaningful if the vector path
# can actually execute what it is handed, so the kernel-dispatch tier choice
# lives here next to the split model: the budget leaves ~4 MB of the 16 MB
# VMEM for the grid pipeline's double-buffered fetches and Mosaic scratch.
FRINGE_VMEM_BUDGET = 12 * 1024 * 1024
FRINGE_MIN_BK = SUBLANES  # smallest legal fp32 k-slice (sublane multiple)


def _pad_rows(num_rows: int) -> int:
    """Packed fringe rows padded to the fp32 sublane multiple."""
    return max(SUBLANES, ((num_rows + SUBLANES - 1) // SUBLANES) * SUBLANES)


def fringe_resident_bytes(k: int, num_rows: int, bn: int) -> int:
    """Tier-(a) working set: full (K, bn) B panel + packed fp32 out block."""
    return (k + _pad_rows(num_rows)) * bn * 4


# --- scalar-memory (SMEM) claims ---------------------------------------------
# The gather kernels stream their nonzero ids through SMEM one grid step at
# a time (kernels.gather_spmm.STEP entries per stream, double-buffered), so
# their SMEM claim is fixed — except the K-sharded tier's chunk -> k-block
# map, which an index map reads and is therefore scalar-prefetched whole.
# The matrix-path kernels prefetch their (window, k-block) step metadata
# whole too, 8 bytes per tile step: compiling dense_tile_spmm for a v5e
# passes at 130,000 steps and is refused at 131,000 (Mosaic keeps ~2 KiB of
# its own), so 32 KiB stay in reserve.
SMEM_BUDGET = SMEM_BYTES - 32 * 1024


def stream_smem_bytes(n_streams: int) -> int:
    """SMEM of ``n_streams`` double-buffered STEP-entry 32-bit blocks."""
    return n_streams * 2 * FRINGE_STEP * 4


def fringe_smem_bytes(tier: str, k: int, bk: int, nnz: int) -> int:
    """SMEM claim of a gather-SpMM tier over a fringe of ``nnz`` entries."""
    if tier == "resident":
        return stream_smem_bytes(3)
    if tier == "ksharded":
        n_kb = -(-int(k) // int(bk))
        # each nonempty k-block bucket is padded to a STEP multiple
        num_chunks = -(-int(nnz) // FRINGE_STEP) + min(n_kb, max(int(nnz), 1))
        return stream_smem_bytes(3) + 4 * num_chunks
    return 0


def assert_smem_claim(claim_bytes: int, what: str) -> None:
    """Backstop against a kernel whose SMEM operands cannot fit the chip."""
    if claim_bytes > SMEM_BUDGET:
        raise ValueError(
            f"{what} needs ~{claim_bytes / 2**10:.0f} KiB of SMEM "
            f"(> {SMEM_BUDGET / 2**10:.0f} KiB budget of the "
            f"{SMEM_BYTES / 2**10:.0f} KiB scalar memory); use the XLA tier "
            "for this shape"
        )


def assert_step_metadata_smem(num_steps: int, kernel: str) -> None:
    """Backstop for a matrix-path kernel, which scalar-prefetches its
    step_window + step_col whole (8 bytes per tile step)."""
    assert_smem_claim(8 * int(num_steps),
                      f"{kernel} step metadata (T={num_steps})")


def fringe_ksharded_bytes(bk: int, num_rows: int, bn: int) -> int:
    """Tier-(b) working set: double-buffered (bk, bn) B slice + out block.

    Unlike the resident tier, the B slice changes every grid step, so the
    pipeline keeps two in flight — hence the 2x on bk.
    """
    return (2 * bk + _pad_rows(num_rows)) * bn * 4


# --- data-parallel shard-axis selection -------------------------------------
# The sharded executor (core/spmm.prepare_sharded) can distribute work two
# ways: shard output row-windows (plan state fully distributed; balance
# limited by how evenly window costs split) or replicate the plan and shard
# RHS columns (perfectly balanced by construction; plan memory replicated
# per device).  The estimator prices both and picks per plan.
ROWS_IMBALANCE_THRESHOLD = 1.25  # max tolerated LPT max/mean before rhs wins


@dataclasses.dataclass(frozen=True)
class ShardAxisDecision:
    shard_axis: str        # "rows" | "rhs"
    n_shards: int
    rows_imbalance: float  # predicted max/mean load of the LPT row split
    reason: str


def select_shard_axis(
    window_costs: np.ndarray,
    n_shards: int,
    imbalance_threshold: float = ROWS_IMBALANCE_THRESHOLD,
) -> ShardAxisDecision:
    """Pick the data-parallel axis for a plan with these window costs.

    Runs the actual LPT assignment (coordinator.balance_row_window_list)
    the rows-sharded executor would use and measures its max/mean load;
    row-sharding wins unless the distribution is provably skewed past the
    threshold or there are too few costed windows to occupy every shard.
    """
    from .coordinator import balance_row_window_list, list_imbalance

    wc = np.asarray(window_costs, np.float64)
    n_shards = int(n_shards)
    if n_shards <= 1:
        return ShardAxisDecision("rows", n_shards, 1.0, "single shard")
    active = int(np.count_nonzero(wc))
    if active == 0:
        # empty matrix: nothing to balance, and rows has no N-divisibility
        # constraint — keep the degenerate case on the unconstrained axis
        return ShardAxisDecision("rows", n_shards, 1.0, "no costed windows")
    if active < n_shards:
        return ShardAxisDecision(
            "rhs", n_shards, float("inf"),
            f"{active} non-empty windows < {n_shards} shards",
        )
    assignment = balance_row_window_list(wc, n_shards)
    imb = list_imbalance(assignment, wc)
    if imb > imbalance_threshold:
        return ShardAxisDecision(
            "rhs", n_shards, float(imb),
            f"LPT row imbalance {imb:.2f} > {imbalance_threshold:.2f}",
        )
    return ShardAxisDecision(
        "rows", n_shards, float(imb), f"LPT row imbalance {imb:.2f}"
    )


# --- dynamic-delta compaction policy ----------------------------------------
# Structural mutations accumulate in a COO sidecar executed on the vector
# path (dynamic/delta.py).  That is the right home for a *small* delta — the
# fringe kernel's cost is proportional to NNZ and the base plan stays intact
# — but the sidecar is unordered/unreordered work, so once it grows past a
# fraction of the base matrix (or its predicted vector-path cost starts to
# dominate the plan's own execution) folding it into a fresh prepare() wins
# back the coordinated split.  The same engine rates that price the
# matrix/vector split price this trigger.
DELTA_MAX_FRACTION = 0.25   # delta nnz / base nnz before a forced fold
DELTA_MAX_SLOWDOWN = 1.25   # predicted (base+delta)/base exec cost ratio
# denominator floor for the fraction trigger: a plan built (near-)empty and
# grown via GraphDelta inserts would otherwise fold on its very first
# batches (fraction ~ delta/1), churning exactly where the sidecar is
# cheapest.  Deltas below FLOOR * DELTA_MAX_FRACTION nonzeros never force a
# fold on fraction grounds.
DELTA_BASE_NNZ_FLOOR = 256


@dataclasses.dataclass(frozen=True)
class CompactionDecision:
    compact: bool
    delta_fraction: float   # delta nnz / base nnz
    est_slowdown: float     # predicted exec-cost ratio with the sidecar
    reason: str


def should_compact(
    cm: EngineCostModel,
    *,
    base_nnz: int,
    delta_nnz: int,
    core_rows: int,
    fringe_nnz: int,
    k: int,
    max_delta_fraction: float = DELTA_MAX_FRACTION,
    max_slowdown: float = DELTA_MAX_SLOWDOWN,
) -> CompactionDecision:
    """Decide whether a delta sidecar should fold into a fresh plan.

    ``core_rows`` is the matrix-path packed row count (num_windows * bm) and
    ``fringe_nnz`` the base plan's vector-path nonzeros; together they give
    the cost-model estimate of the base execution the sidecar rides on.

    Empty-base policy: a plan with no core rows and no fringe nonzeros has
    ``base_cost == 0``, so the slowdown ratio is undefined — the sidecar IS
    the execution, and "1.25x slower than nothing" can never be a sane
    trigger.  Such plans fold only on the nnz-fraction trigger, whose
    denominator is floored at ``DELTA_BASE_NNZ_FLOOR`` so the first small
    insert batches ride the sidecar instead of forcing a fold per update.
    """
    fraction = delta_nnz / max(base_nnz, DELTA_BASE_NNZ_FLOOR)
    base_cost = cm.cost_matrix(core_rows, k) + cm.cost_vector(fringe_nnz)
    if delta_nnz == 0:
        return CompactionDecision(False, 0.0, 1.0, "empty delta")
    if base_cost <= 0.0:
        if fraction > max_delta_fraction:
            return CompactionDecision(
                True, fraction, 1.0,
                f"empty base: delta nnz fraction {fraction:.3f} > "
                f"{max_delta_fraction:.2f} (floored base "
                f"{max(base_nnz, DELTA_BASE_NNZ_FLOOR)})",
            )
        return CompactionDecision(
            False, fraction, 1.0,
            f"empty base: delta within floored fraction budget "
            f"({fraction:.3f})",
        )
    slowdown = (base_cost + cm.cost_vector(delta_nnz)) / base_cost
    if fraction > max_delta_fraction:
        return CompactionDecision(
            True, fraction, slowdown,
            f"delta nnz fraction {fraction:.3f} > {max_delta_fraction:.2f}",
        )
    if slowdown > max_slowdown:
        return CompactionDecision(
            True, fraction, slowdown,
            f"predicted fringe-path slowdown {slowdown:.2f} > "
            f"{max_slowdown:.2f}",
        )
    return CompactionDecision(
        False, fraction, slowdown,
        f"delta within budget ({fraction:.3f}, {slowdown:.2f})",
    )


def ksharded_bk_cap(k: int, num_rows: int, bn: int, budget: int) -> int:
    """Largest legal ``bk`` for the K-sharded fringe tier, or 0 if none.

    Two clamps, both required for the tier to be worth selecting:

    - the VMEM budget: the double-buffered (bk, bn) slice pair plus the
      packed output block must fit ``budget`` bytes;
    - strict byte-superiority over the resident tier: streaming only makes
      sense while the double-buffered working set is *smaller* than keeping
      the whole K panel resident, i.e. ``2*bk < k``.  With the historical
      ``_pad_rows(k)`` clamp this invariant was emergent from the budget
      arithmetic (resident rejected => k > budget_rows => 2*bk < k); making
      it structural means no caller — including the tuner's bk sweep, which
      uses this helper for its candidate grid — can select a "cheaper"
      streaming tier with a larger VMEM claim than the resident tier it
      rejected.

    The result is a sublane multiple; candidates below ``FRINGE_MIN_BK``
    are illegal and collapse to 0 (caller falls back to the XLA tier).
    """
    bk_budget = (int(budget) // (bn * 4) - _pad_rows(num_rows)) // 2
    bk_superior = (int(k) - 1) // 2  # strictly cheaper in bytes: 2*bk < k
    bk = (min(bk_budget, bk_superior) // SUBLANES) * SUBLANES
    return int(bk) if bk >= FRINGE_MIN_BK else 0


def select_fringe_tier(
    k: int, num_rows: int, bn: int, vmem_budget: Optional[int] = None,
    nnz: int = 0,
) -> tuple:
    """Pick the vector-path kernel tier for a fringe of this shape.

    ``nnz`` (the fringe's nonzero count) prices the SMEM the K-sharded
    tier's prefetched chunk map claims; a tier whose VMEM *or* SMEM claim
    does not fit is never picked.

    Returns ``(tier, bk)``:
      - ``("resident", 0)``  — single-panel kernel; whole (K, bn) B panel
        stays in VMEM (fastest: B loaded once per n-block).
      - ``("ksharded", bk)`` — K-sharded streaming kernel; only a (bk, bn)
        B slice is resident per step, with bk the largest sublane multiple
        that fits the budget AND is strictly cheaper in bytes than the
        resident tier it replaces (see ksharded_bk_cap).
      - ``("xla", 0)``       — even one minimal (8, bn) slice plus the
        packed output block overflows; fall back to the XLA gather.
    """
    budget = FRINGE_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    if fringe_resident_bytes(k, num_rows, bn) <= budget:
        return "resident", 0
    bk = ksharded_bk_cap(k, num_rows, bn, budget)
    if bk and fringe_smem_bytes("ksharded", k, bk, nnz) <= SMEM_BUDGET:
        return "ksharded", bk
    return "xla", 0


def assert_vmem_claim(claim_bytes: int, what: str) -> None:
    """Hard physical-VMEM check shared by every pallas kernel entry point.

    The dispatch tiers above keep working sets under the *soft* budget; this
    is the backstop against a caller bypassing tier selection (or forcing a
    tier) into a kernel whose working set cannot physically fit.  One
    helper so the kernels and ``select_fringe_tier`` can never disagree
    about what "fits" means.
    """
    if claim_bytes > VMEM_BYTES:
        raise ValueError(
            f"{what} needs ~{claim_bytes / 2**20:.1f} MB of VMEM "
            f"(> {VMEM_BYTES / 2**20:.0f} MB physical); use the K-sharded "
            "or XLA dispatch tier for this shape"
        )


# --- SDDMM dispatch tiers ----------------------------------------------------
# The SDDMM fringe gather keeps *both* dense operand panels resident: the
# full (M_pad, D) X panel and the (K_pad, D) Y^T panel (each nonzero reads
# one row of each).  There is no useful K-sharded middle tier — the reduced
# axis is D, and slicing D would re-stream both panels — so the selection is
# binary: resident pallas gather, or the XLA reference gather.


def sddmm_resident_bytes(d: int, n_src_rows: int, n_dst_rows: int) -> int:
    """SDDMM gather working set: X panel + Y^T panel + one output block
    (STEP dots)."""
    return (_pad_rows(n_src_rows) + _pad_rows(n_dst_rows)) * d * 4 + \
        FRINGE_STEP * 4


def select_sddmm_tier(
    d: int, n_src_rows: int, n_dst_rows: int,
    vmem_budget: Optional[int] = None,
) -> str:
    """Pick the SDDMM fringe-gather tier: ``"resident"`` or ``"xla"``."""
    budget = FRINGE_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    # SMEM holds only the two streamed id blocks, whatever the nnz
    if (sddmm_resident_bytes(d, n_src_rows, n_dst_rows) <= budget
            and stream_smem_bytes(2) <= SMEM_BUDGET):
        return "resident"
    return "xla"
