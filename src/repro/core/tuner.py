"""Persistent per-device autotuner for the dispatch cost model (ROADMAP 4).

Every dispatch decision the analytic cost model makes — matrix/vector split
rates, fringe VMEM tier, sddmm tier, densify-occupancy crossover, shard-axis
imbalance tolerance, delta-compaction budget — started life as a hand-tuned
constant.  The paper (§5.2.1) calibrates its cost model with microbenchmark
"dry runs" instead; this module is that dry run, made persistent:

- On first sight of a ``(device fingerprint, op, plan shape class)`` key
  (``autotune=True``), the tuner times the real candidate decisions with the
  synchronized best-of-N timer below and records a JSON-serializable entry.
- The table persists through an installed *store* (see ``install_store``) —
  in practice ``repro.dynamic.tuning.RegistryTuningStore``, which rides
  ``PlanRegistry``'s generational atomic layout — so a warm process performs
  **zero** microbenchmarks (CI proves this via ``tune_call_count()``).
- ``autotune="offline"`` never benchmarks inline: records come from the
  table or the resolve falls back to the analytic model, counted in
  ``cold_misses`` (surfaced by ``SpmmService.health()``).  This is the mode
  a serving process runs in; the table is produced offline by
  ``benchmarks/collect_tuning_json.py`` or adopted from a background tune.

Layering: this module sits in ``core`` and imports only downward (kernels,
sibling core modules).  Persistence is dependency-inverted: the registry
lives in the *dynamic* layer, so the store object is built up there and
handed down through ``install_store`` — ``tools/check_layers.py`` verifies
both the import direction and that nothing in ``core`` calls the seam.

Measured preferences are advisory, never load-bearing for safety: a tuned
tier is re-validated against the *exact* plan shape and VMEM budget before
use (the table is keyed by shape class, the plan is precise), and a missing
or corrupt table degrades to the analytic model — never an error.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import REGISTRY, instance_label
from .cost_model import (
    DELTA_MAX_FRACTION,
    DELTA_MAX_SLOWDOWN,
    FRINGE_VMEM_BUDGET,
    MXU_DIM,
    ROWS_IMBALANCE_THRESHOLD,
    SMEM_BUDGET,
    SUBLANES,
    VMEM_BYTES,
    EngineCostModel,
    default_cost_model,
    fringe_resident_bytes,
    fringe_smem_bytes,
    ksharded_bk_cap,
    select_fringe_tier,
)

# bump when the record layout below changes; stored per record and checked
# on load so stale tables degrade to the analytic model instead of
# misinterpreting fields
TABLE_FORMAT_VERSION = 1

# a measured candidate must beat the analytic choice by this factor before
# it overrides it — absorbs timer noise and keeps ties (e.g. two tiers that
# lower to the same XLA gather) on the analytic default
MEASURED_HYSTERESIS = 0.92


# --- synchronized timing (the one shared timer) ------------------------------


def _sync(x: Any) -> Any:
    """Block until the device work behind ``x`` is done.

    Duck-typed before delegating to ``jax.block_until_ready`` so test
    doubles exposing a ``block_until_ready`` method synchronize too (recent
    jax versions only block on actual ``jax.Array`` leaves).
    """
    if hasattr(x, "block_until_ready"):
        x.block_until_ready()
        return x
    return jax.block_until_ready(x)


def timed_best_of(
    fn: Callable[[], Any], repeats: int = 3, warmup: int = 1
) -> float:
    """Best-of-``repeats`` synchronized wall time of ``fn()`` in seconds.

    Under JAX async dispatch a jitted callable returns as soon as the work
    is *enqueued*; timing it without synchronization measures the enqueue,
    not the compute.  Every timing path in the repo (cost-model
    calibration, the tuner's microbenchmarks, ``benchmarks/common.time_fn``)
    routes through this helper so none of them can regress independently.
    """
    for _ in range(max(int(warmup), 0)):
        _sync(fn())
    best = float("inf")
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        _sync(fn())
        best = min(best, time.perf_counter() - t0)
    return max(best, 1e-9)


# --- test hooks: microbenchmark counter + injectable timer -------------------

# tuner observability lives on the repro.obs registry; the per-``instance``
# label keeps each Tuner's counts independent (reset_for_tests swaps the
# tuner, and the fresh instance's series start at zero)
_TUNER_EVENTS = REGISTRY.counter(
    "tuner_events_total",
    "cost-model tuner events (table_hit/cold_miss/measured/store_error)",
    labelnames=("event", "instance"),
    max_series=8192,
)
_MICROBENCH = REGISTRY.counter(
    "tuner_microbench_total", "inline microbenchmark invocations")

_TIMER: Callable[[Callable[[], Any]], float] = timed_best_of


def tune_call_count() -> int:
    """Microbenchmark invocations since process start (or last reset).

    The warm-start acceptance check: a process resolving every decision
    from a persisted table reports 0.  Reads ``tuner_microbench_total``.
    """
    return int(_MICROBENCH.total())


def reset_tune_call_count() -> None:
    _MICROBENCH.reset()


def set_timer(timer: Callable[[Callable[[], Any]], float]) -> None:
    """Replace the wall-clock timer (tests inject deterministic ones)."""
    global _TIMER
    _TIMER = timer


def reset_timer() -> None:
    global _TIMER
    _TIMER = timed_best_of


# --- persistence seam (store installed by the dynamic layer) -----------------

_STORE: Optional[Any] = None  # save(table: dict) -> None; load() -> dict|None


def install_store(store: Optional[Any]) -> None:
    """Install the table persistence backend (``None`` uninstalls).

    Called from *above* core (``repro.dynamic.tuning`` builds the
    registry-backed store); core only ever talks to the protocol.  A newly
    installed store is consulted on the next resolve.
    """
    global _STORE
    _STORE = store
    _TUNER._loaded = False


def installed_store() -> Optional[Any]:
    return _STORE


# --- keys --------------------------------------------------------------------


def device_fingerprint() -> str:
    """Stable id of the device the measurements are valid for."""
    try:
        d = jax.devices()[0]
        kind = getattr(d, "device_kind", None) or d.platform
        return f"{d.platform}:{kind}".replace(" ", "_")
    except Exception:  # pragma: no cover - no backend at all
        return "unknown:unknown"


def _log2_bucket(x: int) -> int:
    return int(math.ceil(math.log2(max(int(x), 1)))) if x > 1 else 0


def shape_class(op: str, m: int, k: int, nnz: int, config: Any) -> str:
    """Coarse problem-class key: two plans in one class share decisions.

    Dims bucket by power of two and density by decade, so one table entry
    covers a family of similar problems instead of re-tuning per matrix.
    """
    density = nnz / max(int(m) * int(k), 1)
    dec = int(np.clip(np.floor(np.log10(max(density, 1e-12))), -12, 0))
    return (
        f"{op}|m{_log2_bucket(m)}|k{_log2_bucket(k)}|d{dec}"
        f"|bn{int(config.bn)}|{config.impl}"
    )


def table_key(op: str, m: int, k: int, nnz: int, config: Any) -> str:
    return f"{device_fingerprint()}|{shape_class(op, m, k, nnz, config)}"


# --- the tuned model ---------------------------------------------------------


@dataclasses.dataclass
class TunedCostModel(EngineCostModel):
    """EngineCostModel whose dispatch decisions come from measurements.

    ``decisions`` holds the per-shape-class measured overrides (absent key
    -> analytic behavior).  Tier preferences are validated against the
    exact plan shape/budget at decision time and can only be adopted when
    physically legal — the table can demote (e.g. force the XLA tier) but
    never promote past a VMEM budget.
    """

    decisions: Dict[str, Any] = dataclasses.field(default_factory=dict)
    key: str = ""
    source: str = "measured"  # "measured" (fresh) | "table" (persisted)

    def select_fringe_tier(
        self, k: int, num_rows: int, bn: int,
        vmem_budget: Optional[int] = None, nnz: int = 0,
    ) -> tuple:
        budget = (
            FRINGE_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
        )
        choice = self.decisions.get("fringe_tier")
        if choice:
            tier, bk = str(choice[0]), int(choice[1])
            if tier == "xla":
                return "xla", 0
            if tier == "resident" and (
                fringe_resident_bytes(k, num_rows, bn) <= budget
            ):
                return "resident", 0
            if tier == "ksharded":
                cap = ksharded_bk_cap(k, num_rows, bn, budget)
                if cap:
                    bk = min(bk, cap) if bk >= SUBLANES else cap
                    bk = (bk // SUBLANES) * SUBLANES
                    if fringe_smem_bytes(tier, k, bk, nnz) <= SMEM_BUDGET:
                        return "ksharded", bk
        return select_fringe_tier(k, num_rows, bn, vmem_budget=vmem_budget,
                                  nnz=nnz)

    def select_sddmm_tier(
        self, d: int, n_src_rows: int, n_dst_rows: int,
        vmem_budget: Optional[int] = None,
    ) -> str:
        # demote-only: a measured "xla" preference always wins (safe), a
        # measured "resident" still has to fit the budget (analytic check)
        if self.decisions.get("sddmm_tier") == "xla":
            return "xla"
        return EngineCostModel.select_sddmm_tier(
            self, d, n_src_rows, n_dst_rows, vmem_budget=vmem_budget
        )

    def imbalance_threshold(self) -> float:
        v = self.decisions.get("shard_imbalance_threshold")
        return float(v) if v is not None else ROWS_IMBALANCE_THRESHOLD

    def compaction_thresholds(self) -> Tuple[float, float]:
        return (
            float(self.decisions.get(
                "delta_max_fraction", DELTA_MAX_FRACTION)),
            float(self.decisions.get(
                "delta_max_slowdown", DELTA_MAX_SLOWDOWN)),
        )

    def densify_occupancy(self) -> Optional[float]:
        v = self.decisions.get("densify_occupancy")
        return float(v) if v is not None else None

    def tile_shape(self, m: int, k: int, n: int, nnz: int) -> Optional[tuple]:
        # demote-only: the measured (bm, bk) is re-validated against the
        # exact plan shape before adoption — MXU/sublane alignment, no tile
        # taller/wider than the padded operand, and the fp32 tile set
        # (A tile + B block + accumulator panel) within the double-buffered
        # VMEM claim.  Anything invalid keeps the config's shape.
        choice = self.decisions.get("tile_shape")
        if not choice:
            return None
        bm, bk = int(choice[0]), int(choice[1])
        if bm <= 0 or bk <= 0 or bm % MXU_DIM or bk % SUBLANES:
            return None
        if bm > max(MXU_DIM, -(-int(m) // MXU_DIM) * MXU_DIM):
            return None
        if bk > max(SUBLANES, -(-int(k) // SUBLANES) * SUBLANES):
            return None
        if (bm * bk + bk * int(n) + bm * int(n)) * 4 > VMEM_BYTES // 2:
            return None
        return (bm, bk)


# --- the tuner ---------------------------------------------------------------


class Tuner:
    """Process-wide table of measured records, keyed by ``table_key``."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._table: Dict[str, dict] = {}
        self._loaded = False
        self._label = instance_label("tuner")

    def _count(self, event: str) -> None:
        _TUNER_EVENTS.inc(event=event, instance=self._label)

    def _value(self, event: str) -> int:
        return int(_TUNER_EVENTS.value(event=event, instance=self._label))

    # registry-backed views of the counters this class used to own
    @property
    def table_hits(self) -> int:    # resolves served from a (loaded) record
        return self._value("table_hit")

    @property
    def cold_misses(self) -> int:   # offline resolves with no record
        return self._value("cold_miss")

    @property
    def measured(self) -> int:      # records produced by inline measurement
        return self._value("measured")

    @property
    def store_errors(self) -> int:  # load/save failures (corrupt table, IO)
        return self._value("store_error")

    # -- store interaction ----------------------------------------------------

    def _maybe_load(self) -> None:
        with self._lock:
            if self._loaded:
                return
            self._loaded = True
        if _STORE is None:
            return
        try:
            table = _STORE.load()
        except Exception:
            # corrupt/unreadable table: analytic fallback, surfaced — never
            # an error on the resolve path
            with self._lock:
                self._count("store_error")
            return
        if not isinstance(table, dict):
            return
        with self._lock:
            for key, rec in table.items():
                if (
                    isinstance(rec, dict)
                    and rec.get("table_format_version") == TABLE_FORMAT_VERSION
                ):
                    # in-memory records win: they are at least as fresh
                    self._table.setdefault(key, rec)

    def _persist(self) -> None:
        if _STORE is None:
            return
        with self._lock:
            snap = dict(self._table)
        try:
            _STORE.save(snap)
        except Exception:
            with self._lock:
                self._count("store_error")

    # -- resolution -----------------------------------------------------------

    def resolve(
        self, op: str, m: int, k: int, nnz: int, config: Any
    ) -> EngineCostModel:
        """The one entry point: analytic model unless autotune says else."""
        mode = getattr(config, "autotune", False)
        if not mode:
            return default_cost_model(n_cols=config.bn)
        self._maybe_load()
        key = table_key(op, m, k, nnz, config)
        with self._lock:
            rec = self._table.get(key)
        if rec is not None:
            with self._lock:
                self._count("table_hit")
            return self._model_from(rec, source="table")
        if mode == "offline":
            with self._lock:
                self._count("cold_miss")
            return default_cost_model(n_cols=config.bn)
        key, rec = self.build_record(op, m, k, nnz, config)
        self.adopt(key, rec)
        return self._model_from(rec, source="measured")

    def peek(self, op: str, m: int, k: int, nnz: int, config: Any):
        """The record for this problem, or None — never measures."""
        self._maybe_load()
        with self._lock:
            return self._table.get(table_key(op, m, k, nnz, config))

    def adopt(self, key: str, rec: dict) -> None:
        """Atomically publish a record (and persist the table).

        Thread-safe: the service's background tuner builds records on a
        worker thread and adopts between drains, like async compaction.
        """
        with self._lock:
            self._table[key] = rec
            self._count("measured")
        self._persist()

    def _model_from(self, rec: dict, source: str) -> TunedCostModel:
        return TunedCostModel(
            p_matrix=float(rec["p_matrix"]),
            p_vector=float(rec["p_vector"]),
            r=float(rec.get("r", 1.0)),
            n_cols=int(rec.get("n_cols", 256)),
            decisions=dict(rec.get("decisions", {})),
            key=str(rec.get("key", "")),
            source=source,
        )

    # -- measurement ----------------------------------------------------------

    def _timed(self, label: str, fn: Callable[[], Any], rec: dict) -> float:
        _MICROBENCH.inc()
        t = float(_TIMER(fn))
        rec["bench_us"][label] = t * 1e6
        return max(t, 1e-9)

    def build_record(
        self, op: str, m: int, k: int, nnz: int, config: Any
    ) -> Tuple[str, dict]:
        """Microbenchmark one shape class; returns ``(key, record)``.

        Pure with respect to the table (no adopt/persist), so the service
        can run it on a worker thread and adopt the result atomically.
        Representative shapes are clamped small: a cold tune is
        milliseconds, not a benchmark suite.
        """
        key = table_key(op, m, k, nnz, config)
        rec: dict = {
            "key": key,
            "device": device_fingerprint(),
            "op": op,
            "table_format_version": TABLE_FORMAT_VERSION,
            "bench_us": {},
            "decisions": {},
        }
        bn = int(config.bn)
        analytic = default_cost_model(n_cols=bn)

        def _r8(x: int) -> int:
            return max(8, (int(x) // 8) * 8)

        m_rep = _r8(min(max(m, 8), 256))
        k_rep = _r8(min(max(k, 8), 256))
        density = float(np.clip(nnz / max(m * k, 1), 1e-4, 0.5))
        nnz_rep = int(np.clip(int(density * m_rep * k_rep), 32, 2048))
        rec["rep"] = {"m": m_rep, "k": k_rep, "nnz": nnz_rep}

        rng = np.random.default_rng(0)
        jrows = jnp.asarray(
            np.sort(rng.integers(0, m_rep, nnz_rep)).astype(np.int32))
        jcols = jnp.asarray(rng.integers(0, k_rep, nnz_rep).astype(np.int32))
        jvals = jnp.ones(nnz_rep, jnp.float32)
        b = jnp.asarray(
            rng.standard_normal((k_rep, bn)).astype(np.float32))
        a_tile = jnp.asarray(
            rng.standard_normal((128, k_rep)).astype(np.float32))

        from ..kernels import ops as kops  # kernels sit below core

        # engine rates: dense GEMM proxies the matrix path, the XLA gather
        # proxies the vector path (relative rates are what alpha needs)
        matrix_fn = jax.jit(lambda: a_tile @ b)
        t_matrix = self._timed("matrix", matrix_fn, rec)

        def vector_fn():
            return kops.fringe_spmm(
                jrows, jcols, jvals, b, num_rows=m_rep, bn=bn, impl="xla"
            )

        t_vector = self._timed("vector", vector_fn, rec)
        rec["p_matrix"] = float(128 * k_rep) / t_matrix
        rec["p_vector"] = float(nnz_rep) / t_vector
        rec["r"] = 1.0
        rec["n_cols"] = bn

        # densify-occupancy crossover: per-slot cost of one fused
        # multi-window GEMM vs one streamed per-step tile dot.  Scales the
        # analytic 25% threshold by the measured ratio — equal throughput
        # keeps 0.25.
        a_slots = jnp.asarray(
            rng.standard_normal((8 * 128, k_rep)).astype(np.float32))
        t_slots = self._timed("densify_slots", jax.jit(lambda: a_slots @ b),
                              rec)
        t_step = self._timed("stream_step", matrix_fn, rec)
        occ = 0.25 * (t_slots / 8.0) / t_step
        rec["decisions"]["densify_occupancy"] = float(np.clip(occ, 0.05, 0.9))

        # shard-axis tolerance: rows-sharding pays LPT imbalance, rhs pays
        # the replicated-plan merge (a row gather).  Tolerated imbalance
        # grows with the relative merge cost.
        out_panel = jnp.asarray(
            rng.standard_normal((m_rep, bn)).astype(np.float32))
        perm = jnp.asarray(rng.permutation(m_rep).astype(np.int32))
        t_merge = self._timed(
            "merge", jax.jit(lambda: jnp.take(out_panel, perm, axis=0)), rec)
        thr = 1.0 + t_merge / max(t_matrix, 1e-9)
        rec["decisions"]["shard_imbalance_threshold"] = float(
            np.clip(thr, 1.05, 2.0))

        # delta-compaction budget: a vector engine measuring faster than
        # the analytic roofline tolerates a proportionally larger sidecar
        frac = DELTA_MAX_FRACTION * (rec["p_vector"] / analytic.p_vector)
        rec["decisions"]["delta_max_fraction"] = float(
            np.clip(frac, 0.05, 0.5))
        rec["decisions"]["delta_max_slowdown"] = float(DELTA_MAX_SLOWDOWN)

        if op == "sddmm":
            self._measure_sddmm(rec, rng, k_rep, m_rep, nnz_rep, config)
        else:
            self._measure_fringe(
                rec, jrows, jcols, jvals, b, m_rep, k_rep, bn, config)
            self._measure_tile_shape(rec, rng, m, k, nnz, bn, config)
        return key, rec

    def _measure_tile_shape(self, rec, rng, m, k, nnz, bn, config) -> None:
        """Sweep matrix-path ``(bm, bk)`` tile-shape candidates.

        Each candidate is timed as a short stacked tile-GEMM stream (the
        matrix path's inner shape) and priced per *expected active tile*
        at this shape class's density: larger tiles amortize per-step
        overhead but activate more padding on sparse problems.  The
        config's own shape is the baseline; a candidate must beat it past
        the hysteresis before a ``tile_shape`` decision is recorded
        (re-validated demote-only at plan-build time by
        ``TunedCostModel.tile_shape``).
        """
        density = float(np.clip(nnz / max(int(m) * int(k), 1), 1e-8, 1.0))
        base = (int(config.bm), int(config.bk))
        cands = {base}
        for bm in (128, 256):
            for bk in (32, 64, 128, 256):
                cands.add((bm, bk))
        t_tiles = 4
        bk_max = max(bk for _, bk in cands)
        b_wide = jnp.asarray(
            rng.standard_normal((bk_max, bn)).astype(np.float32))
        costs = {}
        for bm, bk in sorted(cands):
            a = jnp.asarray(
                rng.standard_normal((t_tiles, bm, bk)).astype(np.float32))
            b_blk = b_wide[:bk]
            fn = jax.jit(lambda a=a, b_blk=b_blk: jnp.einsum(
                "tmk,kn->tmn", a, b_blk,
                preferred_element_type=jnp.float32))
            t_tile = self._timed(f"tile:{bm}x{bk}", fn, rec) / t_tiles
            # expected active tiles under random placement at this density
            tiles = (-(-int(m) // bm)) * (-(-int(k) // bk))
            p_active = 1.0 - (1.0 - density) ** (bm * bk)
            costs[(bm, bk)] = t_tile * tiles * max(p_active, 1e-12)
        best = min(costs, key=costs.get)
        if best != base and costs[best] < MEASURED_HYSTERESIS * costs[base]:
            rec["decisions"]["tile_shape"] = [int(best[0]), int(best[1])]

    def _measure_fringe(
        self, rec, jrows, jcols, jvals, b, m_rep, k_rep, bn, config
    ) -> None:
        """Sweep the real fringe-tier candidates for this shape class.

        The ksharded candidates are proxied by the budget-equivalent
        chunked gather (building a k-bucketed stream host-side here would
        tune plan construction, not execution).  The analytic choice only
        loses to a strictly faster candidate (hysteresis), so the two
        XLA-identical tiers tie back to the analytic default.
        """
        from ..kernels import ops as kops

        budget = (
            FRINGE_VMEM_BUDGET if config.fringe_vmem_budget is None
            else int(config.fringe_vmem_budget)
        )
        rows_f = max(m_rep // 4, 8)
        analytic_choice = select_fringe_tier(
            k_rep, rows_f, bn, vmem_budget=budget)
        cands = []
        if fringe_resident_bytes(k_rep, rows_f, bn) <= budget:
            cands.append(("resident", 0, None))
        cap = ksharded_bk_cap(k_rep, rows_f, bn, budget)
        bks = sorted({cap, max(SUBLANES, (cap // 2 // SUBLANES) * SUBLANES)})
        for bk in bks:
            if bk:
                cands.append(("ksharded", int(bk), int(bk)))
        cands.append(("xla", 0, None))

        times = {}
        for tier, bk, chunk in cands:
            def fn(chunk=chunk):
                return kops.fringe_spmm(
                    jrows, jcols, jvals, b,
                    num_rows=m_rep, bn=bn, impl="xla", chunk=chunk,
                )
            times[(tier, bk)] = self._timed(f"fringe:{tier}:{bk}", fn, rec)
        base = times.get(analytic_choice)
        if base is None:
            base = min(times.values())
        best = min(times, key=times.get)
        if times[best] < MEASURED_HYSTERESIS * base:
            rec["decisions"]["fringe_tier"] = [best[0], int(best[1])]
        # else: analytic choice stands; no decision recorded

    def _measure_sddmm(self, rec, rng, k_rep, m_rep, nnz_rep, config) -> None:
        """Binary sddmm sweep: resident pallas gather vs XLA reference.

        Only meaningful for pallas impls (the xla impl never consults the
        tier).  The resident candidate runs on the plan's own impl, so a
        ``pallas_interpret`` plan on CPU times the interpreter and a
        measured "xla" preference there is the measurement working as
        intended.  Demote-only: a resident preference is not recorded (the
        analytic budget check already picks it when it fits).
        """
        if config.impl == "xla":
            return
        from ..kernels import ops as kops

        d = 64
        x = jnp.asarray(rng.standard_normal((m_rep, d)).astype(np.float32))
        yt = jnp.asarray(rng.standard_normal((k_rep, d)).astype(np.float32))
        srows = jnp.asarray(
            np.sort(rng.integers(0, m_rep, nnz_rep)).astype(np.int32))
        scols = jnp.asarray(rng.integers(0, k_rep, nnz_rep).astype(np.int32))
        t_res = self._timed(
            "sddmm:resident",
            lambda: kops.sddmm_gather(
                srows, scols, x, yt, impl=config.impl, tier="resident"
            ),
            rec,
        )
        t_xla = self._timed(
            "sddmm:xla",
            lambda: kops.sddmm_gather(srows, scols, x, yt, impl="xla"),
            rec,
        )
        if t_xla < MEASURED_HYSTERESIS * t_res:
            rec["decisions"]["sddmm_tier"] = "xla"

    # -- observability --------------------------------------------------------

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return {
                "tune_calls": tune_call_count(),
                "table_hits": self.table_hits,
                "cold_misses": self.cold_misses,
                "measured": self.measured,
                "store_errors": self.store_errors,
                "records": len(self._table),
            }

    def report(self) -> dict:
        with self._lock:
            records = {
                key: {
                    "op": rec.get("op"),
                    "p_matrix": rec.get("p_matrix"),
                    "p_vector": rec.get("p_vector"),
                    "decisions": dict(rec.get("decisions", {})),
                    "bench_us": dict(rec.get("bench_us", {})),
                    "rep": dict(rec.get("rep", {})),
                }
                for key, rec in self._table.items()
            }
        return {
            "device": device_fingerprint(),
            "store_installed": _STORE is not None,
            "table_format_version": TABLE_FORMAT_VERSION,
            "counters": self.counters(),
            "records": records,
        }


_TUNER = Tuner()


def get_tuner() -> Tuner:
    return _TUNER


def resolve_cost_model(
    op: str, m: int, k: int, nnz: int, config: Any
) -> EngineCostModel:
    """Module-level convenience over the process-wide tuner."""
    return _TUNER.resolve(op, m, k, nnz, config)


def tuning_report() -> dict:
    """Observability hook: device, counters, and every record's decisions."""
    return _TUNER.report()


def tuning_fallback_count() -> int:
    """Resolves that degraded to the analytic model (cold + corrupt)."""
    with _TUNER._lock:
        return _TUNER.cold_misses + _TUNER.store_errors


def reset_for_tests(keep_store: bool = False) -> None:
    """Fresh tuner state (table, counters, timer, optionally the store)."""
    global _TUNER, _STORE
    _TUNER = Tuner()
    reset_tune_call_count()
    reset_timer()
    if not keep_store:
        _STORE = None
