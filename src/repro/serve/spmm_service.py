"""Batched SpMM serving front: group per-matrix requests into one dispatch.

Serving-style SpMM traffic is many small right-hand sides against a few
long-lived sparse matrices (GNN inference over a fixed graph, repeated
feature panels).  ``SpmmService`` keeps one prepared plan per registered
matrix and drains queued requests through the batched ``core.spmm.execute``
path: each flush stacks up to ``max_batch`` panels into one ``(batch, K,
N)`` operand, padded up to a power-of-two bucket so the vmapped executor
compiles once per ``(plan signature, bucket)`` instead of once per ragged
batch size.

Dynamic graphs: every registered matrix is wrapped in a
``dynamic.DynamicPlan``, so ``update_matrix(name, delta)`` applies edge
inserts/deletes/value changes between flushes — value changes scatter into
the device-resident plan (retrace-free), structural changes ride the delta
sidecar until the cost model folds them in.  ``update_matrix`` drains that
matrix's queue first, so requests always execute against the matrix state
they were submitted under.

Async compaction: when the cost model says a sidecar should fold
(``should_compact``), the fold runs on a background worker thread against a
versioned COO snapshot while the serving path keeps executing the old plan
+ sidecar; the fresh plan swaps in atomically between drains
(``DynamicPlan.adopt_compacted``), and a swap that went stale — more
mutations landed mid-fold — is discarded and rescheduled.  Compaction never
blocks ``submit``/``flush``/``fetch``.  Set ``async_compaction=False`` for
the old synchronous inline fold.

Persistence: pass a ``dynamic.PlanRegistry`` and ``register`` warm-starts
from disk when the stored entry matches the given COO (no ``prepare()``
run); ``warm_start`` restores by name alone (sharded entries re-shard onto
``mesh``).  Updates re-persist the plan.

Multi-device deployments pass a ``ShardedPlan`` via ``register_sharded`` —
the flush path is identical because ``execute_sharded`` accepts the same
batched operand.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import spmm
from ..core import tuner as core_tuner
from ..dynamic import DynamicPlan, GraphDelta, PlanRegistry
from ..dynamic.tuning import install_registry_store
from ..errors import (
    AdmissionError, CompactionError, DeadlineExceeded, DispatchError,
    PlanBuildError, RegistryError, ReproError,
)
from ..exec.health import HEALTH
from ..kernels.ops import pow2_at_least
from ..obs import REGISTRY, TRACES, instance_label, span
from ..robust.faults import HARNESS

#: Admission policies for a full per-matrix queue (``max_queue`` set).
ADMISSION_POLICIES = ("reject", "shed-oldest")


def _compact_build(name: str, dplan: DynamicPlan, rows, cols, vals):
    """Build the folded plan for a snapshot (worker-thread seam).

    Module-level so tests can monkeypatch in a slow build and prove the
    serving path keeps draining against the old plan until the swap; the
    ``fold_build`` fault seam fires here so injected failures travel the
    real future-exception path.
    """
    HARNESS.fire("fold_build", context=name)
    return dplan.build_compacted(rows, cols, vals)


def _bucket(batch: int, max_batch: int) -> int:
    """Smallest power-of-two >= batch, capped at max_batch (itself pow2)."""
    return min(pow2_at_least(batch), max_batch)


def _plan_nnz(plan) -> int:
    """Structural nnz of any plan flavor (tuner shape-class input)."""
    stats = plan.stats_dict
    if "nnz" in stats:
        return int(stats["nnz"])
    if "shard_nnz" in stats:
        return int(sum(stats["shard_nnz"]))
    um = getattr(plan, "update_maps", None)
    return int(um.nnz) if um is not None else 0


#: Every service's lifecycle counters in one registry metric; the per-
#: ``instance`` label keeps each ``SpmmService``'s counts independent (a
#: fresh service starts from zero, as its tests expect).
_SERVICE_EVENTS = REGISTRY.counter(
    "service_events_total", "SpmmService lifecycle counters",
    labelnames=("event", "instance"), max_series=65536)


class ServiceStats:
    """Monotone serving counters, stored on the ``repro.obs`` registry.

    Call sites read and ``+=``-mutate named attributes exactly as they did
    when this was a dataclass of ints; the attributes are now views over
    ``service_events_total{event,instance}`` series, so ``health()`` / the
    Prometheus export see the same numbers with no second bookkeeping
    path.  Counters only go up — assigning a smaller value raises.
    """

    _FIELDS = (
        "requests",
        "flushes",
        "dispatches",
        "padded_slots",            # zero panels added to reach a bucket size
        "updates",                 # update_matrix calls applied
        "warm_starts",             # registrations served from the registry
        "compactions_scheduled",   # background folds submitted
        "compactions_applied",     # background folds swapped in
        "compactions_stale",       # folds discarded (snapshot went stale)
        "compactions_failed",      # folds whose build raised (fold_errors)
        "admission_rejected",      # submits refused (queue full, "reject")
        "admission_shed",          # oldest requests dropped ("shed-oldest")
        "deadline_expired",        # requests expired before their drain
        "quarantines",             # matrices quarantined (fold failures)
        "tunings_scheduled",       # background microbenchmark runs started
        "tunings_applied",         # tuned records adopted into the table
        "tunings_failed",          # background tunes whose build raised
    )

    def __init__(self) -> None:
        object.__setattr__(self, "_label", instance_label("svc"))

    def __getattr__(self, name: str) -> int:
        # only reached when normal lookup fails — i.e. for counter fields
        if name in self._FIELDS:
            return int(_SERVICE_EVENTS.value(event=name,
                                             instance=self._label))
        raise AttributeError(
            f"ServiceStats has no counter {name!r}; known: {self._FIELDS}")

    def __setattr__(self, name: str, value: int) -> None:
        if name not in self._FIELDS:
            raise AttributeError(
                f"ServiceStats has no counter {name!r}; known: "
                f"{self._FIELDS}")
        delta = int(value) - getattr(self, name)
        if delta < 0:
            raise ValueError(
                f"ServiceStats.{name} is monotone; cannot go from "
                f"{getattr(self, name)} to {value}")
        if delta:
            _SERVICE_EVENTS.inc(delta, event=name, instance=self._label)

    def as_dict(self) -> Dict[str, int]:
        return {f: getattr(self, f) for f in self._FIELDS}


class SpmmService:
    """Plan-cached, request-batching SpMM front end."""

    def __init__(self, config: spmm.SpmmConfig = spmm.SpmmConfig(),
                 max_batch: int = 8,
                 registry: Optional[PlanRegistry] = None,
                 persist_updates: bool = True,
                 async_compaction: bool = True,
                 max_queue: Optional[int] = None,
                 admission_policy: str = "reject",
                 quarantine_after: int = 3):
        if max_batch < 1:
            raise PlanBuildError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue is not None and max_queue < 1:
            raise PlanBuildError(f"max_queue must be >= 1, got {max_queue}")
        if admission_policy not in ADMISSION_POLICIES:
            raise PlanBuildError(
                f"admission_policy must be one of {ADMISSION_POLICIES}, "
                f"got {admission_policy!r}"
            )
        if quarantine_after < 1:
            raise PlanBuildError(
                f"quarantine_after must be >= 1, got {quarantine_after}")
        # measurement-backed dispatch: the serving thread never
        # microbenchmarks inline.  autotune=True is rewritten to "offline"
        # (plans read the tuned table or fall back to the analytic model)
        # and the measurements themselves run on the background worker,
        # adopted atomically between drains like compaction swaps.
        self._background_tune = config.autotune is True
        if self._background_tune:
            config = dataclasses.replace(config, autotune="offline")
        if registry is not None and config.autotune:
            install_registry_store(registry)
        self.config = config
        # registry.save serializes the whole plan (O(matrix), blocking disk
        # I/O) — durable-by-default, but heavy mutation streams over large
        # matrices can set persist_updates=False to persist only on
        # registration and compaction (when base arrays actually change)
        self.persist_updates = persist_updates
        # rounded up to a power of two: a non-pow2 cap would add itself as
        # an extra bucket size, breaking the log2(max_batch)+1 trace bound
        self.max_batch = pow2_at_least(int(max_batch))
        self.registry = registry
        self.async_compaction = bool(async_compaction)
        # bounded admission: None = unbounded (historical behavior)
        self.max_queue = max_queue
        self.admission_policy = admission_policy
        # consecutive fold-build failures before a matrix stops scheduling
        # folds (it keeps serving via its sidecar — see health())
        self.quarantine_after = quarantine_after
        self._plans: Dict[str, Any] = {}  # DynamicPlan | ShardedPlan
        # queue items: (ticket, panel, absolute-monotonic deadline | None)
        self._queues: Dict[str, List[Tuple[int, jax.Array,
                                           Optional[float]]]] = {}
        self._results: Dict[int, jax.Array] = {}
        # tickets that completed with a typed error (shed, expired) —
        # fetch() raises these instead of returning an array
        self._failed: Dict[int, ReproError] = {}
        self._next_ticket = 0
        # background folds: name -> (snapshot version, Future[plan]).
        # Workers only *build*; the swap (adopt_compacted) always runs on
        # the serving thread, between drains, under _fold_lock.
        self._folds: Dict[str, Tuple[int, Future]] = {}
        # background tunes: name -> (table key, Future[(key, record)]);
        # same build-off-thread / adopt-between-drains discipline as folds
        self._tunes: Dict[str, Tuple[str, Future]] = {}
        self._fold_errors: Dict[str, BaseException] = {}
        self._fold_failures: Dict[str, int] = {}  # consecutive, per matrix
        self._fold_lock = threading.Lock()
        self._fold_pool: Optional[ThreadPoolExecutor] = None
        self._closed = False
        # injectable monotonic clock (deadline tests pin time)
        self._clock = time.monotonic
        self.stats = ServiceStats()
        # per-request tracing (SpmmConfig.telemetry): open traces keyed by
        # ticket, published to the repro.obs ring when the request
        # completes (fetch / shed / expired).  Timestamps come from
        # self._clock, so the deadline tests' injected clock also pins
        # span structure exactly.
        self._trace_enabled = bool(getattr(config, "telemetry", False))
        self._traces: Dict[int, Any] = {}

    @property
    def _dynamic_kwargs(self) -> Dict[str, bool]:
        # with async compaction the service owns the fold lifecycle; the
        # plan must not also fold inline inside update()
        return {"auto_compact": not self.async_compaction}

    # -- matrix registration ------------------------------------------------
    def register(
        self,
        name: str,
        rows: np.ndarray,
        cols: np.ndarray,
        vals: np.ndarray,
        shape: Tuple[int, int],
    ) -> None:
        """Prepare (or restore from the registry) a plan for a matrix."""
        self._check_reregister(name)
        if self.config.reorder_cols:
            # DynamicPlan rejects reorder_cols (sidecar columns address the
            # un-permuted operand); such matrices still serve — as static
            # plans, with update_matrix unavailable
            dplan: Any = spmm.prepare(rows, cols, vals, shape, self.config)
        elif self.registry is not None:
            before = spmm.prepare_call_count()
            dplan = self.registry.load_or_prepare(
                name, rows, cols, vals, shape, self.config,
                **self._dynamic_kwargs,
            )
            if spmm.prepare_call_count() == before:
                self.stats.warm_starts += 1
        else:
            dplan = DynamicPlan(
                spmm.prepare(rows, cols, vals, shape, self.config),
                **self._dynamic_kwargs,
            )
        self._plans[name] = dplan
        self._queues.setdefault(name, [])
        self._maybe_schedule_tune(name)

    def warm_start(self, name: str, mesh=None) -> None:
        """Restore a matrix purely from the registry (no COO).

        Single-device entries restore without any ``prepare()``; sharded
        entries re-shard onto ``mesh`` (or a fresh 1-D mesh over the stored
        shard count when None) — see ``dynamic.registry``.
        """
        if self.registry is None:
            raise RegistryError("warm_start needs a service registry")
        self._check_reregister(name)
        self._plans[name] = self.registry.load(
            name, mesh=mesh, **self._dynamic_kwargs
        )
        self.stats.warm_starts += 1
        self._queues.setdefault(name, [])
        self._maybe_schedule_tune(name)

    def register_sharded(self, name: str, splan: spmm.ShardedPlan) -> None:
        """Serve a matrix through an already-prepared multi-device plan."""
        self._check_reregister(name)
        self._plans[name] = (
            DynamicPlan(splan, **self._dynamic_kwargs)
            if splan.update_maps is not None else splan
        )
        self._queues.setdefault(name, [])
        self._maybe_schedule_tune(name)

    def _check_reregister(self, name: str) -> None:
        if self._closed:
            raise AdmissionError("service is closed")
        # panels queued against the old plan's K would dispatch against the
        # new one; make the caller drain first
        if self._queues.get(name):
            raise AdmissionError(
                f"cannot re-register {name!r} with "
                f"{len(self._queues[name])} pending request(s); flush first"
            )
        # an in-flight fold built from the *old* plan must never be adopted
        # by the new one (version counters restart, so a collision could
        # pass the adopt_compacted staleness check) — discard it, along
        # with any stale recorded fold error / failure streak
        with self._fold_lock:
            stale = self._folds.pop(name, None)
            if stale is not None:
                stale[1].cancel()  # running folds finish but are orphaned
            stale_tune = self._tunes.pop(name, None)
            if stale_tune is not None:
                stale_tune[1].cancel()
            self._fold_errors.pop(name, None)
            self._fold_failures.pop(name, None)

    def plan(self, name: str):
        return self._plans[name]

    def _inner_plan(self, name: str):
        p = self._plans[name]
        return p.plan if isinstance(p, DynamicPlan) else p

    # -- dynamic updates ----------------------------------------------------
    def update_matrix(self, name: str, delta: GraphDelta) -> Dict[str, int]:
        """Apply a mutation batch to a registered matrix.

        Pending requests for that matrix are flushed first (they were
        submitted against the pre-update matrix), other queues are left
        alone, and — when a registry is attached — the updated plan state
        is re-persisted so a restart resumes from the mutated matrix.
        """
        if self._closed:
            raise AdmissionError("service is closed")
        if name not in self._plans:
            raise KeyError(f"no matrix registered under {name!r}")
        dplan = self._plans[name]
        if not isinstance(dplan, DynamicPlan):
            raise PlanBuildError(
                f"{name!r} was registered without update maps; re-register "
                "through register()/register_sharded with a maps-carrying "
                "plan to enable updates"
            )
        self.flush(name=name)
        stats = dplan.update(delta)
        self.stats.updates += 1
        if self.async_compaction:
            self._maybe_schedule_fold(name, dplan)
        if self.registry is not None and (
                self.persist_updates or stats["compacted"]):
            self.registry.save(name, dplan)
        return stats

    # -- background compaction ----------------------------------------------
    def _maybe_schedule_fold(self, name: str, dplan: DynamicPlan) -> None:
        decision = dplan.last_decision
        if decision is None or not decision.compact:
            return
        with self._fold_lock:
            if self._closed:
                return  # shutdown: never recreate the pool
            if self._fold_failures.get(name, 0) >= self.quarantine_after:
                return  # quarantined: serve via sidecar, stop folding
            if name in self._folds:
                return  # one in-flight fold per matrix
            if self._fold_pool is None:
                self._fold_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="spmm-compact"
                )
            version, rows, cols, vals = dplan.snapshot_for_compaction()
            fut = self._fold_pool.submit(
                _compact_build, name, dplan, rows, cols, vals
            )
            self._folds[name] = (version, fut)
            self.stats.compactions_scheduled += 1

    def poll_compactions(self) -> int:
        """Swap in any finished background folds; returns swaps applied.

        Runs on the serving thread (also called at every ``flush``), so the
        plan changes only between drains — never under a dispatch.  A fold
        whose snapshot went stale is discarded and rescheduled from the
        current state.  A fold whose *build* failed never aborts the poll
        (an unrelated matrix's flush must not raise another matrix's
        error): the exception is recorded per matrix — surfaced by
        ``drain_compactions`` / ``fold_errors`` — and the next
        ``update_matrix`` on that matrix schedules a fresh fold.
        """
        applied = 0
        with self._fold_lock:
            ready = [(n, v, f) for n, (v, f) in self._folds.items()
                     if f.done()]
            for n, _, _ in ready:
                del self._folds[n]
        for name, version, fut in ready:
            err = fut.exception()
            if err is not None:
                self._fold_errors[name] = err
                self.stats.compactions_failed += 1
                streak = self._fold_failures.get(name, 0) + 1
                self._fold_failures[name] = streak
                if streak == self.quarantine_after:
                    self.stats.quarantines += 1
                continue
            dplan = self._plans.get(name)
            if not isinstance(dplan, DynamicPlan):
                continue  # re-registered while folding: drop the result
            if dplan.adopt_compacted(fut.result(), expected_version=version):
                applied += 1
                self.stats.compactions_applied += 1
                self._fold_failures.pop(name, None)  # streak broken
                if self.registry is not None:
                    self.registry.save(name, dplan)
            else:
                self.stats.compactions_stale += 1
                self._maybe_schedule_fold(name, dplan)
        return applied

    def fold_errors(self) -> Dict[str, BaseException]:
        """Background-fold build failures per matrix (cleared on read)."""
        errors, self._fold_errors = self._fold_errors, {}
        return errors

    # -- background autotuning ----------------------------------------------
    def _maybe_schedule_tune(self, name: str) -> None:
        """Queue a microbenchmark pass for a cold shape class.

        Only with ``autotune=True`` (rewritten to "offline" for the
        serving-path resolves) — the measurement runs on the same
        background worker as compaction folds, and the record is adopted
        between drains by ``poll_tunings``.  Warm shape classes (already
        in the table) schedule nothing."""
        if not self._background_tune:
            return
        plan = self._inner_plan(name)
        m, k = plan.shape
        tun = core_tuner.get_tuner()
        nnz = _plan_nnz(plan)
        if tun.peek("spmm", int(m), int(k), nnz, plan.config) is not None:
            return
        with self._fold_lock:
            if self._closed:
                return
            if name in self._tunes:
                return  # one in-flight tune per matrix
            if self._fold_pool is None:
                self._fold_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="spmm-compact"
                )
            key = core_tuner.table_key(
                "spmm", int(m), int(k), nnz, plan.config)
            fut = self._fold_pool.submit(
                tun.build_record, "spmm", int(m), int(k), nnz, plan.config
            )
            self._tunes[name] = (key, fut)
            self.stats.tunings_scheduled += 1

    def poll_tunings(self) -> int:
        """Adopt any finished background tunes; returns records adopted.

        Runs on the serving thread (also at every ``flush``), mirroring
        ``poll_compactions``: the tuned table and each affected matrix's
        compaction policy change only between drains.  A failed
        measurement is counted and dropped — serving continues on the
        analytic model; it is never an error."""
        with self._fold_lock:
            ready = [(n, k, f) for n, (k, f) in self._tunes.items()
                     if f.done()]
            for n, _, _ in ready:
                del self._tunes[n]
        adopted = 0
        tun = core_tuner.get_tuner()
        for name, _, fut in ready:
            if fut.exception() is not None:
                self.stats.tunings_failed += 1
                continue
            key, rec = fut.result()
            tun.adopt(key, rec)
            adopted += 1
            self.stats.tunings_applied += 1
            dplan = self._plans.get(name)
            if isinstance(dplan, DynamicPlan):
                dplan.refresh_cost_model()
        return adopted

    def drain_tunings(self) -> int:
        """Block until every in-flight tune finished and was adopted (or
        counted as failed).  Returns records adopted.  Test helper."""
        adopted = 0
        while True:
            with self._fold_lock:
                futs = [f for _, f in self._tunes.values()]
            if not futs:
                return adopted
            for f in futs:
                f.exception()  # wait; failures surface via poll counters
            adopted += self.poll_tunings()

    def tuning_report(self) -> dict:
        """Process-wide tuner observability (device, counters, records)."""
        return core_tuner.tuning_report()

    def drain_compactions(self, timeout: Optional[float] = None) -> int:
        """Block until every in-flight fold has finished and been swapped
        in (or discarded as stale, rescheduled, and finished).  Returns the
        number of swaps applied.

        ``timeout`` is a *total* deadline across every wait (it used to be
        applied per-future, which made the total wait unbounded); expiry
        raises :class:`DeadlineExceeded`.  Build failures aggregate into
        one :class:`CompactionError` carrying every recorded error in
        ``.errors`` — no failure is silently discarded when several folds
        break in one drain.  Test/shutdown helper."""
        deadline = None if timeout is None else self._clock() + timeout
        applied = 0
        while True:
            with self._fold_lock:
                futs = [f for _, f in self._folds.values()]
            if not futs:
                errors = self.fold_errors()
                if errors:
                    summary = "; ".join(
                        f"{n}: {e}" for n, e in sorted(errors.items())
                    )
                    raise CompactionError(
                        f"{len(errors)} background fold(s) failed: "
                        f"{summary}", errors=errors,
                    )
                return applied
            for f in futs:
                remaining = None
                if deadline is not None:
                    remaining = deadline - self._clock()
                    if remaining <= 0:
                        raise DeadlineExceeded(
                            f"drain_compactions exceeded its {timeout}s "
                            f"total deadline with folds still in flight"
                        )
                try:
                    f.exception(timeout=remaining)  # wait for completion
                except _FutureTimeout:
                    raise DeadlineExceeded(
                        f"drain_compactions exceeded its {timeout}s "
                        f"total deadline with folds still in flight"
                    ) from None
            applied += self.poll_compactions()

    def close(self) -> None:
        """Shut down the service: drain in-flight folds, stop the worker.

        Idempotent, and safe against concurrent ``update_matrix`` — the
        closed flag is checked under ``_fold_lock`` in
        ``_maybe_schedule_fold``, so nothing can recreate the pool after
        shutdown.  Recorded fold errors still surface (as a
        :class:`CompactionError`) after the pool is torn down."""
        with self._fold_lock:
            if self._closed:
                return
            self._closed = True
        try:
            self.drain_tunings()
            self.drain_compactions()
        finally:
            with self._fold_lock:
                pool, self._fold_pool = self._fold_pool, None
            if pool is not None:
                pool.shutdown(wait=True)

    def __enter__(self) -> "SpmmService":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            self.close()
        except ReproError:
            # don't mask an in-flight exception with a close-time one
            if exc_type is None:
                raise
        return False

    # -- per-request tracing ------------------------------------------------
    def _now_us(self) -> float:
        return self._clock() * 1e6

    def _trace_fail(self, ticket: int, outcome: str) -> None:
        """Close a traced request that completed with a typed failure."""
        tr = self._traces.pop(ticket, None)
        if tr is None:
            return
        tr.attrs["outcome"] = outcome
        TRACES.end(tr, self._now_us())

    # -- request queue ------------------------------------------------------
    def submit(self, name: str, b: jax.Array,
               deadline: Optional[float] = None,
               timeout: Optional[float] = None) -> int:
        """Queue one (K, N) request panel; returns a result ticket.

        Everything a dispatch could reject is validated here, while the
        request is still the caller's problem — a flush-time failure would
        strand the whole batch.

        ``deadline`` (absolute, on the service's monotonic clock) or
        ``timeout`` (seconds from now) bounds how long the panel may wait:
        a request still queued past its deadline at the next drain
        completes its ticket with :class:`DeadlineExceeded` (raised by
        ``fetch``) instead of stranding the batch.  With ``max_queue``
        set, a full queue either raises :class:`AdmissionError`
        (``admission_policy="reject"``) or sheds the oldest queued request
        (``"shed-oldest"`` — the shed ticket completes with
        :class:`AdmissionError`)."""
        t_admit = self._now_us() if self._trace_enabled else 0.0
        if self._closed:
            raise AdmissionError("service is closed")
        if name not in self._plans:
            raise KeyError(f"no matrix registered under {name!r}")
        plan = self._inner_plan(name)
        k = plan.shape[1]
        if b.ndim != 2 or b.shape[0] != k:
            raise DispatchError(
                f"request for {name!r} must be (K={k}, N), got "
                f"{tuple(b.shape)}"
            )
        if (isinstance(plan, spmm.ShardedPlan) and plan.shard_axis == "rhs"
                and b.shape[1] % plan.n_shards):
            raise DispatchError(
                f"request for {name!r} needs N divisible by "
                f"n_shards={plan.n_shards} (rhs-sharded plan); got "
                f"N={b.shape[1]}"
            )
        queue = self._queues[name]
        if self.max_queue is not None and len(queue) >= self.max_queue:
            if self.admission_policy == "reject":
                self.stats.admission_rejected += 1
                raise AdmissionError(
                    f"queue for {name!r} is full "
                    f"({len(queue)}/{self.max_queue}); flush or raise "
                    f"max_queue"
                )
            shed_ticket, _, _ = queue.pop(0)  # shed-oldest
            self._failed[shed_ticket] = AdmissionError(
                f"request {shed_ticket} for {name!r} was shed to admit a "
                f"newer request (queue full at {self.max_queue})"
            )
            self.stats.admission_shed += 1
            self._trace_fail(shed_ticket, "shed")
        if timeout is not None:
            deadline = self._clock() + timeout if deadline is None else min(
                deadline, self._clock() + timeout)
        ticket = self._next_ticket
        self._next_ticket += 1
        queue.append((ticket, jnp.asarray(b), deadline))
        self.stats.requests += 1
        if self._trace_enabled:
            now = self._now_us()
            tr = TRACES.begin(
                f"spmm:{name}", start_us=t_admit,
                ticket=ticket, matrix=name, n=int(b.shape[1]),
            )
            TRACES.add_span(tr, "admit", t_admit, now, deadline=deadline)
            # queue_wait opens here and closes when flush picks the panel up
            tr.attrs["queued_us"] = now
            self._traces[ticket] = tr
        return ticket

    def pending(self, name: Optional[str] = None) -> int:
        if name is not None:
            return len(self._queues.get(name, ()))
        return sum(len(q) for q in self._queues.values())

    # -- batched execution --------------------------------------------------
    def _execute(self, name: str, plan, stacked: jax.Array) -> jax.Array:
        HARNESS.fire("dispatch", context=name)
        if isinstance(plan, DynamicPlan):
            return plan.execute(stacked)
        if isinstance(plan, spmm.ShardedPlan):
            return spmm.execute_sharded(plan, stacked)
        return spmm.execute(plan, stacked)

    def _expire_queue(self, name: str) -> None:
        """Complete overdue tickets with DeadlineExceeded, keep the rest."""
        queue = self._queues[name]
        if not any(d is not None for _, _, d in queue):
            return
        now = self._clock()
        keep: List[Tuple[int, jax.Array, Optional[float]]] = []
        for ticket, panel, d in queue:
            if d is not None and now >= d:
                self._failed[ticket] = DeadlineExceeded(
                    f"request {ticket} for {name!r} expired "
                    f"{now - d:.3f}s past its deadline before a drain"
                )
                self.stats.deadline_expired += 1
                self._trace_fail(ticket, "expired")
            else:
                keep.append((ticket, panel, d))
        queue[:] = keep

    def flush(self, name: Optional[str] = None) -> int:
        """Drain queues through batched dispatches; returns the number of
        requests completed.  ``name`` drains a single matrix's queue —
        dynamic updates to one matrix never force dispatching every queue.
        Results become available via ``fetch``.

        Requests for one matrix may carry different widths N; panels are
        grouped by shape before stacking (a mixed-width stack would raise
        mid-drain).  Requests leave the queue only after their dispatch
        succeeds, so an unexpected execute failure propagates with every
        undispatched request still queued — nothing is stranded
        result-less."""
        if name is not None and name not in self._queues:
            raise KeyError(f"no matrix registered under {name!r}")
        with span("flush"):
            return self._flush(name)

    def _flush(self, name: Optional[str]) -> int:
        if self.async_compaction:
            self.poll_compactions()  # swap finished folds in between drains
        if self._background_tune:
            self.poll_tunings()  # adopt finished tunes between drains
        selected = (
            self._queues.items() if name is None
            else [(name, self._queues[name])]
        )
        done = 0
        for qname, queue in selected:
            plan = self._plans[qname]
            # expired requests complete with DeadlineExceeded up front —
            # they never join a batch, and the batch never waits for them
            with span("expire"):
                self._expire_queue(qname)
            while queue:
                t_asm0 = self._now_us() if self._trace_enabled else 0.0
                with span("assemble"):
                    # FIFO head's shape defines this round's group
                    shape = tuple(queue[0][1].shape)
                    group = [item for item in queue
                             if tuple(item[1].shape) == shape]
                    group = group[: self.max_batch]
                    bucket = _bucket(len(group), self.max_batch)
                    panels = [b for _, b, _ in group]
                    if bucket > len(panels):
                        # pad to the bucket with zeros: one trace per bucket
                        pad = jnp.zeros_like(panels[0])
                        panels += [pad] * (bucket - len(panels))
                    stacked = jnp.stack(panels)
                t_disp0 = self._now_us() if self._trace_enabled else 0.0
                out = self._execute(qname, plan, stacked)
                t_disp1 = self._now_us() if self._trace_enabled else 0.0
                # dispatch succeeded: now dequeue and record
                dispatched = {ticket for ticket, _, _ in group}
                queue[:] = [it for it in queue if it[0] not in dispatched]
                self.stats.dispatches += 1
                self.stats.padded_slots += bucket - len(group)
                for i, (ticket, _, _) in enumerate(group):
                    self._results[ticket] = out[i]
                    if not self._trace_enabled:
                        continue
                    tr = self._traces.get(ticket)
                    if tr is None:
                        continue
                    TRACES.add_span(tr, "queue_wait",
                                    tr.attrs.get("queued_us", t_asm0),
                                    t_asm0)
                    TRACES.add_span(tr, "batch_assembly", t_asm0, t_disp0,
                                    batch=len(group), bucket=bucket)
                    TRACES.add_span(tr, "dispatch", t_disp0, t_disp1)
                done += len(group)
        self.stats.flushes += 1
        return done

    def fetch(self, ticket: int) -> jax.Array:
        """Pop a completed result (each ticket is fetchable exactly once).

        A ticket that completed with a typed failure — shed by admission
        control, or expired past its deadline — raises that
        :class:`AdmissionError` / :class:`DeadlineExceeded` here (popped
        once, like a result).  Otherwise raises a KeyError that says *why*
        the ticket has no result: never issued, still queued (flush
        first), or already fetched."""
        with span("fetch"):
            return self._fetch(ticket)

    def _fetch(self, ticket: int) -> jax.Array:
        if ticket in self._results:
            t0 = self._now_us() if self._trace_enabled else 0.0
            out = self._results.pop(ticket)
            tr = self._traces.pop(ticket, None)
            if tr is not None:
                t1 = self._now_us()
                TRACES.add_span(tr, "fetch", t0, t1)
                tr.attrs["outcome"] = "ok"
                TRACES.end(tr, t1)
            return out
        if ticket in self._failed:
            raise self._failed.pop(ticket)
        if any(t == ticket for q in self._queues.values() for t, _, _ in q):
            raise KeyError(
                f"ticket {ticket} is still queued; call flush() first"
            )
        if 0 <= ticket < self._next_ticket:
            raise KeyError(
                f"ticket {ticket} was already fetched (results pop once)"
            )
        raise KeyError(f"unknown ticket {ticket} (never issued)")

    # -- observability ------------------------------------------------------
    def _plan_sig(self, name: str):
        p = self._inner_plan(name)
        return p.sig if isinstance(p, spmm.ShardedPlan) else p.signature()

    def health(self) -> Dict[str, Any]:
        """Structured serving-health report.

        Per-matrix state ladder:

        - ``serving``     — healthy on its configured tier;
        - ``degraded``    — its executor signature is retrying or demoted
          to the XLA tier (see ``repro.exec.health``); results stay
          bit-identical, throughput drops;
        - ``quarantined`` — ``quarantine_after`` consecutive background
          fold failures: the matrix keeps serving through its sidecar but
          schedules no further folds (re-register to clear).

        Plus queue depths, in-flight folds, service counters with the
        executor health table and fault-seam counters folded in, and the
        registry's generation-fallback count when one is attached."""
        matrices: Dict[str, Dict[str, Any]] = {}
        with self._fold_lock:
            in_flight = set(self._folds)
            failures = dict(self._fold_failures)
        for name in sorted(self._plans):
            streak = failures.get(name, 0)
            if streak >= self.quarantine_after:
                state = "quarantined"
            elif HEALTH.is_degraded(self._plan_sig(name)):
                state = "degraded"
            else:
                state = "serving"
            matrices[name] = {
                "state": state,
                "queue_depth": len(self._queues.get(name, ())),
                "fold_failures": streak,
                "fold_in_flight": name in in_flight,
            }
        stats = self.stats.as_dict()
        stats.update(
            {f"executor_{k}": v for k, v in HEALTH.snapshot().items()}
        )
        stats["faults_fired"] = sum(
            HARNESS.counters()["fired"].values()
        )
        stats.update(
            {f"tuner_{k}": v
             for k, v in core_tuner.get_tuner().counters().items()}
        )
        if self.registry is not None:
            stats["registry_generation_fallbacks"] = (
                self.registry.generation_fallbacks
            )
        return {
            "closed": self._closed,
            "matrices": matrices,
            "stats": stats,
        }
