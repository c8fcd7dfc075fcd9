"""Per-request tracing in a bounded ring, and the program's phase spans.

A :class:`Trace` is one request's life (a serving ticket, a facade
operator call); a :class:`Span` is one named phase inside it — the
serving pipeline emits ``admit -> queue_wait -> batch_assembly ->
dispatch -> block_until_ready -> fetch``.  Completed traces land in a
ring buffer (``capacity`` most recent; older requests age out, so tracing
is O(capacity) memory in a long-lived serving process, like every other
observability surface here).

Timestamps come from an injectable clock (seconds, monotonic by
convention); callers that already own an injectable clock — the serving
layer's ``self._clock`` — pass explicit timestamps instead.  Tests pin
span structure *exactly* by injecting a deterministic counter clock.

Program phase spans (:class:`span`) are the other surface: ``with
span("lookup"):`` opens the host span ``repro.lookup`` as a
``jax.profiler.TraceAnnotation``, so under a profiler it lands on the
device trace's clock beside the operations it launched, and with no
profiler running it costs about a microsecond.  Each span's host duration
also goes to :data:`SPAN_TIMES`, a bounded ring per span name, so the
recent phase times can be read without a profiler.

Host-side only: nothing here touches device state.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

DEFAULT_TRACE_CAPACITY = 512
DEFAULT_SPAN_CAPACITY = 8192

#: Prefix of every program phase span's name on the profiler's trace.
SPAN_PREFIX = "repro."


class Span:
    __slots__ = ("name", "start_us", "end_us", "attrs")

    def __init__(self, name: str, start_us: float,
                 end_us: Optional[float] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.start_us = float(start_us)
        self.end_us = None if end_us is None else float(end_us)
        self.attrs = dict(attrs or {})

    @property
    def duration_us(self) -> Optional[float]:
        if self.end_us is None:
            return None
        return self.end_us - self.start_us

    def as_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "start_us": self.start_us,
            "end_us": self.end_us,
            "duration_us": self.duration_us,
            "attrs": dict(self.attrs),
        }


class Trace:
    """One traced request; spans append in completion order."""

    __slots__ = ("trace_id", "name", "attrs", "spans", "start_us", "end_us")

    def __init__(self, trace_id: int, name: str, start_us: float,
                 attrs: Optional[Dict[str, Any]] = None):
        self.trace_id = int(trace_id)
        self.name = name
        self.attrs = dict(attrs or {})
        self.spans: List[Span] = []
        self.start_us = float(start_us)
        self.end_us: Optional[float] = None

    def span_names(self) -> List[str]:
        return [s.name for s in self.spans]

    def as_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "start_us": self.start_us,
            "end_us": self.end_us,
            "attrs": dict(self.attrs),
            "spans": [s.as_dict() for s in self.spans],
        }


class TraceStore:
    """Thread-safe ring of completed traces + span recording helpers."""

    def __init__(self, capacity: int = DEFAULT_TRACE_CAPACITY,
                 clock=time.monotonic):
        if capacity < 1:
            raise ValueError(f"trace capacity must be >= 1, got {capacity}")
        self._lock = threading.Lock()
        self._ring: "deque[Trace]" = deque(maxlen=int(capacity))
        self._next_id = 0
        self._clock = clock

    # -- clock -------------------------------------------------------------
    def set_clock(self, clock) -> None:
        """Inject a deterministic clock (seconds); tests pin span times."""
        self._clock = clock

    def clock(self) -> float:
        return self._clock()

    def now_us(self) -> float:
        return self._clock() * 1e6

    # -- trace lifecycle ---------------------------------------------------
    def begin(self, name: str, start_us: Optional[float] = None,
              **attrs: Any) -> Trace:
        """Open a trace.  Not visible in snapshots until :meth:`end`."""
        with self._lock:
            trace_id = self._next_id
            self._next_id += 1
        return Trace(
            trace_id, name,
            self.now_us() if start_us is None else start_us, attrs,
        )

    def add_span(self, trace: Trace, name: str, start_us: float,
                 end_us: float, **attrs: Any) -> Span:
        """Record a completed phase with explicit timestamps (us)."""
        span = Span(name, start_us, end_us, attrs)
        trace.spans.append(span)
        return span

    @contextmanager
    def span(self, trace: Trace, name: str, **attrs: Any) -> Iterator[Span]:
        """Measure a phase with the store clock."""
        start = self.now_us()
        span = Span(name, start, None, attrs)
        try:
            yield span
        finally:
            span.end_us = self.now_us()
            trace.spans.append(span)

    def end(self, trace: Trace, end_us: Optional[float] = None) -> None:
        """Close the trace and publish it to the ring."""
        trace.end_us = self.now_us() if end_us is None else float(end_us)
        with self._lock:
            self._ring.append(trace)

    # -- views -------------------------------------------------------------
    def recent(self, n: Optional[int] = None) -> List[Trace]:
        with self._lock:
            traces = list(self._ring)
        return traces if n is None else traces[-n:]

    def snapshot(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        return [t.as_dict() for t in self.recent(limit)]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()


#: Process-wide trace ring used by the serving layer and the facade.
TRACES = TraceStore()


class SpanTimes:
    """Host durations (ns) of the most recent ``capacity`` spans of each
    name; a deque append per span, so it is always on."""

    def __init__(self, capacity: int = DEFAULT_SPAN_CAPACITY):
        self._capacity = int(capacity)
        self._rings: Dict[str, "deque[int]"] = {}
        self._lock = threading.Lock()

    def record(self, name: str, ns: int) -> None:
        ring = self._rings.get(name)
        if ring is None:
            with self._lock:
                ring = self._rings.setdefault(
                    name, deque(maxlen=self._capacity))
        ring.append(ns)

    def durations_ns(self, name: str) -> List[int]:
        """The recorded durations of ``name``, oldest first."""
        ring = self._rings.get(name)
        return list(ring) if ring is not None else []

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Per span name: count held, median and maximum in us."""
        out = {}
        for name in sorted(self._rings):
            ns = sorted(self.durations_ns(name))
            if ns:
                out[name] = {"count": len(ns),
                             "p50_us": ns[len(ns) // 2] * 1e-3,
                             "max_us": ns[-1] * 1e-3}
        return out

    def reset(self) -> None:
        with self._lock:
            self._rings.clear()


#: Process-wide phase-span durations, filled by :class:`span`.
SPAN_TIMES = SpanTimes()


class span:
    """``with span("launch"):`` -- the program's host phase span.

    Opens ``repro.<name>`` on the profiler's trace and records its host
    duration in :data:`SPAN_TIMES`; once closed, :attr:`seconds` holds
    the same duration.  :meth:`close` ends it early (the dispatch path
    closes ``lookup`` right before it launches); the ``with`` block's own
    exit then does nothing.
    """

    __slots__ = ("name", "_annotation", "_t0", "_ns")

    def __init__(self, name: str):
        self.name = name
        self._annotation = TraceAnnotation(SPAN_PREFIX + name)
        self._t0 = 0
        self._ns = 0

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def closed(self) -> bool:
        return self._annotation is None

    @property
    def seconds(self) -> float:
        """The recorded host duration (0 while the span is open)."""
        return self._ns * 1e-9

    def close(self) -> None:
        if self._annotation is None:
            return
        self._ns = time.perf_counter_ns() - self._t0
        SPAN_TIMES.record(self.name, self._ns)
        self._annotation.__exit__(None, None, None)
        self._annotation = None
