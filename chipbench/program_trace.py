"""The program's own spans and stage scopes on a profiler trace.

``trace_reduce`` reads the device's operations and the benchmark's own
host spans.  This module reads what the program writes on the same trace:

- the stage scope of each device operation: the ``jax.named_scope`` names
  of the program's fused bodies (``SCOPES``), a component of the
  operation's HLO ``op_name`` metadata.  The TPU's trace does not carry
  that metadata: its events give the HLO instruction (``%fusion.3 =
  f32[169292,128]{...} fusion(...)``) and their stats only times and
  ``hlo_op``.  So ``hlo_scopes`` reads each instruction's scope from the
  compiled modules' text (an XLA dump), and ``load`` finds an event's
  module by the ``XLA Modules`` event around it on the same device;
- the program's host phase spans, ``repro.*`` (``repro.call``,
  ``repro.lookup``, ``repro.launch``, ``repro.flush``, ...).

``load`` flattens a trace into the events ``trace_reduce.load_xspace``
gives, a ``scope`` on each device event, and the ``repro.*`` host spans
besides; ``benchmark_events`` takes the extra back off, so that
``trace_reduce.reduce`` sees what it always saw.  ``reduce`` works on the
event list alone, and is tested on events made by hand
(``tests/test_chipbench_program_trace.py``):

- ``scope_ns``: device time per scope inside the window, averaged over
  the devices; ``unscoped`` is the busy time under no program scope;
- ``program_spans``: the durations of each ``repro.*`` span inside the
  window;
- ``program_gaps``: the device's idle time keyed by the path of the
  ``repro.*`` spans open at each gap's midpoint
  (``repro.flush/repro.assemble``); where none is open, by the innermost
  benchmark span, as ``trace_reduce`` keys its ``idle_gaps``;
- ``unscoped_ops``: the operations that took most of the unscoped time.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

import trace_reduce

#: The program's stage scopes (``repro.exec.pipeline.SCOPES``), copied so
#: that the yardstick does not move with the program.
SCOPES = ("b_prep", "matrix_path", "fringe_path", "merge")
UNSCOPED = "unscoped"
PROGRAM_SPAN = "repro."
OUTSIDE = "outside benchmark spans"
MODULE_LINE = "XLA Modules"
TOP = 10


def scope_in(op_name: str) -> str | None:
    """The innermost program scope among the components of an HLO
    ``op_name`` (``jit(_run)/vmap(fringe_path)/jit(fringe_spmm)/gather``);
    a transform wraps the name it maps over, as in ``vmap(...)``."""
    found = None
    for part in op_name.split("/"):
        name = part.rstrip(")").rpartition("(")[2]
        if name in SCOPES:
            found = name
    return found


def hlo_scopes(texts) -> dict:
    """``{(module, label): scope}`` for the instructions of compiled HLO
    module texts, ``label`` as ``trace_reduce.op_label`` gives it
    (``fusion.3 = f32[169292,128]``).  A label that two modules of one
    name give different scopes is left out."""
    out, clash = {}, set()
    for text in texts:
        head = re.match(r"HloModule ([^\s,]+)", text)
        if head is None:
            continue
        for line in text.splitlines():
            line = line.strip().removeprefix("ROOT ")
            if not line.startswith("%") or " = " not in line:
                continue
            op = re.search(r'op_name="([^"]*)"', line)
            key = (head.group(1), trace_reduce.op_label(line))
            scope = scope_in(op.group(1)) if op else None
            if out.get(key, scope) != scope:
                clash.add(key)
            out[key] = scope
    return {k: v for k, v in out.items() if v and k not in clash}


def _module_at(modules, t) -> str | None:
    """The module whose run on the device holds time ``t``."""
    j = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if j >= 0 and modules[j][1] >= t:
        return modules[j][2]
    return None


def load(trace_dir: str, hlo_texts=()) -> list:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: those of
    ``trace_reduce.load_xspace``, with a ``scope`` on every device event
    (None outside every program scope, or with no ``hlo_texts``), and the
    ``repro.*`` host spans.  ``hlo_texts`` are the optimized modules'
    texts that ``hlo_scopes`` reads."""
    from jax.profiler import ProfileData

    by_label = hlo_scopes(hlo_texts)

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        lines = list(plane.lines)
        ops = {ln.name for ln in lines
               if ln.name in trace_reduce.OP_LINES} or {
            ln.name for ln in lines if "Ops" in ln.name}
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns,
             e.name.split("(", 1)[0])
            for ln in lines if device and ln.name == MODULE_LINE
            for e in ln.events)
        for line in lines:
            if device and line.name not in ops:
                continue
            for e in line.events:
                ev = {"plane": plane.name, "line": line.name, "name": e.name,
                      "start_ns": e.start_ns, "dur_ns": e.duration_ns}
                if device:
                    ev["scope"] = by_label.get(
                        (_module_at(modules, e.start_ns),
                         trace_reduce.op_label(e.name)))
                elif not e.name.startswith((trace_reduce.HOST_SPAN,
                                            PROGRAM_SPAN)):
                    continue
                events.append(ev)
    return events


def benchmark_events(events) -> list:
    """The events as ``trace_reduce.load_xspace`` gives them."""
    return [{k: v for k, v in e.items() if k != "scope"} for e in events
            if not e["name"].startswith(PROGRAM_SPAN)
            or e["plane"].startswith(trace_reduce.DEVICE_PLANE)]


def _window(host, dev, window_span):
    win = [e for e in host if e["name"] == window_span]
    if win:
        return (min(e["start_ns"] for e in win),
                max(e["start_ns"] + e["dur_ns"] for e in win))
    if dev:
        return (min(e["start_ns"] for e in dev),
                max(e["start_ns"] + e["dur_ns"] for e in dev))
    return 0.0, 0.0


def reduce(events, window_span: str = trace_reduce.HOST_SPAN + "window"):
    dev = [e for e in events
           if e["plane"].startswith(trace_reduce.DEVICE_PLANE)]
    host = [e for e in events
            if not e["plane"].startswith(trace_reduce.DEVICE_PLANE)]
    w0, w1 = _window(host, dev, window_span)
    spans = [e for e in host if e["name"] != window_span]
    planes = sorted({e["plane"] for e in dev})
    n_planes = max(len(planes), 1)

    scope_ns = collections.Counter()
    unscoped_ops = collections.Counter()
    gaps = collections.Counter()
    mids = []
    for plane in planes:
        ivs, per_scope = [], collections.defaultdict(list)
        for e in dev:
            if e["plane"] != plane:
                continue
            s = max(e["start_ns"], w0)
            t = min(e["start_ns"] + e["dur_ns"], w1)
            if t <= s:
                continue
            ivs.append((s, t))
            if e.get("scope"):
                per_scope[e["scope"]].append((s, t))
            else:
                unscoped_ops[trace_reduce.op_label(e["name"])] += t - s
        merged = trace_reduce._merge(ivs)
        for scope, sv in per_scope.items():
            scope_ns[scope] += trace_reduce._length(trace_reduce._merge(sv))
        scope_ns[UNSCOPED] += trace_reduce._length(merged) - (
            trace_reduce._length(trace_reduce._merge(
                [iv for sv in per_scope.values() for iv in sv])))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        mids += [((s + t) / 2, t - s)
                 for s, t in zip(edges[::2], edges[1::2]) if t > s]
    for key, ns in zip(_host_paths(spans, [m for m, _ in mids]),
                       (ns for _, ns in mids)):
        gaps[key] += ns

    program = collections.defaultdict(list)
    for e in spans:
        if (e["name"].startswith(PROGRAM_SPAN) and e["start_ns"] >= w0
                and e["start_ns"] + e["dur_ns"] <= w1):
            program[e["name"]].append(e["dur_ns"])
    return {
        "scope_ns": {k: v / n_planes for k, v in scope_ns.items()},
        "program_spans": dict(program),
        "program_gaps": {k: v / n_planes for k, v in gaps.items()},
        "unscoped_ops": top({k: v / n_planes
                             for k, v in unscoped_ops.items()}),
    }


def top(ns_by_key: dict, scale: float = 1e-9) -> list:
    """The ``TOP`` largest entries, as ``[[key, ns * scale], ...]``."""
    return [[k, v * scale] for k, v in
            collections.Counter(ns_by_key).most_common(TOP)]


def _host_paths(spans, times) -> list:
    """For each time: the path of the program spans open then, outermost
    first, or else the innermost benchmark span open then, or else
    ``OUTSIDE``.  One sweep over the spans' starts and ends."""
    marks = []
    for i, e in enumerate(spans):
        marks.append((e["start_ns"], 0, i))
        marks.append((e["start_ns"] + e["dur_ns"], 1, i))
    marks.sort()
    order = sorted(range(len(times)), key=times.__getitem__)
    out = [OUTSIDE] * len(times)
    open_, j = {}, 0
    for k in order:
        t = times[k]
        # a span holds t when it starts at or before t and ends at or after
        while j < len(marks) and (marks[j][0] < t or (
                marks[j][0] == t and marks[j][1] == 0)):
            _, end, i = marks[j]
            if end:
                open_.pop(i, None)
            else:
                open_[i] = spans[i]
            j += 1
        held = sorted(open_.values(), key=lambda e: (e["start_ns"],
                                                     -e["dur_ns"]))
        program = [e["name"] for e in held
                   if e["name"].startswith(PROGRAM_SPAN)]
        if program:
            out[k] = "/".join(program)
        elif held:
            out[k] = held[-1]["name"]
    return out
