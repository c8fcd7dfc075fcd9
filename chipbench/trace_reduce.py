"""From a profiler trace to the numbers the per-layer metrics read.

``load_xspace`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes
into plain events: ``{"plane", "line", "name", "start_ns", "dur_ns"}``,
keeping the device planes' operations and the benchmark's own host spans
(``chipbench.*``, written by ``jax.profiler.TraceAnnotation``).
``reduce`` works on that list alone, so it is tested without a chip, on
events made by hand (``tests/test_chipbench_trace.py``):

- busy: the union of the device-operation intervals inside the window
  (the benchmark's ``chipbench.window`` span), averaged over the devices;
- per kernel: the device time covered by the events of each named Pallas
  kernel (the names the program's jaxpr gives its ``pallas_call``\\ s; the
  compiled program names the kernel's operation ``<name>.<n>``);
- outside: the busy time that no named kernel covers (the XLA part);
- the operations that took most time, and the idle gaps of the device
  totalled by the innermost benchmark span the host was in at the time.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

DEVICE_PLANE = "/device:"
OP_LINES = ("XLA Ops",)
HOST_SPAN = "chipbench."
TOP = 10


def load_xspace(trace_dir: str) -> list:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = list(plane.lines)
        ops = {ln.name for ln in lines if ln.name in OP_LINES} or {
            ln.name for ln in lines if "Ops" in ln.name}
        for line in lines:
            if device and line.name not in ops:
                continue
            for e in line.events:
                if not device and not e.name.startswith(HOST_SPAN):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": e.name, "start_ns": e.start_ns,
                               "dur_ns": e.duration_ns})
    return events


def op_name(name: str) -> str:
    """The operation's own name in a device event's name.  On the TPU an
    event is named by its HLO instruction, ``%dense_tile_spmm.1 = f32[...]
    custom-call(...)``: the name is what stands before `` = ``, without
    the ``%``; the operands after it may name other operations."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_label(name: str) -> str:
    """A short label for the breakdown: the name and the result's shape,
    ``dense_tile_spmm.1 = f32[35968,128]``, without the layout."""
    return name.lstrip("%").split("{", 1)[0].strip()[:120]


def kernel_of(name: str, kernels) -> str | None:
    """The named kernel an operation event belongs to, or None.

    A Pallas kernel's operation carries its ``pallas_call`` name; longer
    names are tried first, so ``gather_spmm_ksharded`` is never taken for
    ``gather_spmm``."""
    op = op_name(name)
    for k in sorted(kernels, key=len, reverse=True):
        if op == k or op.startswith(k + ".") or op.startswith(k + "-"):
            return k
    return None


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(t - s for s, t in merged)


def reduce(events, kernels=(), window_span: str = HOST_SPAN + "window"):
    host = [e for e in events if not e["plane"].startswith(DEVICE_PLANE)]
    dev = [e for e in events if e["plane"].startswith(DEVICE_PLANE)]
    win = [e for e in host if e["name"] == window_span]
    if win:
        w0 = min(e["start_ns"] for e in win)
        w1 = max(e["start_ns"] + e["dur_ns"] for e in win)
    elif dev:
        w0 = min(e["start_ns"] for e in dev)
        w1 = max(e["start_ns"] + e["dur_ns"] for e in dev)
    else:
        w0 = w1 = 0.0
    spans = sorted((e for e in host if e["name"] != window_span),
                   key=lambda e: e["start_ns"])
    starts = [e["start_ns"] for e in spans]
    planes = sorted({e["plane"] for e in dev})
    n_planes = max(len(planes), 1)

    busy = 0.0
    kernel_ns = collections.Counter()
    outside = 0.0
    by_op = collections.Counter()
    gaps = collections.Counter()
    for plane in planes:
        ivs, per_kernel = [], collections.defaultdict(list)
        for e in dev:
            if e["plane"] != plane:
                continue
            s = max(e["start_ns"], w0)
            t = min(e["start_ns"] + e["dur_ns"], w1)
            if t <= s:
                continue
            ivs.append((s, t))
            k = kernel_of(e["name"], kernels)
            if k is not None:
                per_kernel[k].append((s, t))
            by_op[k or op_label(e["name"])] += t - s
        merged = _merge(ivs)
        plane_busy = _length(merged)
        busy += plane_busy
        for k, kv in per_kernel.items():
            kernel_ns[k] += _length(_merge(kv))
        # device time under no named kernel (nested or overlapping events
        # counted once)
        outside += plane_busy - _length(_merge(
            [iv for kv in per_kernel.values() for iv in kv]))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, t in zip(edges[::2], edges[1::2]):
            if t > s:
                gaps[_host_activity(spans, starts, (s + t) / 2)] += t - s
    return {
        "window_ns": float(w1 - w0),
        "busy_ns": busy / n_planes,
        "kernel_ns": {k: v / n_planes for k, v in kernel_ns.items()},
        "outside_ns": outside / n_planes,
        "devices": len(planes),
        "top_ops": [[k, v / n_planes * 1e-9]
                    for k, v in by_op.most_common(TOP)],
        "idle_gaps": [[k, v / n_planes * 1e-9]
                      for k, v in gaps.most_common(TOP)],
    }


def _host_activity(spans, starts, t: float) -> str:
    """The innermost benchmark span that holds time ``t``: the latest
    started of those that hold it (the spans nest or follow each other)."""
    j = bisect.bisect_right(starts, t) - 1
    for e in spans[max(j - 8, 0):j + 1][::-1]:
        if e["start_ns"] + e["dur_ns"] >= t:
            return e["name"]
    return "outside benchmark spans"
