"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``chipbench/``, found by the
name ``BENCHMARK.json`` gives it:

    chipbench/configs/<file named by the configuration entry>
    chipbench/traffic/<mix>.json      read by traffic.py
    chipbench/metrics/<metric>.py     ``read(ctx)`` -> a number or None
    chipbench/limits/<workload>.json  the limit of each number compared

so a later change adds a cell, a configuration, a mix or a metric by
adding files and entries, and edits none.

The timed path is the program's public entry: ``repro.sparse.from_coo``
then ``repro.sparse.spmm`` / ``sddmm`` for closed mixes, and
``SpmmService.submit`` / ``flush`` / ``fetch`` for open ones.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

import counts
import graphs
import reference
import trace_reduce
import traffic
from peaks import least_time_s

HERE = "chipbench"
SPANS = "chipbench."


class Bench:
    """BENCHMARK.json and the files it names, under one root directory."""

    def __init__(self, root):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, *parts):
        return json.loads(self.root.joinpath(*parts).read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return self._json(c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        return traffic.validate(self._json(HERE, "traffic", f"{name}.json"),
                                name)

    def limits(self, workload: str) -> dict:
        return self._json(HERE, "limits", f"{workload}.json")

    def metric_reader(self, name: str):
        path = self.root / HERE / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def metrics(self, section: str, workload: str) -> list:
        """The entries of ``section`` that this workload reports."""
        e2e = {m["name"] for m in self.spec["end_to_end"]
               if workload in m.get("workloads", [workload])}
        out = []
        for m in self.spec[section]:
            if "workloads" in m:
                if workload in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out


def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(SPANS + name)


def _counters() -> dict:
    from repro.obs import REGISTRY

    snap = REGISTRY.snapshot()

    def total(metric, pred=lambda labels: True):
        return sum(s["value"] for s in snap.get(metric, {}).get("series", ())
                   if pred(s["labels"]))

    return {
        "traces": total("exec_traces_total"),
        "dispatches": total("exec_dispatches_total"),
        "degraded": total("exec_dispatches_total",
                          lambda lb: lb["kind"].endswith(":degraded")),
        "prepares": total("core_prepares_total"),
    }


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def spmm_config(cfg: dict, impl=None):
    """The program's SpmmConfig: each key of the configuration's
    ``spmm_config`` (and ``impl``) is set only where the program still has
    that field, so a later program that derives one no longer needs it."""
    from repro.core.plan_ir import SpmmConfig

    fields = {f.name for f in dataclasses.fields(SpmmConfig)}
    kw = dict(cfg.get("spmm_config", {}))
    if impl is not None:
        kw["impl"] = impl
    return SpmmConfig(**{k: v for k, v in kw.items() if k in fields})


@dataclasses.dataclass
class Inputs:
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    shape: tuple
    width: int


def make_inputs(cfg: dict, rng) -> Inputs:
    rows, cols = graphs.structure(cfg["generator"])
    vals = rng.standard_normal(rows.size, np.float32)
    shape = (int(cfg["generator"]["m"]), int(cfg["generator"]["k"]))
    return Inputs(rows, cols, vals, shape, int(cfg["width"]))


def make_panels(key_seed: int, count: int, shapes):
    """``count`` operand panels (one array per entry of ``shapes`` each),
    made on the device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    def make(key):
        keys = jax.random.split(key, count * len(shapes))
        out, i = [], 0
        for _ in range(count):
            group = []
            for s in shapes:
                group.append(jax.random.normal(keys[i], s, jnp.float32))
                i += 1
            out.append(tuple(group))
        return tuple(out)

    panels = jax.jit(make)(jax.random.key(key_seed))
    return jax.block_until_ready(panels)


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             t0: float, impl=None) -> dict:
    """Run one cell once; returns the result object (without printing)."""
    bench = Bench(root)
    wl = bench.workload(workload)
    cfg = bench.config(wl["config"])
    mix = bench.mix(wl["traffic"])
    limits = bench.limits(workload)
    r_vals, r_key, r_traffic = (np.random.default_rng(s) for s in
                                np.random.SeedSequence(seed).spawn(3))
    inp = make_inputs(cfg, r_vals)
    key_seed = int(r_key.integers(2 ** 31))
    config = spmm_config(cfg, impl)
    if trace and mix["kind"] == "open" and "telemetry" in {
            f.name for f in dataclasses.fields(type(config))}:
        # the service's own request spans, read in the traced run only
        # (library calls stay untouched: telemetry blocks inside dispatch)
        config = dataclasses.replace(config, telemetry=True)

    runner = _closed if mix["kind"] == "closed" else _open
    run = runner(inp, config, mix, key_seed, r_traffic, seconds, trace, t0)
    device = _device_info(run)
    ctx = run.ctx
    ctx["device_kind"] = device["kind"]
    if trace:
        events = trace_reduce.load_xspace(run.trace_dir)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        red = trace_reduce.reduce(events, ctx.get("kernels", ()),
                                  window_span=SPANS + "window")
        ctx["trace"] = red
        device["busy_s"] = red["busy_ns"] * 1e-9
        device["window_s"] = red["window_ns"] * 1e-9
        if device["platform"] != "cpu":  # the CPU has no roofline
            ctx["least_s"] = {k: least_time_s(f, b, device["kind"])[0]
                              for k, (f, b) in ctx["work"].items()}
    # the reference runs on the host once the window has closed and the
    # device's peak memory has been read
    readings = reference.worst(run.answers())
    missing = run.missing
    checks = {k: {"value": readings.get(k, float("inf")), "limit": lim}
              for k, lim in limits.items()}
    correct = (not missing and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": {},
        "device": device,
    }
    if trace:
        for m in bench.metrics("per_layer", workload):
            value = bench.metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["top_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        for m in bench.metrics("end_to_end", workload):
            result["metrics"][m["name"]] = {"value": float(run.e2e[m["name"]]),
                                            "unit": m["unit"]}
    result["info"] = run.info
    if missing:
        result["info"]["missing_answers"] = missing
    result["checks"] = checks
    return result


def _device_info(run) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": run.memory_peak}


class _Run:
    """What a window leaves: metrics, per-layer context, answers to check."""

    def __init__(self):
        self.e2e, self.ctx, self.info = {}, {}, {}
        self.attempted = self.failed = self.missing = 0
        self.trace_dir = None
        self.memory_peak = 0
        self._answers = []  # (out_device, reference thunk)

    def keep(self, out, ref):
        self._answers.append((out, ref))

    def answers(self):
        got = [(np.asarray(out), ref) for out, ref in self._answers]
        self._answers = []
        return [reference.errors(out, ref()) for out, ref in got]


def _peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


@contextlib.contextmanager
def _profiled(run: _Run, on: bool):
    if not on:
        yield
        return
    import jax

    run.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(run.trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _closed(inp, config, mix, key_seed, rng, seconds, trace, t0) -> _Run:
    import jax
    import repro.sparse as sp

    run = _Run()
    tp = time.perf_counter()
    with _span(trace, "prepare"):
        a = sp.from_coo(inp.rows, inp.cols, inp.vals, inp.shape,
                        config=config)
    run.ctx["prepare_s"] = time.perf_counter() - tp
    m, k = inp.shape
    n = inp.width
    if mix["op"] == "spmm":
        shapes = [(k, n)]

        def op(p):
            return sp.spmm(a, p[0])
    else:
        shapes = [(m, n), (n, k)]

        def op(p):
            return sp.sddmm(a, p[0], p[1])
    panels = make_panels(key_seed, int(mix["panels"]), shapes)
    ahead = int(mix.get("ahead", 0))
    for p in panels:  # warm every shape the window drives
        jax.block_until_ready(op(p))
    # and as many calls on the device at once as the window keeps
    jax.block_until_ready([op(panels[i % len(panels)])
                           for i in range(ahead + 1)])
    if trace:
        run.ctx["kernels"] = reference.pallas_kernels(
            lambda *x: op(x), *panels[0])
    sampled = traffic.sample_calls(int(mix["samples"]), rng)
    c0 = _counters()
    kept, dispatch_us = {}, []
    pending = collections.deque()
    calls = 0
    with _profiled(run, trace):
        t_start = time.perf_counter()
        deadline = t_start + seconds
        with _span(trace, "window"):
            while True:
                p = calls % len(panels)
                with _span(trace, "dispatch"):
                    ta = time.perf_counter()
                    out = op(panels[p])
                    tb = time.perf_counter()
                pending.append(out)
                dispatch_us.append((tb - ta) * 1e6)
                if calls in sampled:
                    kept[calls] = (p, out)
                calls += 1
                if len(pending) > ahead:
                    with _span(trace, "block"):
                        jax.block_until_ready(pending.popleft())
                if time.perf_counter() >= deadline:
                    break
            # nothing more is sent; the window closes when every call
            # sent has finished, and all of them count
            with _span(trace, "block"):
                jax.block_until_ready(list(pending))
        t_end = time.perf_counter()
    pending.clear()
    kept[calls - 1] = (p, out)
    run.memory_peak = _peak_bytes()
    delta = _delta(_counters(), c0)
    unhealthy = reference.check_health()
    run.attempted = calls
    # a call behind a health fallback or on a degraded signature is no
    # kernel measurement: once the process has one, every call counts
    run.failed = calls if unhealthy else 0
    if unhealthy:
        run.info["unhealthy"] = unhealthy
    window_s = t_end - t_start
    run.e2e = {"setup_s": t_start - t0, "call_ms": window_s / calls * 1e3}
    run.info.update({"calls": calls, "window_s": window_s, "ahead": ahead,
                     "compared_calls": sorted(kept),
                     "plan": _plan_info(a.plan)})
    run.ctx.update({"kind": "closed", "op": mix["op"], "calls": calls,
                    "dispatch_us": dispatch_us, "counters": delta})
    if trace:
        run.ctx["work"] = _work(a.plan, inp, mix["op"])

    host = {}

    def ref_of(p):
        def thunk():
            if p not in host:
                host[p] = [np.asarray(x) for x in panels[p]]
            if mix["op"] == "spmm":
                return reference.spmm_ref(inp.rows, inp.cols, inp.vals,
                                          inp.shape, host[p][0])
            return reference.sddmm_ref(inp.rows, inp.cols, *host[p])
        return thunk

    for i in sorted(kept):
        p, out = kept[i]
        run.keep(out, ref_of(p))
    return run


def _plan_info(plan) -> dict:
    st = plan.stats_dict
    keys = ("nnz", "core_nnz", "fringe_nnz", "num_steps", "num_windows",
            "fringe_tier", "matrix_format")
    return {k: st[k] for k in keys if k in st}


def _work(plan, inp, op) -> dict:
    """(flops, bytes) per kernel path and for the whole call."""
    from repro.core.plan_ir import PATH_CORE

    core = np.asarray(plan.update_maps.path) == PATH_CORE
    r, c = inp.rows[core], inp.cols[core]
    if op == "spmm":
        return {"dense_tile_spmm": counts.spmm_path(r, c, inp.width),
                "call": counts.spmm_call(inp.rows, inp.cols, inp.shape,
                                         inp.width)}
    return {"dense_tile_sddmm": counts.sddmm_path(r, c, inp.width),
            "call": counts.sddmm_call(inp.rows, inp.cols, inp.shape,
                                      inp.width)}


def _p95(values) -> float:
    """Nearest-rank 95th percentile."""
    s = sorted(values)
    return s[max(int(np.ceil(0.95 * len(s))) - 1, 0)]


def _open(inp, config, mix, key_seed, rng, seconds, trace, t0) -> _Run:
    import jax
    import repro.sparse as sp
    from repro.obs import TRACES
    from repro.serve import SpmmService

    run = _Run()
    name = "graph"
    svc = SpmmService(config, max_batch=int(mix["max_batch"]))
    try:
        tp = time.perf_counter()
        with _span(trace, "prepare"):
            svc.register(name, inp.rows, inp.cols, inp.vals, inp.shape)
        run.ctx["prepare_s"] = time.perf_counter() - tp
        panels = make_panels(key_seed, int(mix["panels"]),
                             [(inp.shape[1], inp.width)])
        # warm every batch the window can dispatch: 1 .. max_batch
        # panels, the zero padding up to the buckets 1, 2, 4, ... with them
        for size in range(1, svc.max_batch + 1):
            tickets = [svc.submit(name, panels[i % len(panels)][0])
                       for i in range(size)]
            svc.flush()
            jax.block_until_ready([svc.fetch(t) for t in tickets])
        due = traffic.arrivals(float(mix["rate_per_s"]), seconds,
                               np.random.default_rng(int(mix["arrival_seed"])))
        n_req = due.size
        sampled = traffic.sample_requests(int(mix["samples"]), n_req, rng)
        panel_of = rng.integers(len(panels), size=n_req)
        c0 = _counters()
        s0 = (svc.stats.requests, svc.stats.padded_slots,
              svc.stats.dispatches)
        TRACES.reset()
        latency = np.full(n_req, np.inf)
        late = np.zeros(n_req)
        kept = {}
        with _profiled(run, trace):
            t_start = time.perf_counter()
            with _span(trace, "window"):
                i = 0
                open_tickets = {}
                while i < n_req or open_tickets:
                    now = time.perf_counter() - t_start
                    with _span(trace, "submit"):
                        # at most one batch goes to the service at a
                        # time; requests due meanwhile wait here, and
                        # their latency counts from when they were due
                        while (i < n_req and due[i] <= now
                               and len(open_tickets) < svc.max_batch):
                            t = svc.submit(name, panels[panel_of[i]][0])
                            open_tickets[t] = i
                            late[i] = time.perf_counter() - t_start - due[i]
                            i += 1
                    if open_tickets:
                        with _span(trace, "flush"):
                            svc.flush()
                        with _span(trace, "fetch"):
                            outs = {}
                            for t, j in open_tickets.items():
                                try:
                                    outs[j] = svc.fetch(t)
                                except Exception:  # noqa: BLE001 — missing
                                    pass
                            jax.block_until_ready(list(outs.values()))
                        done = time.perf_counter() - t_start
                        for j, out in outs.items():
                            latency[j] = done - due[j]
                            if j in sampled:
                                kept[j] = out
                        open_tickets = {}
                    elif i < n_req:
                        with _span(trace, "idle"):
                            time.sleep(max(due[i] - now, 0.0))
            t_end = time.perf_counter()
        run.memory_peak = _peak_bytes()
        delta = _delta(_counters(), c0)
        unhealthy = reference.check_health()
        never = ~np.isfinite(latency)
        # a request that never completed waited at least until the end
        latency[never] = t_end - t_start - due[never]
        run.attempted = n_req
        run.missing = int(never.sum())
        run.failed = n_req if unhealthy else run.missing
        if unhealthy:
            run.info["unhealthy"] = unhealthy
        run.e2e = {"setup_s": t_start - t0,
                   "req_p95_ms": _p95(latency) * 1e3}
        reqs = svc.stats.requests - s0[0]
        padded = svc.stats.padded_slots - s0[1]
        waits = [s["end_us"] - s["start_us"]
                 for tr in TRACES.snapshot(None)
                 for s in tr["spans"] if s["name"] == "queue_wait"]
        run.info.update({
            "requests": n_req, "window_s": t_end - t_start,
            "rate_per_s": float(mix["rate_per_s"]),
            "latency_p50_ms": float(np.median(latency)) * 1e3,
            # a backlog that grows through the window finishes after it
            "finished_after_close": int(np.sum(due + latency > seconds)),
            "generator_late_max_ms": float(late.max()) * 1e3,
            "dispatches": svc.stats.dispatches - s0[2],
            "compared_requests": sorted(kept),
            "plan": _plan_info(svc.plan(name).plan),
        })
        run.ctx.update({
            "kind": "open", "op": "spmm", "calls": n_req,
            "counters": delta,
            "service": {"queue_wait_us": waits, "requests": reqs,
                        "padded_slots": padded},
            "work": {},
        })
        if trace:
            run.ctx["kernels"] = reference.pallas_kernels(
                lambda x: sp.spmm(svc.plan(name), x), panels[0][0])
    finally:
        svc.close()

    host = {}

    def ref_of(p):
        def thunk():
            if p not in host:
                host[p] = np.asarray(panels[p][0])
            return reference.spmm_ref(inp.rows, inp.cols, inp.vals,
                                      inp.shape, host[p])
        return thunk

    for j in sorted(kept):
        run.keep(kept[j], ref_of(int(panel_of[j])))
    return run
