"""The program's spans and scopes on a trace (program_trace.py), and the
readers of the program's own span record."""
import pytest

import chipbench_tiny  # noqa: F401  (CPU platform, paths)
from chipbench_tiny import tiny_root  # noqa: F401
import program_trace
import trace_reduce

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, name, start, dur, line="XLA Ops", scope=None):
    e = {"plane": plane, "line": line, "name": name, "start_ns": start,
         "dur_ns": dur}
    if plane.startswith("/device:"):
        e["scope"] = scope
    return e


def host(name, start, dur):
    return ev(HOST, name, start, dur, "python")


def test_scope_names_from_op_names():
    assert program_trace.scope_in(
        "jit(_run)/vmap(fringe_path)/jit(fringe_spmm)/gather") == \
        "fringe_path"
    assert program_trace.scope_in(
        "jit(_run)/matrix_path/jit(dense_tile_spmm)/pallas_call") == \
        "matrix_path"
    assert program_trace.scope_in("jit(f)/merge_rows/add") is None
    assert program_trace.scope_in("jit(f)/add") is None
    assert program_trace.scope_in("flat_values") is None


def test_hlo_scopes_of_a_compiled_fused_body():
    """Each instruction of the compiled executor's text, by the label a
    TPU trace event gives it, maps to its stage; a label two modules of
    one name scope differently maps to none."""
    import jax
    import jax.numpy as jnp

    def _run(x, idx):
        with jax.named_scope("b_prep"):
            y = jnp.pad(x, ((0, 8), (0, 0)))
        with jax.named_scope("fringe_path"):
            z = jnp.sin(y)[idx]
        with jax.named_scope("merge"):
            return z * 2.0

    text = jax.jit(_run).lower(jnp.ones((64, 8)),
                               jnp.arange(16)).compile().as_text()
    scopes = program_trace.hlo_scopes([text])
    assert {m for m, _ in scopes} == {"jit__run"}
    assert set(scopes.values()) <= {"b_prep", "fringe_path", "merge"}
    assert "merge" in scopes.values()
    label = next(k for k, v in scopes.items() if v == "merge")[1]
    assert " = f32[" in label and "{" not in label
    other = text.replace('merge/', 'fringe_path/')
    both = program_trace.hlo_scopes([text, other])
    assert ("jit__run", label) not in both
    modules = [(0, 100, "jit__run"), (200, 300, "jit_make")]
    assert program_trace._module_at(modules, 50) == "jit__run"
    assert program_trace._module_at(modules, 250) == "jit_make"
    assert program_trace._module_at(modules, 150) is None


def test_scope_ns_and_unscoped():
    events = [
        host("chipbench.window", 0, 1000),
        ev(DEV, "fusion.1", -100, 150, scope="b_prep"),      # clipped: 50
        ev(DEV, "dense_tile_spmm.1", 50, 200, scope="matrix_path"),
        ev(DEV, "fusion.3", 250, 400, scope="fringe_path"),
        ev(DEV, "sort.2", 300, 100, scope="fringe_path"),    # nested: once
        ev(DEV, "fusion.5", 650, 50, scope="merge"),
        ev(DEV, "copy.1", 700, 100),                         # no scope
        ev(DEV, "copy.1", 900, 50),
        ev(DEV, "fusion.9", 1200, 50, scope="merge"),        # after
    ]
    red = program_trace.reduce(events)
    assert red["scope_ns"] == {"b_prep": 50, "matrix_path": 200,
                               "fringe_path": 400, "merge": 50,
                               "unscoped": 150}
    busy = trace_reduce.reduce(program_trace.benchmark_events(events))
    assert sum(red["scope_ns"].values()) == busy["busy_ns"] == 850
    assert red["unscoped_ops"] == [["copy.1", pytest.approx(150e-9)]]
    # two devices: each scope is averaged over them
    events.append(ev("/device:TPU:1", "dense_tile_spmm.1", 0, 100,
                     scope="matrix_path"))
    assert program_trace.reduce(events)["scope_ns"]["matrix_path"] == 150


GAPS = {
    # the device idles while the host is inside flush's assemble
    "nested": ([host("repro.flush", 100, 400),
                host("repro.assemble", 150, 200)],
               "repro.flush/repro.assemble"),
    # lookup has ended, launch is open: siblings never join the path
    "sibling": ([host("repro.call", 100, 400),
                 host("repro.lookup", 110, 100),
                 host("repro.launch", 210, 200)],
                "repro.call/repro.launch"),
    # no program span open: the innermost benchmark span, as idle_gaps
    "none": ([host("chipbench.block", 100, 400),
              host("repro.call", 100, 50)],
             "chipbench.block"),
}


@pytest.mark.parametrize("case", sorted(GAPS))
def test_program_gaps_paths(case):
    spans, key = GAPS[case]
    events = [host("chipbench.window", 0, 1000),
              ev(DEV, "fusion.1", 0, 250), ev(DEV, "fusion.2", 350, 650)
              ] + spans
    # one idle gap, [250, 350), midpoint 300
    red = program_trace.reduce(events)
    assert red["program_gaps"] == {key: 100}
    # the benchmark's own key for the same gap is unchanged
    old = trace_reduce.reduce(program_trace.benchmark_events(events))
    assert [k for k, _ in old["idle_gaps"]] == [
        "chipbench.block" if case == "none" else "outside benchmark spans"]


def test_program_spans_inside_the_window():
    events = [host("chipbench.window", 100, 1000),
              host("repro.lookup", 50, 20),        # before the window
              host("repro.lookup", 200, 30), host("repro.launch", 230, 40),
              host("repro.lookup", 400, 10), host("repro.launch", 410, 60)]
    red = program_trace.reduce(events)
    assert red["program_spans"] == {"repro.lookup": [30, 10],
                                    "repro.launch": [40, 60]}
    assert red["program_gaps"] == {} and red["scope_ns"] == {}


def test_reduce_keys_unchanged_by_program_events():
    """``trace_reduce.reduce`` reads the same events, so every key it had
    keeps its value once the program's spans and scopes are on the trace."""
    base = [host("chipbench.window", 0, 2000),
            host("chipbench.dispatch", 0, 100),
            host("chipbench.block", 100, 1500)]
    for i in range(4):
        base += [ev(DEV, "dense_tile_spmm.1", 100 + 450 * i, 100),
                 ev(DEV, "fusion.3", 200 + 450 * i, 300)]
    scopes = ["matrix_path", "fringe_path"] * 4
    with_program = [dict(e, scope=scopes.pop(0)) if "scope" in e else e
                    for e in base]
    with_program += [host("repro.call", 10, 80), host("repro.lookup", 12, 40),
                     host("repro.launch", 55, 30)]
    kernels = ["dense_tile_spmm"]
    assert trace_reduce.reduce(
        program_trace.benchmark_events(with_program), kernels) == \
        trace_reduce.reduce(program_trace.benchmark_events(base), kernels)


def test_load_reads_the_program_spans_of_a_cpu_trace(tmp_path):
    """``load`` on a real ``.xplane.pb`` written here on the CPU around a
    library call and a served request: the program's spans come through,
    and what the benchmark's own loader reads is unchanged."""
    import jax
    import numpy as np

    import repro.sparse as sp
    from repro.serve import SpmmService

    rng = np.random.default_rng(3)
    rows = rng.integers(0, 64, 400)
    cols = rng.integers(0, 48, 400)
    vals = rng.standard_normal(400).astype(np.float32)
    a = sp.from_coo(rows, cols, vals, (64, 48), impl="xla")
    b = rng.standard_normal((48, 8)).astype(np.float32)
    svc = SpmmService(a.plan.config, max_batch=2)
    svc.register("g", rows, cols, vals, (64, 48))
    jax.block_until_ready(sp.spmm(a, b))
    t = svc.submit("g", b)
    svc.flush()
    svc.fetch(t)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            jax.block_until_ready(sp.spmm(a, b))
            t = svc.submit("g", b)
            with jax.profiler.TraceAnnotation("chipbench.flush"):
                svc.flush()
            jax.block_until_ready(svc.fetch(t))
    finally:
        jax.profiler.stop_trace()
        svc.close()
    events = program_trace.load(str(tmp_path))
    names = {e["name"] for e in events}
    assert {"repro.call", "repro.lookup", "repro.launch", "repro.flush",
            "repro.expire", "repro.assemble", "repro.fetch"} <= names
    assert program_trace.benchmark_events(events) == \
        trace_reduce.load_xspace(str(tmp_path))
    spans = program_trace.reduce(events)["program_spans"]
    # one library call and one served batch: two lookups and launches
    assert len(spans["repro.lookup"]) == len(spans["repro.launch"]) == 2
    assert len(spans["repro.call"]) == len(spans["repro.flush"]) == 1


@pytest.mark.parametrize("name,span", [("lookup_us.call", "lookup"),
                                       ("launch_us.call", "launch")])
def test_span_readers_on_a_window_of_several_calls(name, span):
    """Each reader takes the median of the window's calls, the last ones
    the program recorded: warm-up calls before them do not count."""
    import harness
    from repro.obs import SPAN_TIMES

    read = harness.Bench(chipbench_tiny.BENCH.parent).metric_reader(name)
    SPAN_TIMES.reset()
    assert read({"kind": "closed", "calls": 4}) is None
    for ns in (9_000_000, 8_000_000, 3_000, 1_000, 4_000, 2_000):
        SPAN_TIMES.record(span, ns)
    assert read({"kind": "closed", "calls": 4}) == pytest.approx(2.5)
    assert read({"kind": "closed", "calls": 5}) == pytest.approx(3.0)
    assert read({"kind": "open", "calls": 4}) is None
    SPAN_TIMES.reset()


@pytest.mark.parametrize("workload", ["tiny.spmm", "tiny.serve"])
def test_scopes_tool_reads_a_traced_tiny_cell(tiny_root, workload):
    """``scopes.read_cell`` runs a cell traced and reads the same trace a
    second time; the CPU has no device plane, so only the program's spans
    and the window's own end-to-end number are there."""
    import scopes

    out = scopes.read_cell(tiny_root, workload, 2**31 + 5, 0.2,
                           impl="pallas_interpret")
    assert out["correct"] is True
    spans = out["span_count"]
    if workload == "tiny.spmm":
        assert spans["repro.call"] == spans["repro.lookup"] == out["calls"]
        assert out["traced_call_ms"] > 0 and out["traced_req_p95_ms"] is None
        assert {"lookup_us.call", "launch_us.call"} <= set(out["metrics"])
    else:
        assert spans["repro.flush"] >= 1 and spans["repro.assemble"] >= 1
        assert out["traced_req_p95_ms"] > 0
    assert out["scope_sum_over_busy"] is None  # no device time on the CPU
