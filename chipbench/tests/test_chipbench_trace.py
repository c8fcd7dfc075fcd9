"""The reduction from trace events to per-layer numbers (trace_reduce.py)."""
import pytest

import chipbench_tiny  # noqa: F401  (CPU platform, paths)
import trace_reduce

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, name, start, dur, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_busy_union_kernels_outside_and_gaps():
    events = [
        ev(HOST, "chipbench.window", 100, 1000, "python"),
        ev(HOST, "chipbench.dispatch", 100, 150, "python"),
        ev(HOST, "chipbench.block", 250, 500, "python"),
        ev(HOST, "chipbench.dispatch", 750, 200, "python"),
        # before the window: clipped away
        ev(DEV, "fusion.1", 0, 80),
        ev(DEV, "dense_tile_spmm", 250, 200),
        ev(DEV, "gather_spmm_ksharded", 400, 100),   # overlaps the last
        ev(DEV, "fusion.7", 500, 150),
        ev(DEV, "fusion.7", 1050, 100),              # runs past the window
    ]
    kernels = ["gather_spmm", "gather_spmm_ksharded", "dense_tile_spmm"]
    red = trace_reduce.reduce(events, kernels)
    assert red["window_ns"] == 1000
    # union: [250, 650) + [1050, 1100)
    assert red["busy_ns"] == 450
    assert red["kernel_ns"] == {"dense_tile_spmm": 200,
                                "gather_spmm_ksharded": 100}
    assert red["outside_ns"] == 150 + 50
    # idle: [100, 250) in dispatch, [650, 1050) mostly in block/dispatch
    gaps = dict(red["idle_gaps"])
    assert gaps["chipbench.dispatch"] == pytest.approx((150 + 400) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(550e-9)
    assert red["top_ops"][0] == ["dense_tile_spmm", pytest.approx(200e-9)]


def test_kernel_names_match_whole_names_only():
    ks = ["gather_spmm", "gather_spmm_ksharded"]
    assert trace_reduce.kernel_of("gather_spmm_ksharded", ks) == \
        "gather_spmm_ksharded"
    assert trace_reduce.kernel_of("gather_spmm.3", ks) == "gather_spmm"
    assert trace_reduce.kernel_of("fusion.gather_spmm", ks) is None
    # the TPU names an event by its HLO instruction; operands do not count
    tpu = ("%gather_spmm_ksharded.1 = f32[1224,128]{1,0:T(8,128)S(1)} "
           "custom-call(s32[48]{0:T(128)S(1)} %copy-done.7)")
    assert trace_reduce.kernel_of(tpu, ks) == "gather_spmm_ksharded"
    assert trace_reduce.kernel_of(
        "%fusion = f32[36000,128]{1,0:T(8,128)S(1)} fusion(f32[35968,128]"
        "{1,0:T(8,128)S(1)} %gather_spmm.1)", ks) is None
    assert trace_reduce.op_label(tpu) == \
        "gather_spmm_ksharded.1 = f32[1224,128]"


def test_devices_are_averaged():
    events = [ev(HOST, "chipbench.window", 0, 100, "python"),
              ev("/device:TPU:0", "k", 0, 100),
              ev("/device:TPU:1", "k", 0, 50)]
    red = trace_reduce.reduce(events, ["k"])
    assert red["devices"] == 2
    assert red["busy_ns"] == 75 and red["kernel_ns"] == {"k": 75}


def test_loader_reads_a_profiler_trace(tmp_path):
    """``load_xspace`` on a real ``.xplane.pb`` written here on the CPU: the
    benchmark's host spans come through (the CPU has no device plane)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                    y = f(x)
                y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = trace_reduce.load_xspace(str(tmp_path))
    names = sorted(e["name"] for e in events)
    assert names == ["chipbench.dispatch"] * 2 + ["chipbench.window"]
    red = trace_reduce.reduce(events)
    assert red["devices"] == 0 and red["busy_ns"] == 0
    assert red["window_ns"] > 0


def test_readers_on_a_reduced_trace_of_several_calls():
    """Each device reader on a window of four calls, against numbers worked
    out by hand: a roofline divides one call's least time by the device
    time per call, never by the window's total."""
    import harness

    events = [ev(HOST, "chipbench.window", 0, 2000, "python")]
    for i in range(4):  # per call: 100 ns in the kernel, 300 ns outside
        events += [ev(DEV, "dense_tile_spmm.1", 500 * i, 100),
                   ev(DEV, "fusion.3", 500 * i + 100, 300)]
    ctx = {"kind": "closed", "calls": 4,
           "trace": trace_reduce.reduce(events, ["dense_tile_spmm"]),
           "least_s": {"dense_tile_spmm": 50e-9, "call": 200e-9}}
    bench = harness.Bench(chipbench_tiny.BENCH.parent)

    def read(name):
        return bench.metric_reader(name)(ctx)

    assert read("dense_tile_spmm_roofline") == pytest.approx(50.0)
    assert read("call_roofline") == pytest.approx(50.0)
    assert read("xla_ms.call") == pytest.approx(300e-6)
    assert read("device_idle.call") == pytest.approx(20.0)
    # no sddmm kernel in this trace: that reader finds nothing, never 0
    assert read("dense_tile_sddmm_roofline") is None
    ctx["trace"]["kernel_ns"]["dense_tile_sddmm"] = 4 * 80.0
    ctx["least_s"]["dense_tile_sddmm"] = 20e-9
    assert read("dense_tile_sddmm_roofline") == pytest.approx(25.0)
