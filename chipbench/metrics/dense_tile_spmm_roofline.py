"""Kernel ``dense_tile_spmm``: the least time one call's work on its path
could take on the chip's peaks (chipbench/counts.py) over the kernel's
device time per call (its events' summed time in the traced window over
the window's calls), in %."""


def read(ctx):
    ns = ctx.get("trace", {}).get("kernel_ns", {}).get("dense_tile_spmm")
    least = ctx.get("least_s", {}).get("dense_tile_spmm")
    if not ns or least is None or not ctx.get("calls"):
        return None
    return 100.0 * least / (ns * 1e-9 / ctx["calls"])
