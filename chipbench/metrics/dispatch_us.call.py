"""Dispatch: median host time to enqueue one library call, before the
block on its output (benchmark span around ``repro.sparse.spmm``/``sddmm``)."""
import statistics


def read(ctx):
    if ctx.get("kind") != "closed" or not ctx.get("dispatch_us"):
        return None
    return statistics.median(ctx["dispatch_us"])
