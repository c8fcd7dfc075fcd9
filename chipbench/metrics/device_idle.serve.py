"""Device, serving cells: 1 - (union of device-op intervals / traced
window), in %."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "open" or not tr or not tr["window_ns"]:
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / tr["window_ns"])
