"""The whole library call: the least time one call's compulsory work could
take on the chip's peaks (every nonzero once, B and C once; counts.py)
over the device's busy time per call in the trace, in %.  It bounds every
kernel's share: a kernel taken off the path leaves its own roofline
silent, and this one still reads the whole call."""


def read(ctx):
    tr = ctx.get("trace")
    least = ctx.get("least_s", {}).get("call")
    if ctx.get("kind") != "closed" or not tr or least is None:
        return None
    if not tr["busy_ns"] or not ctx["calls"]:
        return None
    return 100.0 * least / (tr["busy_ns"] * 1e-9 / ctx["calls"])
