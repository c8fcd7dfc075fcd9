"""Fused body, XLA part: device milliseconds per library call spent in
operations outside every named Pallas kernel (today the XLA fringe, the
merge and the gathers)."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "closed" or not tr or not ctx["calls"]:
        return None
    return tr["outside_ns"] * 1e-6 / ctx["calls"]
