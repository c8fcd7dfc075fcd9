"""Dispatch: executor traces (``exec_traces_total``) inside the window of
a library cell.  Every shape is warmed before the window, so it reads 0."""


def read(ctx):
    if ctx.get("kind") != "closed":
        return None
    return ctx["counters"]["traces"]
