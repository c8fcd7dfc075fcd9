"""Dispatch, Python side: median host time of the program's own
``repro.lookup`` span (validation, plan signature and leaves, health
gate, executor cache lookup) over the window's library calls, in us.

The program keeps each span's recent host durations in
``repro.obs.SPAN_TIMES``; the window's calls are the last ones a closed
mix made.  A program without that record reads nothing."""
import statistics


def read(ctx):
    if ctx.get("kind") != "closed" or not ctx.get("calls"):
        return None
    try:
        from repro.obs import SPAN_TIMES
    except ImportError:
        return None
    ns = SPAN_TIMES.durations_ns("lookup")[-ctx["calls"]:]
    return statistics.median(ns) * 1e-3 if ns else None
