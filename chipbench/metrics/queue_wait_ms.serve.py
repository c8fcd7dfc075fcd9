"""Serving: median of the service's own ``queue_wait`` spans (submit to the
flush that picks the request up) over the window's requests, in ms."""
import statistics


def read(ctx):
    waits = ctx.get("service", {}).get("queue_wait_us")
    if ctx.get("kind") != "open" or not waits:
        return None
    return statistics.median(waits) * 1e-3
