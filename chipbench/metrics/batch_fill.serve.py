"""Serving: real request panels over the padded batch-bucket slots the
service dispatched in the window (``service_events_total`` requests and
padded_slots), in %."""


def read(ctx):
    svc = ctx.get("service")
    if ctx.get("kind") != "open" or not svc or not svc["requests"]:
        return None
    return 100.0 * svc["requests"] / (svc["requests"] + svc["padded_slots"])
