"""Host prepare: seconds of the benchmark's span around ``from_coo`` or
``SpmmService.register`` (reorder, partition, tile stream, upload)."""


def read(ctx):
    return ctx.get("prepare_s")
