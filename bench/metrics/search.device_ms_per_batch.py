"""Device time of the beam-search program `_search_batch` in the trace,
per execution (one execution is one device batch)."""


def read(ctx):
    p = ctx.program("_search_batch")
    return None if p is None else 1e3 * p["device_s"] / p["count"]
