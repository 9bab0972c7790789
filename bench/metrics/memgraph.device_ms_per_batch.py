"""Device time of the MemGraph navigation program `_beam_search_mem_batch`
in the trace, per execution."""


def read(ctx):
    p = ctx.program("_beam_search_mem_batch")
    return None if p is None else 1e3 * p["device_s"] / p["count"]
