"""Device time of the flush's candidate-search program `_insert_candidates`
in the trace, per flush (the flushes of the window's steps); None when the
window flushed nothing."""


def read(ctx):
    flushes = sum(st.flushes for st in ctx.steps)
    p = ctx.program("_insert_candidates")
    if not flushes or p is None:
        return None
    return 1e3 * p["device_s"] / flushes
