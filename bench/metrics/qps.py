"""Requests completed in the window over the window's wall seconds: from
the start of the first call to the end of the first call that ended after
--seconds (host clock)."""


def read(ctx):
    return ctx.requests / ctx.window_s
