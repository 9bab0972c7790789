"""Process start to the window's start: data, index build, warm-up."""


def read(ctx):
    return ctx.setup_s
