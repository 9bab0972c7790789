"""Host clock around the one warm-up call of the entry (compile, or load
from the persistent compile cache, of every program the window runs)."""


def read(ctx):
    return ctx.warmup_s
