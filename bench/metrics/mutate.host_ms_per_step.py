"""Host time of the mutation path per step (trace): the `bench.mutate`
spans' wall time less the device-busy time inside them, over the window's
steps. The spans cover each step and also the compaction policy's hook
after every served batch, so the hooks' host time is counted here too."""


def read(ctx):
    red = ctx.red
    if red is None or not ctx.steps \
            or not red.span_count.get("bench.mutate"):
        return None
    host = red.span_s["bench.mutate"] - red.span_busy_s.get("bench.mutate",
                                                            0.0)
    return 1e3 * host / len(ctx.steps)
