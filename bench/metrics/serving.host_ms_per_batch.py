"""Host time per device batch inside the entry calls: the `bench.entry`
spans' wall time less the device-busy time inside them, per call (trace)."""


def read(ctx):
    red = ctx.red
    if red is None or not red.span_count.get("bench.entry"):
        return None
    host = red.span_s["bench.entry"] - red.span_busy_s.get("bench.entry", 0)
    return 1e3 * host / red.span_count["bench.entry"]
