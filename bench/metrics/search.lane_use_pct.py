"""Share of the vmapped while_loop's lanes that did useful work: the sum of
per-query hops over the sum, over calls, of that batch's largest hop count
times its queries (exact counts from QueryStats)."""
import numpy as np


def read(ctx):
    used = lanes = 0
    for c in ctx.calls:
        hops = np.asarray(c.out["hops"], np.int64)
        used += int(hops.sum())
        lanes += int(hops.max()) * len(hops)
    return 100.0 * used / lanes if lanes else None
