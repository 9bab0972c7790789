"""Pages read per request, the paper's I/O unit (QueryStats.page_reads)."""


def read(ctx):
    return float(ctx.per_request("page_reads").mean())
