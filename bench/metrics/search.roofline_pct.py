"""The beam search's share of its roofline: the least time the chip could
take for the work the algorithm needs, over the device time of
`_search_batch` in the trace.

Work, from the kernel's exact counts (QueryStats) over the traced window:
bytes = page_reads * page_bytes + pq_evals * pq_m (pages read, PQ codes
read); operations = full_evals * 3d + pq_evals * pq_m (a subtract, a
multiply and an add per dimension of an exact distance, one add per PQ
sub-quantizer). The least time is the larger of bytes over the HBM
bandwidth and operations over the peak rate (bench/peaks.json)."""


def bounds(ctx):
    """(memory-bound seconds, compute-bound seconds)."""
    d = ctx.config["dataset"]["d"]
    pages = ctx.per_request("page_reads").sum()
    pq = ctx.per_request("pq_evals").sum()
    full = ctx.per_request("full_evals").sum()
    nbytes = pages * ctx.page_bytes + pq * ctx.pq_m
    ops = full * 3 * d + pq * ctx.pq_m
    return (nbytes / ctx.peaks["hbm_bytes_per_s"],
            ops / ctx.peaks["bf16_flops_per_s"])


def read(ctx):
    p = ctx.program("_search_batch")
    if p is None or ctx.peaks is None:
        return None
    return 100.0 * max(bounds(ctx)) / p["device_s"]
