"""§Fused pipeline: MEASURED wall clock of the fused double-buffered beam
kernel vs. the two separately-jitted calls it replaces — the bridge between
the repo's two latency worlds (the Pallas kernels and the analytic SSD/HBM
model, which until now only met through `SSDModel`'s overlap rebate).

Part 1 — kernel sweep (synthetic shapes): beam width x page size x
LAANN-style look-ahead depth. Each cell builds the hop-major page schedule
a pipelined beam search issues (width confirmed pages per hop + `lookahead`
speculative pages staged from the frontier) and times
  fused   : kernels.fused_page_rank — ONE grid; the DMA of step i+1's
            vector+code tiles is double-buffered behind step i's fused
            exact-scan + ADC compute
  unfused : kernels.page_scan then kernels.page_adc — the same tiles
            through two separately-jitted grids, back to back
reporting per-hop step wall clock, the ACHIEVED overlap ratio
(1 - fused/unfused) next to the ANALYTIC rebate the device model would
grant the same shape (0.9 * min(io, compute) / (io + compute), the
`pipeline=True` term priced on the shared TPU device table), and
pages/query.

Part 2 — search path at the default shape: a real index searched with
pipeline=True vs pipeline="fused"; results must be bit-identical, and the
fused schedule must beat the split execution of the SAME traced schedule.

Wall clock here is interpret-mode (this container has no TPU): the kernel
bodies run as Python/jnp per grid step, so the ABSOLUTE numbers are not
device times — but fused and unfused pay the same interpreter tax per
step, so the ratio (and the fused-not-slower guard) is meaningful, and on
a TPU backend the same script times the compiled kernels unchanged.

Env: REPRO_FP_WIDTHS / REPRO_FP_NP / REPRO_FP_LOOKAHEAD (sweep axes),
REPRO_FP_HOPS / REPRO_FP_QUERIES (shape), REPRO_FP_GUARD=1 (assert fused
<= unfused * REPRO_FP_SLACK at the default shape — the CI smoke guard).
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import get_preset, tpu_device
from repro.core.search_kernel import measure_step_us
from repro.kernels import fused_page_rank, page_adc, page_scan

D = 128
M = 16
N_PAGES = 512

WIDTHS = [int(x) for x in
          os.environ.get("REPRO_FP_WIDTHS", "4,8,16").split(",")]
PAGE_NP = [int(x) for x in os.environ.get("REPRO_FP_NP", "8,16").split(",")]
LOOKAHEAD = [int(x) for x in
             os.environ.get("REPRO_FP_LOOKAHEAD", "0,2,4").split(",")]
HOPS = int(os.environ.get("REPRO_FP_HOPS", 8))
QUERIES = int(os.environ.get("REPRO_FP_QUERIES", 32))
DEFAULT = (8, 8, 2)          # (width, n_p, lookahead) — the guarded cell


def _time_us(fn, iters=3):
    jax.block_until_ready(fn())          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters * 1e6


def analytic_overlap(dev, pages: int, n_p: int, q: int) -> dict:
    """The rebate the device model's pipeline term grants this shape on the
    shared TPU table: io/compute priced at peak, overlapped execution
    max(io, c) + 0.1 * min(io, c) vs sequential io + c."""
    bytes_moved = pages * n_p * (D * 4 + M)          # vector + code tiles
    flops = pages * n_p * q * 2 * (D + 256 * M)      # exact + one-hot ADC
    t_io = dev.memory_s(bytes_moved)
    t_c = dev.compute_s(flops)
    seq = t_io + t_c
    piped = max(t_io, t_c) + 0.1 * min(t_io, t_c)
    return {"t_io_us": t_io * 1e6, "t_compute_us": t_c * 1e6,
            "overlap": (seq - piped) / seq if seq else 0.0}


def kernel_sweep():
    dev = tpu_device(os.environ.get("REPRO_TPU_DEVICE"))
    rng = np.random.default_rng(0)
    rows = []
    for n_p in PAGE_NP:
        pages = jnp.asarray(
            rng.normal(size=(N_PAGES, n_p, D)).astype(np.float32))
        codes = jnp.asarray(
            rng.integers(0, 256, (N_PAGES, n_p, M)).astype(np.uint8))
        q = jnp.asarray(rng.normal(size=(QUERIES, D)).astype(np.float32))
        lut = jnp.asarray(
            (rng.normal(size=(QUERIES, M, 256)) ** 2).astype(np.float32))
        for w in WIDTHS:
            for la in LOOKAHEAD:
                per_hop = w + la
                sched = jnp.asarray(rng.integers(
                    0, N_PAGES, HOPS * per_hop).astype(np.int32))
                fused_us = _time_us(
                    lambda: fused_page_rank(pages, codes, sched, q, lut))
                unfused_us = _time_us(
                    lambda: (page_scan(pages, sched, q),
                             page_adc(codes, sched, lut)))
                ana = analytic_overlap(dev, HOPS * per_hop, n_p, QUERIES)
                rows.append({
                    "width": w, "n_p": n_p, "lookahead": la,
                    "hops": HOPS, "pages_per_query": round(
                        HOPS * per_hop / QUERIES, 2),
                    "fused_step_us": round(fused_us / HOPS, 1),
                    "unfused_step_us": round(unfused_us / HOPS, 1),
                    "measured_overlap": round(1.0 - fused_us / unfused_us, 4),
                    "analytic_overlap": round(ana["overlap"], 4),
                    f"{dev.name}_io_us": round(ana["t_io_us"], 3),
                    f"{dev.name}_compute_us": round(ana["t_compute_us"], 3),
                })
    return rows


def search_path_check():
    """The default shape through the REAL search path: bit-identical
    results, measured fused vs split wall clock of the traced schedule."""
    from benchmarks.common import dataset, index
    ds = dataset("deep-like")
    idx = index("deep-like", "pipeline")
    cfg = get_preset("pipeline", L=48)
    r_model = idx.search(ds.queries, cfg)
    r_fused = idx.search(ds.queries, cfg.replace(pipeline="fused"))
    assert np.array_equal(r_model.ids, r_fused.ids), \
        "pipeline='fused' changed search results — the fused kernel is a " \
        "measurement surface and must not touch the result path"
    # re-time both executions of the SAME traced schedule
    store = idx.page_store(use_cache=False)
    from repro.core.search_kernel import search_batched
    st = search_batched(store, idx.pq, cfg, ds.queries[:QUERIES],
                        medoid=idx.medoid, collect_visited=False,
                        collect_trace=True, account_kernel_io=False)
    fused = measure_step_us(store, idx.pq, ds.queries[:QUERIES],
                            st.page_trace, mode="fused")
    split = measure_step_us(store, idx.pq, ds.queries[:QUERIES],
                            st.page_trace, mode="split")
    return {
        "pages_per_query": round(float(r_fused.page_reads.mean()), 2),
        "modeled_mean_latency_us": round(float(
            r_fused.summary(_ssd_model(), d=ds.d, pq_m=cfg.pq_m,
                            page_bytes=cfg.page_bytes,
                            pipeline=True)["mean_latency_us"]), 1),
        "measured_step_us_per_query": round(
            float(r_fused.measured_step_us.mean()), 1),
        "fused_wall_us": round(fused["wall_us"], 1),
        "unfused_wall_us": round(split["wall_us"], 1),
        "schedule_pages": fused["pages"],
        "measured_overlap": round(
            1.0 - fused["wall_us"] / split["wall_us"], 4)
        if split["wall_us"] else 0.0,
    }


def _ssd_model():
    from benchmarks.common import MODEL
    return MODEL


def main(argv=None):
    rows = kernel_sweep()
    cols = list(rows[0])
    print("== fused pipeline (kernel sweep: width x page size x "
          "look-ahead) ==")
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r[c]) for c in cols))

    check = search_path_check()
    print("== fused pipeline (search path, default shape) ==")
    print(",".join(check))
    print(",".join(str(v) for v in check.values()))

    dw, dnp, dla = DEFAULT
    cell = next((r for r in rows
                 if (r["width"], r["n_p"], r["lookahead"]) == (dw, dnp, dla)),
                rows[0])
    faster = cell["fused_step_us"] < cell["unfused_step_us"]
    print(f"default shape w={cell['width']} n_p={cell['n_p']} "
          f"lookahead={cell['lookahead']}: fused "
          f"{'FASTER' if faster else 'SLOWER'} "
          f"({cell['fused_step_us']} vs {cell['unfused_step_us']} us/step, "
          f"measured overlap {cell['measured_overlap']}, "
          f"analytic {cell['analytic_overlap']})")
    if os.environ.get("REPRO_FP_GUARD"):
        slack = float(os.environ.get("REPRO_FP_SLACK", 1.25))
        assert cell["fused_step_us"] <= cell["unfused_step_us"] * slack, (
            f"wall-clock smoke guard: fused step "
            f"{cell['fused_step_us']}us exceeds unfused "
            f"{cell['unfused_step_us']}us x {slack} slack")
        assert check["fused_wall_us"] <= check["unfused_wall_us"] * slack, (
            f"wall-clock smoke guard (search path): fused "
            f"{check['fused_wall_us']}us exceeds unfused "
            f"{check['unfused_wall_us']}us x {slack} slack")
        print(f"guard OK (slack {slack})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
