"""Benchmark orchestrator — one module per paper table/figure.

Default is the QUICK grid (2 datasets x 3 Ls — CPU-feasible end-to-end);
set REPRO_BENCH_FULL=1 for all four datasets and the full L sweeps.
Prints `name,us_per_call,derived`-style CSV sections per module. The
kernels and roofline sections price the attached TPU, or off a TPU the
device_kind REPRO_TPU_DEVICE names (e.g. "TPU v5 lite"); without either they
fail rather than assume a chip.
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def main() -> None:
    full = os.environ.get("REPRO_BENCH_FULL", "0") == "1"
    datasets = (("sift-like", "deep-like", "spacev-like", "gist-like")
                if full else ("sift-like", "gist-like"))
    Ls = (12, 16, 24, 32, 48, 64, 96) if full else (16, 32, 64)

    from benchmarks import (breakdown, build_overhead, cache_policy,
                            combinations, concurrency,
                            io_model, kernels, latency_breakdown,
                            memory_budget, open_loop, page_size, roofline,
                            single_factor, sota)

    sections = [
        ("kernels (microbench)", lambda: kernels.main()),
        ("fig2_latency_breakdown", lambda: latency_breakdown.main(datasets)),
        ("eq1_io_model", lambda: io_model.main()),
        ("fig11-13_single_factor+table5",
         lambda: single_factor.main(datasets, Ls=Ls)),
        ("fig16-18_combinations+table7",
         lambda: combinations.main(datasets, Ls=Ls)),
        ("fig19-21_sota", lambda: sota.main(
            datasets, targets=(0.90, 0.95) if full else (0.90,))),
        ("sec8_concurrency_serving", lambda: concurrency.main(
            datasets if full else datasets[:1],
            workers=(1, 2, 4, 8, 16, 32, 64) if full else (1, 4, 16, 64))),
        ("sec8_open_loop_cache_policies", lambda: open_loop.main(
            datasets if full else datasets[:1],
            rates=((2000.0, 8000.0, 32000.0, 128000.0) if full
                   else (2000.0, 32000.0)))),
        ("fig22_breakdown", lambda: breakdown.main()),
        ("fig23_page_size", lambda: page_size.main()),
        ("fig15_memory_budget", lambda: memory_budget.main()),
        ("table6_build_overhead", lambda: build_overhead.main(
            datasets[:2])),
        ("beyond-paper: cache policy ablation",
         lambda: cache_policy.main()),
        ("roofline (from dry-run artifacts)", lambda: roofline.main([])),
    ]
    failures = 0
    for name, fn in sections:
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            fn()
            print(f"# section done in {time.time()-t0:.1f}s")
        except Exception:
            failures += 1
            traceback.print_exc()
    print(f"\nbenchmarks complete ({'full' if full else 'quick'} grid), "
          f"failures={failures}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
