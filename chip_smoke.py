"""Serve a SIFT1M-shaped index on one TPU through AnnServer, end to end.

    python chip_smoke.py                  # on a machine with one TPU chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --n 4096 --nq 64

Phases, all in this one process (a second process could not reach the chip
this one holds):

1. Device check: a JAX platform other than "tpu" exits non-zero before
   anything compiles. Only `--rehearse` lets the CPU through.
2. Compile cache: JAX_COMPILATION_CACHE_DIR when it is set; otherwise the
   fixed directory `.jax_cache/` in this checkout (git-ignored).
3. Data from a seed: `make_dataset("sift-like", n=2**17, nq=1000, seed=0)`,
   SIFT1M's shape (128-d, uint8-valued) with 1,000 of its 10,000 queries,
   and exact brute-force ground truth. n is cut from SIFT1M's 2**20 to
   2**17: on one v5e a Vamana batch of 1,024 points takes 1.3-2.3 s, so
   the two-pass build alone would take over an hour at 2**20 and about 15
   min at 2**18, and the run has to end well inside 20 min.
4. Indexes through `build_index`: `baseline` (Vamana R=64, L_build=125),
   then `octopusann` on the same graph (adds page shuffle and MemGraph).
5. Serving through `AnnServer.serve_closed_loop(queries, workers=64)` for
   each preset: the first batch is timed as compile time, the rest as wall
   clock with the results on the host. Recall@10 against the exact ground
   truth must reach 0.90 for both presets.
6. The last line of stdout: {"ok": true, "device": {platform, kind, count}}.

Every earlier line is one JSON object with a "phase" key. Nothing is
caught: any failure exits non-zero and the last line is never printed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DATASET = "sift-like"
SIFT1M_N, SIFT1M_QUERIES = 2 ** 20, 10_000
DEFAULT_N = 2 ** 17              # the cut of SIFT1M_N; see phase 3 above
WORKERS = 64
RECALL_FLOOR = 0.90
SERVE_L = 128          # the search list for both presets (sweeps stop at 128)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=DEFAULT_N,
                    help="base vectors (SIFT1M has 2**20)")
    ap.add_argument("--nq", type=int, default=1000, help="queries served")
    ap.add_argument("--rehearse", action="store_true",
                    help="let a CPU run through the device check (a "
                         "rehearsal of the control flow, never a chip "
                         "result)")
    return ap.parse_args(argv)


def check_device(rehearse: bool):
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        sys.exit(f"chip_smoke: needs a TPU, but JAX's device is on platform "
                 f"{dev.platform!r} ({dev.device_kind}); pass --rehearse "
                 f"for a CPU rehearsal")
    return dev


def place_compile_cache() -> str:
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"] + " (environment)"
    path = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)


def emit_build_steps(preset: str, stats: dict) -> None:
    """One line per build phase (`build_s` is Vamana's own share of
    `graph_build_s`)."""
    for step, seconds in stats.items():
        if step.endswith("_s") and step != "build_s":
            emit("build", preset=preset, step=step, seconds=seconds)


def serve(preset: str, index, ds, dev) -> dict:
    from repro.core import recall_at_k
    from repro.serving import AnnServer
    server = AnnServer(index, index.cfg.replace(L=SERVE_L))
    mb = server.server_cfg.max_batch
    t0 = time.perf_counter()
    server.serve_closed_loop(ds.queries[:mb], workers=mb)   # one batch
    compile_s = time.perf_counter() - t0
    rounds = math.ceil(len(ds.queries) / WORKERS)
    t0 = time.perf_counter()
    rep = server.serve_closed_loop(ds.queries, workers=WORKERS,
                                   rounds=rounds)
    wall_s = time.perf_counter() - t0
    recall = recall_at_k(rep.stats.ids, ds.gt[rep.query_indices], 10)
    mem = dev.memory_stats() or {}
    row = {
        "preset": preset, "L": SERVE_L, "workers": WORKERS,
        "requests": int(rep.queries),
        "distinct_queries": int(len(set(rep.query_indices.tolist()))),
        "first_batch_compile_s": compile_s,
        "serve_wall_clock_s": wall_s,
        "wall_clock_qps": rep.queries / wall_s,
        "recall_at_10": recall,
        "pages_per_query": rep.pages_per_query,
        "hops_per_query": float(rep.stats.hops.mean()),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use", "not reported"),
        "modeled_qps_ssd_model": rep.qps,
    }
    emit("serve", **row)
    if recall < RECALL_FLOOR:
        raise RuntimeError(f"{preset}: Recall@10 {recall:.4f} is below "
                           f"{RECALL_FLOOR}")
    return row


def main(argv=None) -> None:
    args = parse_args(argv)
    dev = check_device(args.rehearse)
    import jax
    cache = place_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import build_index, get_preset, make_dataset, tpu_device

    peaks = (tpu_device() if dev.platform == "tpu" else None)
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), compile_cache=cache,
         rehearsal=args.rehearse,
         table_entry=peaks.name if peaks else "none (not a TPU)")
    cuts = []
    if args.n != SIFT1M_N:
        cuts.append(f"n={args.n} of SIFT1M's {SIFT1M_N} base vectors")
    cuts.append(f"{args.nq} of SIFT1M's {SIFT1M_QUERIES} queries")
    emit("config", dataset=DATASET, n=args.n, nq=args.nq, seed=0,
         vamana_R=64, vamana_L_build=125, cuts=cuts)

    t0 = time.perf_counter()
    ds = make_dataset(DATASET, n=args.n, nq=args.nq, seed=0)
    emit("data", n=ds.n, d=ds.d, nq=len(ds.queries), dtype=ds.dtype_tag,
         seconds_with_ground_truth=time.perf_counter() - t0)

    def log(msg):
        emit("build_progress", message=msg)

    base = build_index(ds, get_preset("baseline"), seed=0, log=log)
    emit_build_steps("baseline", base.build_stats)
    octo = build_index(ds, get_preset("octopusann"), seed=0,
                       graph=base.graph, medoid_id=base.medoid)
    emit_build_steps("octopusann", octo.build_stats)

    for preset, index in (("baseline", base), ("octopusann", octo)):
        serve(preset, index, ds, dev)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
