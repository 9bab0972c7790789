"""The readings that L and the correctness limits are set from, on the chip.

In one process (the index build is most of a run): build the
configuration's index once, from its `data_seed`, then drive the timed path
for a window at each cell's own load, exactly as bench/run.py does, once
for each order seed, and read the numbers that bench/checks.py compares
twice:

- program: the answers the entry served;
- control: the reference computed in bfloat16 (reference.control_topk), put
  in the program's place for the same requests. It has to fail.

    python3 bench/control.py --config deep-baseline --seeds 11,12,13 \
        --seconds 10 [--ladder 24,32 --ladder-seeds 2] \
        [--witness-data-seeds 1,2] > chiprun_out/control.jsonl

`--ladder` first reads the first closed-loop cell at each search list L on
the first `--ladder-seeds` seeds, and reads every seed at the smallest L at
which each of those reads Recall@10 >= `--recall-target`, in place of the
configuration's L. `--witness-data-seeds` then builds the index again from
each of those data seeds and reads the first two order seeds there: the same
limits on other data. Every line of stdout is one JSON object. The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from bench import run as harness  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw, default=float), flush=True)


def readings(base, pool, calls, guarantees):
    import numpy as np
    from bench import checks, reference
    rows = np.concatenate([c.pool_idx for c in calls])
    ids = np.concatenate([c.out["ids"] for c in calls])
    dists = np.concatenate([c.out["dists"] for c in calls])
    uniq, inv = np.unique(rows, return_inverse=True)
    ref = reference.exact_topk(base, pool[uniq], guarantees["k"])[0][inv]
    prog, _ = checks.judge(base, pool[rows], ids, dists, ref, guarantees)
    c_ids, c_d = reference.control_topk(base, pool[uniq], guarantees["k"])
    ctrl, _ = checks.judge(base, pool[rows], c_ids[inv], c_d[inv], ref,
                           guarantees)
    flat = {f"program.{k}": v["value"] for k, v in prog.items()}
    flat.update({f"control.{k}": v["value"] for k, v in ctrl.items()})
    flat["program.correct"] = all(v["holds"] for v in prog.values())
    flat["control.correct"] = all(v["holds"] for v in ctrl.values())
    flat["requests"] = int(len(rows))
    hops = [np.asarray(c.out["hops"]) for c in calls]
    flat["qps"] = len(rows) / (calls[-1].end - calls[0].start)
    flat["hops_mean"] = float(np.concatenate(hops).mean())
    flat["batches_at_max_hops"] = sum(int(h.max() == max(map(np.max, hops)))
                                      for h in hops)
    flat["batches"] = len(hops)
    return flat


def read_cells(cfg, cells, mixes, base, pool, index, L, seeds, seconds,
               closed_only, **extra):
    """One window per seed and cell at search list L; returns the rows."""
    import numpy as np
    from repro.serving import AnnServer
    from bench import load
    server = AnnServer(index, index.cfg.replace(L=L))
    mb = server.server_cfg.max_batch
    entry = harness.make_entry(server, pool)
    t0 = time.perf_counter()
    entry(np.arange(mb) % len(pool))
    warmup_s = time.perf_counter() - t0
    if closed_only:
        cells = [w for w in cells
                 if mixes[w["name"]]["generator"] == "closed"][:1]
    rows = []
    for seed in seeds:
        for w in cells:
            t0 = time.perf_counter()
            calls, _ = load.generate(mixes[w["name"]], entry, len(pool), mb,
                                     seconds, seed)
            row = dict(seed=seed, cell=w["name"], L=L, warmup_s=warmup_s,
                       window_s=time.perf_counter() - t0, **extra,
                       **readings(base, pool, calls, cfg["guarantees"]))
            emit(**row)
            rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--ladder", default="")
    ap.add_argument("--ladder-seeds", type=int, default=2)
    ap.add_argument("--recall-target", type=float, default=0.915)
    ap.add_argument("--witness-data-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    devs = harness.check_device(1, args.rehearse)
    harness.place_compile_cache()
    from bench import registry
    bench = registry.benchmark()
    cfg = registry.config(bench, args.config)
    cells = [w for w in bench["workloads"] if w["config"] == args.config]
    mixes = {w["name"]: registry.mix(w["traffic"]) for w in cells}
    for w in cells:
        harness.apply_overrides(cfg, mixes[w["name"]], args.set)
    seeds = [int(s) for s in args.seeds.split(",")]
    ladder = [int(x) for x in args.ladder.split(",") if x]

    def built(data_seed):
        t0 = time.perf_counter()
        base, pool, _, index, build_s = harness.build(cfg, data_seed)
        emit(data_seed=data_seed, n=cfg["n"], build_s=build_s,
             data_and_build_s=time.perf_counter() - t0,
             stats=index.build_stats)
        return base, pool, index

    base, pool, index = built(cfg["data_seed"])
    L = cfg["L"]
    if ladder:
        head = seeds[:args.ladder_seeds]
        for rung in ladder:
            rows = read_cells(cfg, cells, mixes, base, pool, index, rung,
                              head, args.seconds, True, phase="ladder")
            if min(r["program.recall_at_10"] for r in rows) >= \
                    args.recall_target:
                L = rung
                break
        else:
            L = ladder[-1]
        emit(chosen_L=L, recall_target=args.recall_target)
    read_cells(cfg, cells, mixes, base, pool, index, L, seeds, args.seconds,
               False, phase="seeds", data_seed=cfg["data_seed"])
    mem = devs[0].memory_stats() or {}
    emit(peak_bytes=mem.get("peak_bytes_in_use"), device=devs[0].device_kind)
    for ds in (int(x) for x in args.witness_data_seeds.split(",") if x):
        del base, pool, index
        base, pool, index = built(ds)
        read_cells(cfg, cells, mixes, base, pool, index, L, seeds[:2],
                   args.seconds, False, phase="witness", data_seed=ds)


if __name__ == "__main__":
    main()
