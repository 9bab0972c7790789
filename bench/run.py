"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine that holds the chip:

1. Device check: a JAX platform other than "tpu", or fewer chips than the
   cell asks for, exits non-zero before anything else. `--rehearse` lets
   the CPU through for tests; such a run is never a chip result.
2. Compile cache: JAX's persistent cache in bench/out/jax_cache inside the
   checkout, a fixed path, so only a cell's first run there compiles.
3. Set-up, timed as `setup_s`: base vectors and the query pool from the
   configuration's `data_seed` (bench/data.py), the index through
   `repro.core.build_index`, one `AnnServer`, and exactly one warm-up call
   of the entry. `--seed` orders each round of the query pool
   (bench/load.py): every seed does the same work in another order.
4. The window: the generator that the cell's traffic mix names
   (bench/load.py, bench/generators/) drives the entry
   `AnnServer.serve_closed_loop(batch, workers=len(batch))` for `--seconds`
   on the host clock. With `--trace 1` the profiler records a window of at
   most TRACE_SECONDS in a run of its own; one more call of the entry under
   the profiler, before the window opens, takes the profiler's own first-use
   cost out of it.
5. After the window, with the chip's peak memory read and the program's
   state freed: the reference (bench/reference.py) and the comparison that
   decides `correct` (bench/checks.py), over every request served.
6. The metrics the cell lists, each from its reader in bench/metrics/.

A configuration with a `stream` block mutates the index while it serves:

- set-up draws its `inserts` rows with the base set and holds them back
  from the build, wraps the built index in the program's MutableIndex
  (`stream.mutation`: the fields of repro.mutation.MutationConfig) and
  builds the one AnnServer over it;
- the mutation entry (`make_mutate`) runs serve_open_loop's ingest steps
  under the configuration's compaction policy (`stream.compaction`), and
  the live set (bench/live.py) keeps the books; the traffic mix's generator
  applies its steps through them inside the window;
- after the window, outside it and before the program's state is freed,
  the rows inserted in it and still live, and the rows deleted in it, are
  queried through the same entry (a sample of at most READ_BACK_MAX each,
  drawn from --seed);
- the reference is the exact top-k over the vids live at each request's
  version, and bench/checks.py judges the run with `judge_live`.

The last lines on stderr give each number compared beside its limit; the
last line on stdout is the result as one JSON object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
TRACE_SECONDS = 10.0
READ_BACK_MAX = 1024
RESULT_FIELDS = ("ids", "dists", "hops", "page_reads", "pq_evals",
                 "full_evals", "mem_hops")


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="let a CPU run through the device check (tests "
                         "only; never a chip result)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="with --rehearse only: override a configuration "
                         "key (dotted for nested keys, `mix.` for the "
                         "traffic mix), to rehearse at a tiny size")
    args = ap.parse_args(argv)
    if args.set and not args.rehearse:
        ap.error("--set is for rehearsals only")
    return args


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def apply_overrides(cfg: dict, mix: dict, sets) -> None:
    for item in sets:
        key, _, value = item.partition("=")
        target, path = (mix, key[4:]) if key.startswith("mix.") else (cfg,
                                                                       key)
        *head, last = path.split(".")
        for h in head:
            target = target[h]
        target[last] = json.loads(value)


def check_device(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        sys.exit(f"bench/run.py: the cell needs {chips} TPU chip(s); JAX "
                 f"found {len(devs)} device(s) on platform "
                 f"{devs[0].platform!r}")
    return devs


def place_compile_cache() -> None:
    import jax
    path = OUT / "jax_cache"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Lowerings (a jit cache miss in the process, compiled or loaded from
    the persistent cache) and backend compiles, from jax.monitoring."""

    def __init__(self):
        import jax
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def snapshot(self):
        return self.lowered, self.compiled


def load_peaks(dev, rehearse: bool):
    chips = json.loads((BENCH / "peaks.json").read_text())["chips"]
    if dev.platform != "tpu" and rehearse:
        return None
    if dev.device_kind not in chips:
        sys.exit(f"bench/run.py: no peaks for device_kind "
                 f"{dev.device_kind!r} in bench/peaks.json")
    return chips[dev.device_kind]


def build(cfg: dict, seed: int):
    """(base, pool, held, index, seconds): the data from `seed` and the
    program's index over the base, built with the same seed. With a `stream`
    block the last `inserts` rows drawn are held back from the build
    (`held`) and the index is wrapped in the program's MutableIndex; without
    one `held` is empty."""
    import numpy as np
    from repro.core import Dataset, build_index, get_preset
    from bench import data
    stream = cfg.get("stream")
    n = cfg["n"]
    base, pool = data.make(cfg["dataset"],
                           n + (stream["inserts"] if stream else 0),
                           cfg["pool"], seed)
    base, held = base[:n], base[n:]
    ds = Dataset(cfg["dataset"]["name"], base, pool,
                 np.zeros((len(pool), 0), np.int32), cfg["dataset"]["dtype"])
    b = cfg["build"]
    t0 = time.perf_counter()
    index = build_index(ds, get_preset(cfg["preset"]), R=b["R"],
                        L_build=b["L_build"], alpha=b["alpha"],
                        seed=seed % (2 ** 31 - 1))
    if stream:
        from repro.mutation import MutableIndex, MutationConfig
        index = MutableIndex(index, MutationConfig(**stream["mutation"]))
    return base, pool, held, index, time.perf_counter() - t0


def make_entry(server, pool):
    """The timed path: one synchronous call of the server per batch of pool
    rows; `entry.search` takes the vectors themselves."""
    import numpy as np

    def search(vecs):
        rep = server.serve_closed_loop(vecs, workers=len(vecs))
        inv = np.argsort(rep.query_indices, kind="stable")
        return {f: np.asarray(getattr(rep.stats, f))[inv]
                for f in RESULT_FIELDS}

    def entry(idx):
        return search(pool[idx])
    entry.search = search
    return entry


def make_mutate(server, stream: dict):
    """The mutation entry, (mutate, after_batch): the steps of
    AnnServer.serve_open_loop's ingest, run synchronously on the server's
    MutableIndex. An insert is followed by `maybe_flush` and the compaction
    policy's `after_mutation`, a delete by `after_mutation`; `after_batch`
    is the policy's hook after each served batch. The harness reaches the
    program's mutations through these two alone."""
    from repro.mutation import Compactor, MutationMix
    c = stream["compaction"]
    index = server.index
    compactor = Compactor(index, MutationMix(
        compaction=c["policy"], threshold=c["threshold"],
        max_pages=c["max_pages"]))

    def mutate(insert_vecs, delete_vids):
        vids, took, flushes, compactions = [], [], 0, 0
        for vec in insert_vecs:
            vids.append(index.insert(vec))
            flushes += index.maybe_flush() is not None
            compactions += compactor.after_mutation() is not None
        for vid in delete_vids:
            took.append(index.delete(int(vid)))
            compactions += compactor.after_mutation() is not None
        return {"vids": vids, "took": took, "flushes": flushes,
                "compactions": compactions}

    def after_batch():
        return int(compactor.after_batch() is not None)
    return mutate, after_batch


def read_back(entry, live, max_batch: int, seed: int) -> dict:
    """After the window: the vectors of a sample (at most READ_BACK_MAX,
    drawn from `seed`) of the rows inserted in it and still live, and of the
    rows deleted in it, queried through the entry in batches of max_batch.
    Returns {"inserted": (vids, ids), "deleted": (vids, ids)}."""
    import numpy as np
    rng = np.random.default_rng([seed, 4])
    vectors = live.books()[0]
    out = {}
    for kind, vids in (("inserted", live.inserted_live()),
                       ("deleted", live.deleted())):
        if len(vids) > READ_BACK_MAX:
            vids = np.sort(rng.choice(vids, READ_BACK_MAX, replace=False))
        ids = [entry.search(vectors[vids[s:s + max_batch]])["ids"]
               for s in range(0, len(vids), max_batch)]
        out[kind] = (vids, np.concatenate(ids) if ids
                     else np.zeros((0, 1), np.int64))
    return out


def run_window(mix, entry, pool_size, max_batch, seconds, seed, trace):
    from bench import load
    if not trace:
        return load.generate(mix, entry, pool_size, max_batch, seconds,
                             seed)
    import jax
    import numpy as np
    tdir = OUT / "trace"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    try:
        entry(np.arange(max_batch) % pool_size)
        with jax.profiler.TraceAnnotation("bench.window"):
            calls, info = load.generate(mix, entry, pool_size, max_batch,
                                        seconds, seed, trace=True)
    finally:
        jax.profiler.stop_trace()
    info["trace_dir"] = tdir
    return calls, info


def reduce_trace(tdir: Path):
    from bench import trace_reduce
    files = sorted(tdir.rglob("*.xplane.pb"))
    if not files:
        return None
    red = trace_reduce.reduce(trace_reduce.read(str(files[-1])))
    shutil.rmtree(tdir, ignore_errors=True)
    return red


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import registry
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    mix = registry.mix(cell["traffic"])
    apply_overrides(cfg, mix, args.set)

    devs = check_device(cell["chips"], args.rehearse)
    dev = devs[0]
    peaks = load_peaks(dev, args.rehearse)
    place_compile_cache()
    counter = CompileCounter()

    import numpy as np
    from repro.serving import AnnServer
    from bench import checks, reference
    from bench.context import Ctx

    base, pool, held, index, build_s = build(cfg, cfg["data_seed"])
    server = AnnServer(index, index.cfg.replace(L=cfg["L"]))
    max_batch = server.server_cfg.max_batch
    entry = make_entry(server, pool)
    stream = cfg.get("stream")
    if stream:
        from bench.live import LiveSet
        entry.stream = live = LiveSet(base, held, cfg["data_seed"],
                                      *make_mutate(server, stream))
    t0 = time.perf_counter()
    entry(np.arange(max_batch) % len(pool))
    warmup_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - T_PROCESS
    page_bytes, pq_m = index.cfg.page_bytes, index.cfg.pq_m

    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    before = counter.snapshot()
    calls, info = run_window(mix, entry, len(pool), max_batch, seconds,
                             args.seed, bool(args.trace))
    after = counter.snapshot()
    steps = info.pop("steps", [])
    if stream:
        info.update(live.summary(), steps=len(steps),
                    mutate_s=sum(st.end - st.start for st in steps))
        back = read_back(entry, live, max_batch, args.seed)

    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    if stream:
        live.detach()
    del server, index, entry
    gc.collect()
    red = reduce_trace(info.pop("trace_dir")) if args.trace else None

    rows = np.concatenate([c.pool_idx for c in calls])
    out = {f: np.concatenate([c.out[f] for c in calls])
           for f in ("ids", "dists")}
    k = cfg["guarantees"]["k"]
    attempted = len(rows)
    if stream:
        books = live.books()
        versions = np.concatenate([np.full(len(c.pool_idx), c.version)
                                   for c in calls])
        pairs = np.stack([rows, versions], axis=1)
        uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        ref_ids = reference.exact_topk_live(
            *books, pool[uniq[:, 0]], uniq[:, 1], k)[0][inv]
        found, failed = checks.judge_live(
            books, pool[rows], versions, out["ids"], out["dists"], ref_ids,
            back, live.version, len(live.bad_acks), cfg["guarantees"])
        attempted += sum(len(v) for v, _ in back.values()) + live.attempted
        for msg in live.bad_acks[:20]:
            say(f"bad acknowledgement: {msg}")
    else:
        uniq, inv = np.unique(rows, return_inverse=True)
        ref_ids = reference.exact_topk(base, pool[uniq], k)[0][inv]
        found, failed = checks.judge(base, pool[rows], out["ids"],
                                     out["dists"], ref_ids,
                                     cfg["guarantees"])
    correct = all(c["holds"] for c in found.values())

    ctx = Ctx(config=cfg, mix=mix, calls=calls, setup_s=setup_s,
              build_s=build_s, warmup_s=warmup_s,
              recall=found["recall_at_10"]["value"], page_bytes=page_bytes,
              pq_m=pq_m, red=red, peaks=peaks, steps=steps)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in registry.metrics(bench, args.workload, kind):
        value = registry.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": int(attempted),
              "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        from bench.trace_reduce import breakdown
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = breakdown(red)
    result["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                        for n, c in found.items()}

    print(json.dumps({"window": {
        "workload": args.workload, "seed": args.seed, "seconds": seconds,
        "calls": len(calls), "requests": int(len(rows)),
        "window_s": ctx.window_s, "setup_s": setup_s, "build_s": build_s,
        "warmup_s": warmup_s,
        "lowered_in_window": after[0] - before[0],
        "compiled_in_window": after[1] - before[1],
        "rehearsal": args.rehearse, **info}}), flush=True)
    for name, c in found.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r}, "
            f"{'holds' if c['holds'] else 'FAILS'})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
