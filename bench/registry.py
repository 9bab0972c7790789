"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one mix or one metric is a
file of its own, found by the name that BENCHMARK.json gives it:

- a configuration: the `file` of its entry in BENCHMARK.json `configs`;
- a traffic mix: bench/traffic/<traffic>.json, which names its generator;
- a generator: bench/generators/<generator>.py, whose `generate(...)`
  drives the entry (bench/load.py);
- a metric: bench/metrics/<name>.py, whose `read(ctx)` returns the value,
  or None where the run holds nothing for it to read.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def mix(name: str, bench_dir: Path = BENCH) -> dict:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def metrics(bench: dict, workload: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` entries that a cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def _load(kind: str, name: str, bench_dir: Path):
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path.name!r} in {path.parent}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench_dir: Path = BENCH):
    return _load("metrics", name, bench_dir).read


def generator(name: str, bench_dir: Path = BENCH):
    return _load("generators", name, bench_dir).generate
