"""Helpers of the benchmark's tests: run bench/run.py on the CPU at a tiny size and parse what it printed."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
# a tiny index that a CPU rehearsal builds in seconds
TINY = ["n=768", "pool=128", "build.R=16", "build.L_build=32"]
# a test-only mutating configuration and its mix, under bench/tests/data
DATA = Path(__file__).resolve().parent / "data"
STREAM_CELL = "stream-tiny.churn"


def rehearse(workload, seed=5, seconds=1.0, trace=0, extra=()):
    sets = [a for s in TINY + list(extra) for a in ("--set", s)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse", *sets],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1]), proc.stderr


def assert_contract_line(result, bench, workload, kind):
    from bench import registry
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    want = {m["name"]: m["unit"]
            for m in registry.metrics(bench, workload, kind)}
    for name, m in result["metrics"].items():
        assert m["unit"] == want[name] and m["value"] == m["value"]
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    dev = result["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    return want


def point_registry_at_stream(monkeypatch):
    """Let bench/registry.py find the test-only cell STREAM_CELL: the
    configuration data/stream-tiny.json under the mix data/churn.json."""
    from bench import registry
    real_benchmark, real_mix = registry.benchmark, registry.mix

    def benchmark(root=registry.ROOT):
        b = real_benchmark(root)
        cfg = {"name": "stream-tiny", "source": "test only",
               "file": str((DATA / "stream-tiny.json").relative_to(ROOT)),
               "reduced": [], "why": "test only"}
        cell = {"name": STREAM_CELL, "config": "stream-tiny",
                "traffic": "churn", "chips": 1, "why": "test only"}
        return dict(b, configs=b["configs"] + [cfg],
                    workloads=b["workloads"] + [cell])

    def mix(name, bench_dir=registry.BENCH):
        path = DATA / f"{name}.json"
        return (json.loads(path.read_text()) if path.is_file()
                else real_mix(name, bench_dir))
    monkeypatch.setattr(registry, "benchmark", benchmark)
    monkeypatch.setattr(registry, "mix", mix)


def run_in_process(monkeypatch, capsys, workload, seed=31, seconds=0.5,
                   extra=(), tiny=True):
    """bench/run.py's main in this process on the CPU, past the look for a
    chip: (window line, result line, stderr)."""
    from bench import run
    monkeypatch.setattr(run, "place_compile_cache", lambda: None)
    sets = [a for s in (TINY if tiny else []) + list(extra)
            for a in ("--set", s)]
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0", "--rehearse", *sets])
    assert rc == 0
    cap = capsys.readouterr()
    lines = cap.out.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1]), cap.err
