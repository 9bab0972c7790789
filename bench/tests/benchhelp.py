"""Helpers of the benchmark's tests: run bench/run.py on the CPU at a tiny size and parse what it printed."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
# a tiny index that a CPU rehearsal builds in seconds
TINY = ["n=768", "pool=128", "build.R=16", "build.L_build=32"]


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
