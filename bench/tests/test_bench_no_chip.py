"""Off a TPU, without --rehearse, the benchmark exits non-zero and prints no
result; so it does where only BENCHMARK.json and bench/ are present."""
import os
import shutil
import subprocess
import sys

from benchhelp import ROOT

ARGS = ["--workload", "deep-baseline.closed64", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS, *extra],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_cpu_without_rehearse_exits_nonzero():
    proc = run(ROOT)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_set_needs_rehearse():
    assert run(ROOT, "--set", "n=64").returncode != 0


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = run(tmp_path, "--rehearse")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
