"""BENCHMARK.json keeps to the benchmark's contract, and a configuration, a
mix and a metric added as new files are found by name."""
import json
import re
import shutil

import pytest

from bench import load, registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return registry.benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51


def test_every_name_and_file_resolves(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        cfg = registry.config(bench, c["name"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        gen = registry.mix(w["traffic"])["generator"]
        assert callable(registry.generator(gen))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert callable(registry.reader(m["name"]))


def test_each_cell_reports_what_its_per_layer_metrics_move(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        mine = {m["name"] for m in registry.metrics(bench, w["name"],
                                                    "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layer = registry.metrics(bench, w["name"], "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in e2e and m["moves"] in mine, m["name"]
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_new_files_are_found_by_name(tmp_path, bench):
    root = tmp_path / "checkout"
    shutil.copytree(registry.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps({"name": "tiny", "n": 64}))
    (root / "bench" / "traffic" / "trickle.json").write_text(
        json.dumps({"generator": "one_by_one", "rate_qps": 1.5}))
    (root / "bench" / "generators" / "one_by_one.py").write_text(
        "from bench.load import Call\n"
        "def generate(entry, pool_size, max_batch, seconds, mix, seed,\n"
        "             trace=False):\n"
        "    return [Call(0.0, 1.0, [i], [0.0], entry([i]))\n"
        "            for i in range(pool_size)], {'mix': mix['rate_qps']}\n")
    (root / "bench" / "metrics" / "calls.count.py").write_text(
        "def read(ctx):\n    return len(ctx.calls)\n")
    new = dict(bench)
    new["configs"] = bench["configs"] + [
        {"name": "tiny", "source": "x", "file": "bench/configs/tiny.json",
         "reduced": [], "why": "x"}]
    new["workloads"] = bench["workloads"] + [
        {"name": "tiny.trickle", "config": "tiny", "traffic": "trickle",
         "chips": 1, "why": "x"}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "calls.count", "unit": "calls", "better": "higher",
         "source": "host_clock", "layer": "entry call", "moves": "setup_s",
         "workloads": ["tiny.trickle"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    got = registry.benchmark(root)
    cell = registry.cell(got, "tiny.trickle")
    assert registry.config(got, cell["config"], root)["n"] == 64
    mix = registry.mix(cell["traffic"], root / "bench")
    calls, info = load.generate(mix, lambda idx: {"ids": idx}, 3, 16, 1.0,
                                seed=0, bench_dir=root / "bench")
    assert [c.out["ids"] for c in calls] == [[0], [1], [2]]
    assert info == {"mix": 1.5}
    names = [m["name"] for m in registry.metrics(got, "tiny.trickle",
                                                 "per_layer")]
    assert "calls.count" in names and "search.roofline_pct" not in names
    read = registry.reader("calls.count", root / "bench")

    class Ctx:
        calls = [1, 2, 3]
    assert read(Ctx()) == 3
