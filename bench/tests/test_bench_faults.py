"""A run with the timed path broken underneath comes out not correct.

Each test drives bench/run.py's whole run in this process on the CPU at a
tiny size (past the look for a chip, with --rehearse) and plants one fault
in the program's served path. The exchange between chips is not among the
faults: every cell runs on one chip.
"""
import json

import numpy as np
import pytest

from benchhelp import TINY

CELL = "deep-baseline.closed64"


def run_cell(monkeypatch, capsys, workload=CELL):
    from bench import run
    monkeypatch.setattr(run, "place_compile_cache", lambda: None)
    sets = [a for s in TINY for a in ("--set", s)]
    rc = run.main(["--workload", workload, "--seed", "31", "--seconds",
                   "0.5", "--trace", "0", "--rehearse", *sets])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch, capsys):
    assert run_cell(monkeypatch, capsys)["correct"] is True


def test_search_that_leaves_its_state_unchanged(monkeypatch, capsys):
    from repro.core import search_kernel
    orig = search_kernel._search_batch

    def unchanged(*args, **kw):
        return orig(*args, **dict(kw, max_iters=0))
    monkeypatch.setattr(search_kernel, "_search_batch", unchanged)
    result = run_cell(monkeypatch, capsys)
    assert result["correct"] is False and result["failed"] > 0


def test_half_the_batch_left_out(monkeypatch, capsys):
    from repro.core import QueryStats
    from repro.serving import ann_server
    orig = ann_server.search_batched

    def half(store, pq, cfg, queries, **kw):
        h = len(queries) // 2
        st = orig(store, pq, cfg, queries[:h], **dict(kw, batch=h))
        return QueryStats.concat([st, st]).take(len(queries))
    monkeypatch.setattr(ann_server, "search_batched", half)
    result = run_cell(monkeypatch, capsys)
    assert result["correct"] is False
    assert result["failed"] > 0.3 * result["attempted"]


@pytest.mark.parametrize("cell", [CELL, "sift-octopusann.closed64"])
def test_answer_altered_where_produced(monkeypatch, capsys, cell):
    from repro.core import search_kernel
    orig = search_kernel._search_batch

    def altered(*args, **kw):
        out = dict(orig(*args, **kw))
        n = args[3].shape[0]
        out["ids"] = out["ids"].at[0, 0].set((out["ids"][0, 0] + 1) % n)
        return out
    monkeypatch.setattr(search_kernel, "_search_batch", altered)
    result = run_cell(monkeypatch, capsys, cell)
    assert result["correct"] is False
    assert result["checks"]["rerank_rel_err"]["value"] > \
        result["checks"]["rerank_rel_err"]["limit"]
    assert np.isfinite(result["failed"]) and result["failed"] >= 1
