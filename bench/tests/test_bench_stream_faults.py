"""A mutating run with the program's mutation path broken underneath comes
out not correct; and the stream changes the answers, so the live reference
is needed.

Each test drives bench/run.py's whole run in this process on the CPU over
the test-only cell (benchhelp.STREAM_CELL), past the look for a chip.
"""
import numpy as np

from bench import checks, reference
from benchhelp import (STREAM_CELL, point_registry_at_stream,
                       run_in_process)


def run_stream(monkeypatch, capsys, extra=()):
    point_registry_at_stream(monkeypatch)
    return run_in_process(monkeypatch, capsys, STREAM_CELL, seed=43,
                          seconds=2.0, tiny=False, extra=extra)


def test_merge_without_its_tombstone_filter(monkeypatch, capsys):
    from repro.mutation import MutableIndex
    merge = MutableIndex.merge_mutations

    def unfiltered(self, stats, queries, cfg=None):
        deleted = self.deleted
        self.deleted = np.zeros_like(deleted)
        try:
            return merge(self, stats, queries, cfg)
        finally:
            self.deleted = deleted
    monkeypatch.setattr(MutableIndex, "merge_mutations", unfiltered)
    _, result, _ = run_stream(monkeypatch, capsys)
    assert result["correct"] is False and result["failed"] > 0
    assert result["checks"]["dead_returned"]["value"] > 0


def test_delta_results_dropped(monkeypatch, capsys):
    from repro.mutation import DeltaIndex

    def nothing(self, queries, k):
        b = len(queries)
        return (np.full((b, k), -1, np.int64),
                np.full((b, k), np.inf, np.float32), 0)
    monkeypatch.setattr(DeltaIndex, "search", nothing)
    info, result, _ = run_stream(
        monkeypatch, capsys, ["stream.mutation.flush_threshold=100000"])
    assert info["window"]["flushes"] == 0 and info["window"]["inserts"] > 0
    assert result["correct"] is False and result["failed"] > 0
    rb = result["checks"]["read_back"]
    assert rb["value"] < rb["limit"]


def test_stream_changes_the_answers(monkeypatch, capsys):
    """Control: scored against the exact top-10 of the frozen base set, the
    stream's answers read a lower recall than against the live reference."""
    seen = {}
    judge = checks.judge_live

    def keep(books, queries, versions, ids, dists, ref_ids, *rest):
        seen.update(books=books, queries=queries, versions=versions, ids=ids,
                    ref_ids=ref_ids)
        return judge(books, queries, versions, ids, dists, ref_ids, *rest)
    monkeypatch.setattr(checks, "judge_live", keep)
    info, result, _ = run_stream(monkeypatch, capsys)
    assert result["correct"] is True
    assert info["window"]["steps"] > 0
    n = 768
    frozen = reference.exact_topk(seen["books"][0][:n], seen["queries"],
                                  10)[0]
    after = seen["versions"] > 0
    assert after.any()
    live_recall = checks.recall(seen["ids"][after], seen["ref_ids"][after],
                                10)
    frozen_recall = checks.recall(seen["ids"][after], frozen[after], 10)
    assert frozen_recall < live_recall - 0.02
