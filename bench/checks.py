"""How `correct` is decided: the served answers against the reference.

Two layers are compared, over every request served in the window:

- the exact re-rank: each returned distance against the float64 squared L2
  of the returned id (`rerank_rel_err`, the largest gap as a share of the
  larger of that distance and the median returned distance);
- the approximate search: the returned ids against the exact top-k
  (`recall_at_10`, held to the configuration's own recall guarantee).

An answer is malformed when an id is out of range or repeated, or when its
distances are not in ascending order; `malformed_answers` has the limit 0.

A mutating configuration (`stream`) is judged by `judge_live` over the vid
space of its books (bench/live.py): recall against the exact top-k over the
vids live at each request's version, the re-rank and malformed answers as
above, and three more checks, each with its limit:

- `dead_returned` (0): answers that hold an id not live at the request's
  version, in the window and in the read-back;
- `read_back` (`guarantees.read_back_min`): after the window, the share of
  the rows inserted in it and still live whose own vector, queried through
  the entry, finds their vid in its top-k (an acknowledged insert is read
  back); a run that inserted nothing reads 0;
- `bad_acks` (0): acknowledgements that the books refuse.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def rerank_gaps(base, queries, ids, dists):
    """Per-answer largest relative gap between the returned distances and
    the float64 distances of the returned ids."""
    true = reference.sq_l2(base, queries, ids)
    finite = true[np.isfinite(true)]
    scale = np.maximum(true, np.median(finite) if finite.size else 1.0)
    gap = np.abs(np.asarray(dists, np.float64) - true) / scale
    return np.where(np.isnan(gap), np.inf, gap).max(axis=1)


def malformed(ids, dists, n):
    """Per-answer: an id out of range, a repeated id, or distances out of
    ascending order."""
    ids = np.asarray(ids)
    out_of_range = ((ids < 0) | (ids >= n)).any(axis=1)
    srt = np.sort(ids, axis=1)
    repeated = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    d = np.asarray(dists, np.float64)
    unordered = (d[:, 1:] < d[:, :-1]).any(axis=1)
    return out_of_range | repeated | unordered


def recall(ids, ref_ids, k):
    ids = np.asarray(ids)[:, :k]
    ref = np.asarray(ref_ids)[:, :k]
    hits = (ids[:, :, None] == ref[:, None, :]).any(axis=2).sum()
    return float(hits) / (len(ref) * k)


def judge(base, queries, ids, dists, ref_ids, guarantees: dict):
    """`queries`, `ids`, `dists` and `ref_ids` are per request. Returns
    (checks, failed): each check is {"value", "limit", "holds"}."""
    k = guarantees["k"]
    gaps = rerank_gaps(base, queries, ids, dists)
    bad = malformed(ids, dists, len(base))
    rec = recall(ids, ref_ids, k)
    lim = guarantees["rerank_rel_err_max"]
    checks = {
        "recall_at_10": {"value": rec,
                         "limit": guarantees["recall_at_10_min"],
                         "holds": rec >= guarantees["recall_at_10_min"]},
        "rerank_rel_err": {"value": float(gaps.max()), "limit": lim,
                           "holds": bool(gaps.max() <= lim)},
        "malformed_answers": {"value": int(bad.sum()), "limit": 0,
                              "holds": not bad.any()},
    }
    failed = int((bad | (gaps > lim)).sum())
    return checks, failed


def judge_live(books, queries, versions, ids, dists, ref_ids, read_back,
               final_version: int, bad_acks: int, guarantees: dict):
    """`books` is (vectors, born, died) over the vid space; `queries`,
    `versions`, `ids`, `dists` and `ref_ids` are per request; `read_back`
    maps "inserted" and "deleted" to (vids, ids): the rows queried after
    the window, at `final_version`, and their answers. Returns (checks,
    failed): failed counts window answers that are malformed, off in the
    re-rank or hold a dead id, read-back answers that hold a dead id or
    miss their own inserted vid, and bad acknowledgements."""
    vectors, born, died = books
    k = guarantees["k"]
    ids = np.asarray(ids)
    gaps = rerank_gaps(vectors, queries, ids, dists)
    bad = malformed(ids, dists, len(vectors))
    rec = recall(ids, ref_ids, k)

    def dead(got, vers):
        got = np.asarray(got)
        in_range = (got >= 0) & (got < len(vectors))
        return (in_range & ~reference.live_mask(born, died, got,
                                                vers)).any(axis=1)

    dead_win = dead(ids, versions)
    vids, got = read_back["inserted"]
    own = (np.asarray(got)[:, :k] == np.asarray(vids)[:, None]).any(axis=1)
    share = float(own.mean()) if len(vids) else 0.0
    ins_dead = dead(got, np.full(len(vids), final_version))
    del_vids, del_got = read_back["deleted"]
    del_dead = dead(del_got, np.full(len(del_vids), final_version))
    lim = guarantees["rerank_rel_err_max"]
    rb_min = guarantees["read_back_min"]
    n_dead = int(dead_win.sum() + ins_dead.sum() + del_dead.sum())
    checks = {
        "recall_at_10": {"value": rec,
                         "limit": guarantees["recall_at_10_min"],
                         "holds": rec >= guarantees["recall_at_10_min"]},
        "rerank_rel_err": {"value": float(gaps.max()), "limit": lim,
                           "holds": bool(gaps.max() <= lim)},
        "malformed_answers": {"value": int(bad.sum()), "limit": 0,
                              "holds": not bad.any()},
        "dead_returned": {"value": n_dead, "limit": 0,
                          "holds": n_dead == 0},
        "read_back": {"value": share, "limit": rb_min,
                      "holds": share >= rb_min},
        "bad_acks": {"value": int(bad_acks), "limit": 0,
                     "holds": bad_acks == 0},
    }
    failed = (int((bad | (gaps > lim) | dead_win).sum())
              + int((ins_dead | ~own).sum() + del_dead.sum()) + bad_acks)
    return checks, failed
