"""How `correct` is decided: the served answers against the reference.

Two layers are compared, over every request served in the window:

- the exact re-rank: each returned distance against the float64 squared L2
  of the returned id (`rerank_rel_err`, the largest gap as a share of the
  larger of that distance and the median returned distance);
- the approximate search: the returned ids against the exact top-k
  (`recall_at_10`, held to the configuration's own recall guarantee).

An answer is malformed when an id is out of range or repeated, or when its
distances are not in ascending order; `malformed_answers` has the limit 0.
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
