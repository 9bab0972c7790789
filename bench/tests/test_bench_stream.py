"""A mutating configuration (`stream`): the CPU rehearsal of a test-only
cell, the stream generator's seeding, the live set's books and the live
reference; and a frozen configuration still runs as it did."""
import time

import numpy as np
import pytest

from bench import checks, data, live, reference, registry
from benchhelp import (STREAM_CELL, point_registry_at_stream,
                       run_in_process)

DEEP = {"name": "deep-like", "d": 96, "dtype": "float", "clusters": 64}
stream_gen = registry.generator("stream")


@pytest.mark.parametrize("victims,policy", [("uniform", "threshold"),
                                            ("oldest", "continuous")])
def test_stream_rehearsal(monkeypatch, capsys, victims, policy):
    point_registry_at_stream(monkeypatch)
    info, result, err = run_in_process(
        monkeypatch, capsys, STREAM_CELL, seed=2 ** 31 + 7, seconds=6.0,
        tiny=False, extra=[f'mix.victims="{victims}"',
                           f'stream.compaction.policy="{policy}"'])
    w = info["window"]
    assert result["correct"] is True and result["failed"] == 0, err[-2000:]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"recall_at_10", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    for key in ("inserts", "deletes", "flushes", "compactions", "steps"):
        assert w[key] > 0, key
    assert w["bad_acks"] == 0
    assert w["live"] == 768 + w["inserts"] - w["deletes"]
    assert {"lowered_in_window", "compiled_in_window"} <= set(w)
    c = result["checks"]
    assert set(c) == {"recall_at_10", "rerank_rel_err", "malformed_answers",
                      "dead_returned", "read_back", "bad_acks"}
    assert c["dead_returned"]["value"] == 0
    assert c["read_back"]["value"] >= c["read_back"]["limit"]
    assert result["attempted"] > w["requests"]
    assert "check bad_acks" in err.strip().splitlines()[-1]


class FakeProgram:
    """Assigns vids in order and takes every delete; records what it was
    asked."""

    def __init__(self, n):
        self.next, self.asked = n, []

    def mutate(self, vecs, vids):
        self.asked.append((np.array(vecs), np.array(vids)))
        got = list(range(self.next, self.next + len(vecs)))
        self.next += len(vecs)
        return {"vids": got, "took": [True] * len(vids), "flushes": 0,
                "compactions": 0}


def fake_stream(n=64, held=20, seed=0, program=None):
    base, _ = data.make(DEEP, n + held, 0, seed=3)
    program = program or FakeProgram(n)
    return live.LiveSet(base[:n], base[n:], seed, program.mutate,
                        lambda: 0), program


def fake_entry(stream):
    def entry(idx):
        time.sleep(0.002)
        return {"ids": np.asarray(idx)[:, None]}
    entry.stream = stream
    return entry


@pytest.mark.parametrize("victims", live.VICTIMS)
def test_two_seeds_apply_the_same_mutations(victims):
    mix = {"clients": 8, "searches_per_step": 2, "inserts_per_step": 6,
           "deletes_per_step": 5, "victims": victims}
    runs = []
    for seed in (11, 2 ** 31 + 12):
        stream, program = fake_stream()
        calls, info = stream_gen(fake_entry(stream), 40, 16, 0.15, mix, seed)
        runs.append((calls, info["steps"], program.asked, stream))
    (calls_a, steps_a, asked_a, stream_a), (calls_b, _, asked_b, _) = runs
    m = min(len(asked_a), len(asked_b))
    assert m >= 6
    for (va, da), (vb, db) in zip(asked_a[:m], asked_b[:m]):
        assert np.array_equal(va, vb) and np.array_equal(da, db)
    # past the 20 held rows the deleted rows come back, earliest first
    flat = np.concatenate([v for v, _ in asked_a[:m]])
    dead = np.concatenate([d for _, d in asked_a[:m]])
    vectors = stream_a.books()[0]
    assert np.array_equal(flat[20:26], vectors[dead[:6]])
    if victims == "oldest":
        assert list(dead[:10]) == list(range(10))
    # the seed orders the searches, and each call knows its version
    rows_a = np.concatenate([c.pool_idx for c in calls_a])
    rows_b = np.concatenate([c.pool_idx for c in calls_b])
    assert not np.array_equal(rows_a[:16], rows_b[:16])
    assert [c.version for c in calls_a[:4]] == [0, 0, 11, 11]
    assert len(steps_a) == (len(calls_a) - 1) // 2


def test_bad_acknowledgements_are_caught():
    class Liar(FakeProgram):
        def mutate(self, vecs, vids):
            ack = super().mutate(vecs, vids)
            ack["vids"][0] = 3                    # a base vid: out of range
            ack["vids"][1] = ack["vids"][2]       # handed out twice
            ack["took"][0] = False
            return ack
    stream, _ = fake_stream(program=Liar(64))
    stream.step(4, 2, "uniform")
    assert len(stream.bad_acks) == 3
    vectors, born, died = stream.books()
    assert len(vectors) == 68 and (born[64:] != live.NEVER).sum() == 2
    assert (died != live.NEVER).sum() == 1
    assert stream.summary()["live"] == 64 + 2 - 1


def test_exact_topk_live_without_mutations_is_exact_topk():
    base, queries = data.make(DEEP, 700, 40, seed=21)
    n = len(base)
    got = reference.exact_topk_live(
        base, np.zeros(n, np.int64), np.full(n, live.NEVER), queries,
        np.zeros(len(queries), np.int64), 10)
    want = reference.exact_topk(base, queries, 10)
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1],
                                                              want[1])


def test_exact_topk_live_matches_brute_force_on_a_stream():
    vecs, queries = data.make(DEEP, 600, 60, seed=22)
    rng = np.random.default_rng(5)
    born = np.zeros(600, np.int64)
    born[500:] = rng.integers(1, 40, 100)           # inserted later
    died = np.full(600, live.NEVER)
    gone = rng.choice(600, 150, replace=False)
    died[gone] = born[gone] + rng.integers(1, 20, 150)
    versions = rng.integers(0, 60, len(queries))
    ids, d = reference.exact_topk_live(vecs, born, died, queries, versions,
                                       10)
    for i, v in enumerate(versions):
        alive = np.flatnonzero((born <= v) & (v < died))
        dist = np.square(vecs[alive].astype(np.float64)
                         - queries[i].astype(np.float64)).sum(-1)
        order = np.lexsort((alive, dist))[:10]
        assert np.array_equal(ids[i], alive[order])
        np.testing.assert_allclose(d[i], dist[order], rtol=1e-12)
    assert reference.live_mask(born, died, ids, versions).all()


def test_judge_live_counts_dead_ids_and_lost_inserts():
    vecs, queries = data.make(DEEP, 300, 20, seed=23)
    born = np.zeros(300, np.int64)
    born[250:] = 5
    died = np.full(300, live.NEVER)
    died[:10] = 3
    versions = np.full(20, 8)
    ids, d = reference.exact_topk_live(vecs, born, died, queries, versions,
                                       10)
    g = {"k": 10, "recall_at_10_min": 0.9, "rerank_rel_err_max": 1e-4,
         "read_back_min": 0.9}
    own = np.arange(250, 260)
    back = {"inserted": (own, np.stack([own, own + 20], axis=1)),
            "deleted": (np.arange(3), np.full((3, 2), 270))}
    found, failed = checks.judge_live((vecs, born, died), queries, versions,
                                      ids, d.astype(np.float32), ids, back,
                                      8, 0, g)
    assert failed == 0 and all(c["holds"] for c in found.values())
    bad = ids.copy()
    bad[0, 0] = 4                                   # died at version 3
    back["inserted"] = (own, np.stack([own + 1, own + 20], axis=1))
    found, failed = checks.judge_live((vecs, born, died), queries, versions,
                                      bad, d.astype(np.float32), ids, back,
                                      8, 2, g)
    assert found["dead_returned"]["value"] == 1
    assert found["read_back"]["value"] == 0.0
    assert not found["bad_acks"]["holds"]
    assert failed == 1 + 10 + 2


def test_frozen_configuration_runs_as_before(monkeypatch, capsys):
    import repro.mutation
    asked = []
    real_make = data.make

    def make(spec, n, nq, seed):
        asked.append(n)
        return real_make(spec, n, nq, seed)

    def refuse(*a, **kw):
        raise AssertionError("a frozen configuration built a MutableIndex")
    monkeypatch.setattr(data, "make", make)
    monkeypatch.setattr(repro.mutation, "MutableIndex", refuse)
    info, result, _ = run_in_process(monkeypatch, capsys,
                                     "deep-baseline.closed64", seed=19)
    assert asked == [768]
    assert result["correct"] is True
    assert set(result["checks"]) == {"recall_at_10", "rerank_rel_err",
                                     "malformed_answers"}
    assert result["attempted"] == info["window"]["requests"]
    assert "inserts" not in info["window"]
