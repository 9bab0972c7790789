"""The window arithmetic: the closed loop's qps window and batches, and
generators found by the name a mix gives."""
import time

import numpy as np
import pytest

from bench import load, registry
from bench.context import Ctx

closed = registry.generator("closed")


def fake_entry(service_s):
    def entry(idx):
        time.sleep(service_s)
        idx = np.asarray(idx)
        return {"ids": idx[:, None], "hops": np.ones(len(idx), np.int32),
                "page_reads": np.full(len(idx), 2.0)}
    return entry


def ctx_of(calls):
    return Ctx(config={}, mix={}, calls=calls, setup_s=1.0, build_s=0.5,
               warmup_s=0.1, recall=1.0, page_bytes=4096, pq_m=16)


def read(name, ctx):
    from bench import registry
    return registry.reader(name)(ctx)


def test_closed_window_ends_with_first_call_past_seconds():
    calls, _ = closed(fake_entry(0.02), 100, 16, 0.1,
                           {"clients": 64}, seed=0)
    assert all(len(c.pool_idx) == 16 for c in calls)
    assert calls[-1].end >= 0.1 and calls[-2].end < 0.1
    # round robin over the pool in the seed's order
    rows = np.concatenate([c.pool_idx for c in calls])
    assert list(rows) == list(load.pool_order(100, len(rows), 0, 64))
    assert len(set(rows[:100])) == min(100, len(rows))
    ctx = ctx_of(calls)
    assert read("qps", ctx) == pytest.approx(
        16 * len(calls) / (calls[-1].end - calls[0].start))


def test_closed_batch_is_clients_when_fewer_than_max_batch():
    calls, _ = closed(fake_entry(0.0), 10, 16, 0.01,
                           {"clients": 4}, seed=0)
    assert {len(c.pool_idx) for c in calls} == {4}


def test_every_seed_asks_for_the_same_queries_in_each_round():
    orders = [load.pool_order(1000, 2000, s, 64) for s in (1, 2, 2 ** 31 + 5)]
    assert not np.array_equal(orders[0], orders[1])
    for o in orders:
        assert np.array_equal(o[1000:], o[:1000])
        assert sorted(o[:1000]) == list(range(1000))
        for s in range(0, 1000, 64):
            end = min(s + 64, 1000)
            assert sorted(o[s:end]) == list(range(s, end))
    assert np.array_equal(load.pool_order(1000, 5, 7, 64),
                          load.pool_order(1000, 5, 7, 64))


def test_generate_runs_the_generator_that_the_mix_names():
    calls, info = load.generate({"generator": "closed", "clients": 8},
                                fake_entry(0.0), 50, 16, 0.01, seed=4)
    assert info == {} and {len(c.pool_idx) for c in calls} == {8}
    rows = np.concatenate([c.pool_idx for c in calls])
    assert list(rows) == list(load.pool_order(50, len(rows), 4, 8))


def test_unknown_generator_is_an_error():
    with pytest.raises(KeyError, match="no generators file"):
        load.generate({"generator": "nowhere"}, fake_entry(0.0), 10, 16,
                      0.01, seed=0)


def test_lane_use_and_pages():
    calls = [load.Call(0.0, 1.0, np.arange(3), np.zeros(3),
                       {"hops": np.array([2, 4, 6]),
                        "page_reads": np.array([1.0, 2.0, 3.0])}),
             load.Call(1.0, 2.0, np.arange(2), np.zeros(2),
                       {"hops": np.array([5, 5]),
                        "page_reads": np.array([4.0, 5.0])})]
    ctx = ctx_of(calls)
    assert read("search.lane_use_pct", ctx) == pytest.approx(
        100.0 * 22 / (6 * 3 + 5 * 2))
    assert read("io.pages_per_query", ctx) == pytest.approx(3.0)
    # no trace, so the trace readers read nothing
    for name in ("search.device_ms_per_batch", "search.roofline_pct",
                 "memgraph.device_ms_per_batch",
                 "serving.host_ms_per_batch", "device.idle_pct.closed"):
        assert read(name, ctx) is None
