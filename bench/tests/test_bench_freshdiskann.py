"""The streaming cell `deep-freshdiskann.churn16` rehearsed on the CPU,
shrunk with --set (base rows, held-back inserts, reserved capacity, pool,
graph degree): correct over the live set, nothing compiled in the window,
at least one flush and one compaction run; the traced run prints the
mutation layer's readings. And the readers read None where a run holds
nothing for them."""
import pytest

from bench import registry
from bench.context import Ctx
from bench.load import Step
from bench.trace_reduce import Reduction
from benchhelp import assert_contract_line, rehearse, run_in_process

CELL = "deep-freshdiskann.churn16"
# the reserve covers every insert a CPU window can make at this size
SHRINK = ["stream.inserts=4096", "stream.mutation.capacity=4864"]
MUTATE = ("mutate.ms_per_step", "mutate.host_ms_per_step",
          "mutate.flush_device_ms")


def _window_holds(w):
    assert w["lowered_in_window"] == 0 and w["compiled_in_window"] == 0
    assert w["flushes"] >= 1 and w["compactions"] >= 1
    assert w["bad_acks"] == 0
    assert w["live"] == 768 + w["inserts"] - w["deletes"]


def test_freshdiskann_churn16_rehearsal(monkeypatch, capsys):
    bench = registry.benchmark()
    info, result, err = run_in_process(monkeypatch, capsys, CELL,
                                       seed=2 ** 31 + 17, seconds=3.0,
                                       extra=SHRINK)
    want = assert_contract_line(result, bench, CELL, "end_to_end")
    assert set(result["metrics"]) == set(want) == {"qps", "recall_at_10",
                                                   "setup_s"}
    _window_holds(info["window"])
    c = result["checks"]
    assert c["dead_returned"]["value"] == 0
    assert c["read_back"]["value"] >= c["read_back"]["limit"]
    assert "check bad_acks" in err.strip().splitlines()[-1]


def test_freshdiskann_churn16_traced():
    bench = registry.benchmark()
    info, result, _ = rehearse(CELL, seed=29, seconds=3.0, trace=1,
                               extra=SHRINK)
    want = assert_contract_line(result, bench, CELL, "per_layer")
    assert set(result["metrics"]) == set(want)
    assert set(MUTATE) <= set(want)
    for name in MUTATE:
        assert result["metrics"][name]["value"] > 0, name
    _window_holds(info["window"])


def _ctx(steps, red=None, programs=None):
    if red is None and programs is not None:
        red = Reduction(window=(0.0, 1.0), busy_s=0.5, programs=programs,
                        op_self_s={}, idle_gaps=[], span_s={},
                        span_busy_s={}, span_count={})
    return Ctx(config={}, mix={}, calls=[], setup_s=1.0, build_s=1.0,
               warmup_s=0.1, recall=1.0, page_bytes=4096, pq_m=16, red=red,
               steps=steps)


@pytest.mark.parametrize("name", MUTATE)
def test_mutation_readers_read_none_without_steps(name):
    assert registry.reader(name)(_ctx([])) is None


def test_mutation_readers_on_a_synthetic_window():
    steps = [Step(0.0, 0.010, 16, 16, 0, 1), Step(0.1, 0.130, 16, 16, 1, 0)]
    assert registry.reader("mutate.ms_per_step")(_ctx(steps)) \
        == pytest.approx(20.0)
    # no trace: the trace readers read nothing
    assert registry.reader("mutate.host_ms_per_step")(_ctx(steps)) is None
    flush = registry.reader("mutate.flush_device_ms")
    assert flush(_ctx(steps)) is None
    prog = {"_insert_candidates": {"count": 1, "device_s": 0.004}}
    assert flush(_ctx(steps, programs=prog)) == pytest.approx(4.0)
    # a window without a flush reads nothing, not 0
    assert flush(_ctx(steps[:1], programs=prog)) is None
    red = Reduction(window=(0.0, 1.0), busy_s=0.5, programs={},
                    op_self_s={}, idle_gaps=[],
                    span_s={"bench.mutate": 0.05},
                    span_busy_s={"bench.mutate": 0.01},
                    span_count={"bench.mutate": 5})
    assert registry.reader("mutate.host_ms_per_step")(
        _ctx(steps, red=red)) == pytest.approx(20.0)
