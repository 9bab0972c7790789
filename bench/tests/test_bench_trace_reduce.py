"""The reduction from a profiler trace to busy time, idle gaps and device
time per program, on synthetic traces and on one recorded on the CPU."""
import pytest

from bench import trace_reduce as tr


def synthetic():
    ops = [("fusion.1", 1.0, 2.0), ("while.3", 2.5, 4.0),
           ("sort.2", 3.0, 3.5), ("fusion.1", 4.0, 4.5),
           ("fusion.9", 9.5, 10.5)]                     # ends past the window
    progs = [("_search_batch", 1.0, 4.5), ("other", 9.5, 10.5)]
    spans = [("bench.window", 0.5, 10.0), ("bench.entry", 0.5, 4.6),
             ("bench.dispatch_wait", 4.6, 9.4), ("bench.entry", 9.4, 10.0)]
    return tr.Trace([tr.Chip(ops, progs)], spans)


def test_union_gaps_overlap():
    busy = tr.union([(3, 4), (1, 2), (1.5, 2.5), (4, 5)])
    assert busy == [(1, 2.5), (3, 5)]
    assert tr.gaps(busy, 0, 6) == [(0, 1), (2.5, 3), (5, 6)]
    assert tr.overlap(busy, [(2, 3.5)]) == pytest.approx(1.0)
    assert tr.clip([("a", 1, 2.5), ("b", 3, 5), ("c", 5, 6)], 2, 4) == \
        [("a", 2, 2.5), ("b", 3, 4)]


def test_self_time_subtracts_nested_ops():
    st = tr.self_times([("while", 0.0, 10.0), ("sort", 1.0, 3.0),
                        ("fusion", 4.0, 5.0), ("inner", 4.2, 4.4),
                        ("after", 11.0, 12.0)])
    assert st == pytest.approx({"while": 7.0, "sort": 2.0, "fusion": 0.8,
                                "inner": 0.2, "after": 1.0})


def test_reduce_synthetic_trace():
    red = tr.reduce(synthetic())
    assert red.window_s == pytest.approx(9.5)
    # busy: [1, 2] + [2.5, 4.5] + [9.5, 10] (clipped to the window)
    assert red.busy_s == pytest.approx(1.0 + 2.0 + 0.5)
    assert red.programs == {"_search_batch": {"count": 1, "device_s": 3.5}}
    assert red.op_self_s["while.3"] == pytest.approx(1.0)
    assert red.op_self_s["fusion.1"] == pytest.approx(1.5)
    assert red.op_self_s["fusion.9"] == pytest.approx(0.5)
    # the longest idle gap is the dispatcher's wait, then the entry's
    assert red.idle_gaps[0] == ("bench.dispatch_wait", pytest.approx(5.0))
    assert red.idle_gaps[1][0] == "bench.entry"
    assert red.span_count == {"bench.entry": 2, "bench.dispatch_wait": 1}
    assert red.span_busy_s["bench.entry"] == pytest.approx(3.5)
    bd = tr.breakdown(red, top=2)
    assert [n for n, _ in bd["device_ops"]] == ["fusion.1", "while.3"]
    assert len(bd["idle_gaps"]) == 2


def test_reduce_needs_window_and_ops():
    t = synthetic()
    assert tr.reduce(tr.Trace(t.chips, t.spans[1:])) is None
    assert tr.reduce(tr.Trace([], t.spans)) is None


def test_program_names():
    assert tr.program_name("jit__search_batch(12)") == "_search_batch"
    assert tr.program_name("jit__beam_search_mem_batch") == \
        "_beam_search_mem_batch"
    assert tr.program_name("jit_f.3") == "f"


def test_op_names_from_tpu_hlo_text():
    assert tr.op_name("%fusion.127 = f32[524288]{0:T(1024)S(1)} fusion("
                      "f32[16,256,16]{2,1,0} %p)") == "fusion.127 f32[524288]"
    assert tr.op_name("%while.119 = (s32[16,32]{1,0:T(8,128)}, f32[16,32,2]"
                      ") while(%t)") == "while.119"
    assert tr.op_name("dot_general.1") == "dot_general.1"


def test_reduce_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _probe_program(x):
        return jnp.sort(x @ x.T, axis=1)

    x = jnp.ones((128, 128))
    _probe_program(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.entry"):
                _probe_program(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    red = tr.reduce(tr.read(str(path)))
    assert red is not None
    assert red.programs["_probe_program"]["count"] == 3
    assert 0 < red.busy_s <= red.window_s
    assert red.span_count["bench.entry"] == 3
    assert 0 < red.span_busy_s["bench.entry"] <= red.span_s["bench.entry"]
