"""The reading of the program's own spans and scopes from a profiler trace
(bench/program_trace.py): on synthetic traces and on one recorded on the
CPU."""
import pytest

from bench import program_trace as pt
from bench import trace_reduce as tr

S = "jit(_search_batch)/vmap()/while"
WINDOW = ("bench.window", 0.0, 11.0)
ENTRY = ("bench.entry", 1.0, 10.0)
ENTRY2 = ("bench.entry", 10.2, 10.8)
ANN = [("ann.serve.batch", 1.0, 9.0),
       ("ann.search.memgraph", 1.0, 3.0),
       ("ann.search.launch", 3.0, 3.5),
       ("ann.search.pull", 3.5, 7.0),
       ("ann.serve.price", 7.0, 8.5),
       ("ann.serve.report", 9.0, 9.8)]
# (op as trace_reduce names it, start, end); PATHS: its op_name metadata
OPS = [("fusion.1 f32[8]", 1.5, 2.0), ("while.5", 3.2, 6.4),
       ("fusion.127 f32[524288]", 3.3, 5.3), ("sort.2", 5.4, 5.9),
       ("fusion.9 pred[16]", 6.0, 6.3), ("fusion.3 s32[16,10]", 6.4, 6.5),
       ("fusion.7 f32[8]", 10.0, 10.1)]
PATHS = {"fusion.1": "jit(_beam_search_mem_batch)/vmap()/while/body/merge/"
                     "add",
         "while.5": S,
         "fusion.127": S + "/body/pq_lookup/jit(take)/gather",
         "sort.2": S + "/body/merge/jit(argsort)/sort",
         "fusion.9": S + "/body/select/lt",
         "fusion.3": "jit(_search_batch)/vmap(rerank)/gather"}
PROGS = [("_beam_search_mem_batch", 1.5, 2.0), ("_search_batch", 3.2, 6.5),
         ("other", 10.0, 10.1)]


def synthetic(spans):
    return tr.Trace([tr.Chip(OPS, PROGS)], spans)


def test_scope_paths_and_hlo_text():
    assert pt.scope_of(S + "/body/merge/jit(argsort)/sort") == "merge"
    assert pt.scope_of("jit(_search_batch)/vmap(pq_lookup)/gather") == \
        "pq_lookup"
    assert pt.scope_of("jit(f)/merge/pq_lookup/add") == "pq_lookup"
    assert pt.scope_of(S) is None and pt.scope_of(None) is None
    assert pt.scope_of("jit(f)/body/merge") is None   # a primitive's name
    text = ('  %fusion.127 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
            'metadata={op_name="jit(f)/vmap(pq_lookup)/gather" '
            'stack_frame_id=3}\n'
            '  ROOT %while.5 = (s32[]) while(%t), condition=%c, body=%b, '
            'metadata={op_name="jit(f)/while"}\n'
            '  %copy.1 = f32[8]{0} copy(%p)\n')
    assert pt.hlo_paths(text) == {"fusion.127": "jit(f)/vmap(pq_lookup)/"
                                                "gather",
                                  "while.5": "jit(f)/while"}
    assert pt.instruction("%fusion.127 = f32[524288]{0:T(1024)} fusion("
                          "f32[16,256,16] %p)") == "fusion.127"
    assert pt.instruction("fusion.127 f32[524288]") == "fusion.127"
    assert pt.instruction("while.5") == "while.5"


def test_innermost_segments():
    segs = pt.innermost([("a", 0.0, 10.0), ("b", 1.0, 3.0),
                         ("c", 2.0, 2.5), ("d", 4.0, 5.0), ("e", 12.0, 13.0)])
    assert segs == [("a", 0.0, 1.0), ("b", 1.0, 2.0), ("c", 2.0, 2.5),
                    ("b", 2.5, 3.0), ("a", 3.0, 4.0), ("d", 4.0, 5.0),
                    ("a", 5.0, 10.0), ("e", 12.0, 13.0)]


def test_reduce_synthetic_program_trace():
    red = pt.reduce(synthetic([WINDOW, ENTRY] + ANN + [ENTRY2]), PATHS)
    # gaps [0, 1.5], [2, 3.2], [6.5, 10], [10.1, 11], each labelled by the
    # innermost span over the longest stretch of it
    assert red.idle_gaps == [("ann.serve.price", pytest.approx(3.5)),
                             ("ann.search.memgraph", pytest.approx(1.5)),
                             ("ann.search.memgraph", pytest.approx(1.2)),
                             ("bench.entry", pytest.approx(0.9))]
    assert red.ann_idle_share == pytest.approx(6.2 / 7.1)
    # self time: a span less the spans nested in it
    assert red.span_self_s == pytest.approx(
        {"bench.entry": 0.8, "ann.serve.batch": 0.5,
         "ann.search.memgraph": 2.0, "ann.search.launch": 0.5,
         "ann.search.pull": 3.5, "ann.serve.price": 1.5,
         "ann.serve.report": 0.8})
    # device busy inside each span: [1.5, 2] and [3.2, 6.5]
    assert red.base.span_busy_s == pytest.approx(
        {"bench.entry": 3.8, "ann.serve.batch": 3.8,
         "ann.search.memgraph": 0.5, "ann.search.launch": 0.3,
         "ann.search.pull": 3.0, "ann.serve.price": 0.0,
         "ann.serve.report": 0.0})
    # device self time by scope, inside _search_batch only (the MemGraph
    # program's `merge` op is not counted)
    assert red.search == {"count": 1, "device_s": pytest.approx(3.3)}
    assert red.base.span_count[pt.BATCH] == 1
    assert red.scope_s == pytest.approx(
        {"pq_lookup": 2.0, "merge": 0.5, "select": 0.3, "rerank": 0.1,
         pt.UNSCOPED: 0.4})
    assert red.scoped_share == pytest.approx(2.9 / 3.3)
    assert pt.readings(red) == pytest.approx(
        {"search.pq_lookup_ms_per_batch": 2000.0,
         "search.merge_ms_per_batch": 500.0,
         "serving.price_ms_per_batch": 1500.0,
         "search.pull_ms_per_batch": 500.0,
         "memgraph.host_ms_per_batch": 1500.0})
    assert pt.device_scopes(red, top=2) == [["pq_lookup", pytest.approx(2.0)],
                                            ["merge", pytest.approx(0.5)]]
    # what trace_reduce gave before, for every bench.* span, is unchanged
    old = tr.reduce(synthetic([WINDOW, ENTRY, ENTRY2]))
    assert red.base.idle_gaps == old.idle_gaps
    for field in ("span_s", "span_busy_s", "span_count"):
        new = getattr(red.base, field)
        assert {k: new[k] for k in getattr(old, field)} == \
            getattr(old, field), field
    assert (red.base.busy_s, red.base.programs, red.base.op_self_s) == \
        (old.busy_s, old.programs, old.op_self_s)


def test_without_ann_spans_it_reads_as_trace_reduce():
    """A trace of a program without spans or scopes (an older program)
    gives trace_reduce's gaps, labels and span times, and no reading."""
    spans = [WINDOW, ENTRY, ENTRY2]
    red = pt.reduce(synthetic(spans), {})
    old = tr.reduce(synthetic(spans))
    assert red.base == old
    assert red.idle_gaps == old.idle_gaps
    assert red.span_self_s == pytest.approx({"bench.entry": 9.6})
    assert red.scope_s == pytest.approx({pt.UNSCOPED: 3.3})
    assert red.scoped_share == 0.0
    assert pt.readings(red) == {}
    assert pt.reduce(synthetic(spans[1:]), PATHS) is None


@pytest.fixture(scope="module")
def served():
    """A tiny OctopusANN server (MemGraph on the path), compiled."""
    import numpy as np
    from repro.core import build_index, get_preset, make_dataset
    from repro.serving import AnnServer
    ds = make_dataset("sift-like", n=768, nq=32, seed=2)
    index = build_index(ds, get_preset("octopusann"), R=8, L_build=16)
    srv = AnnServer(index, index.cfg.replace(L=16))
    q = np.asarray(ds.queries[:16])
    srv.serve_closed_loop(q, workers=16)
    return srv, q


def test_recorded_cpu_trace_reads_spans_and_scopes(tmp_path, served):
    import jax
    srv, q = served
    hlo = pt.capture_hlo(lambda: srv.serve_closed_loop(q, workers=16))
    assert "pq_lookup" in hlo
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.entry"):
                    srv.serve_closed_loop(q, workers=16)
    finally:
        jax.profiler.stop_trace()
    trace = pt.read(str(next(tmp_path.rglob("*.xplane.pb"))))
    assert [n for n, _, _ in trace.spans].count(pt.BATCH) == 2
    red = pt.reduce(trace, pt.hlo_paths(hlo))
    assert red.search["count"] == 2 and red.base.span_count[pt.BATCH] == 2
    assert set(pt.readings(red)) == {
        "search.pq_lookup_ms_per_batch", "search.merge_ms_per_batch",
        "serving.price_ms_per_batch", "search.pull_ms_per_batch",
        "memgraph.host_ms_per_batch"}
    # the CPU fuses the PQ lookup into the merge's ops (a fusion takes its
    # root's scope); the chip keeps the lookup's gather a fusion of its own
    assert {"merge", "select", "page_gather", "exact_dist",
            "rerank"} <= set(red.scope_s)
    assert 0 < red.scoped_share <= 1.0
    assert 0 < red.ann_idle_share <= 1.0
    # no hlo text: the CPU's op events carry no path, so no scope is read
    bare = pt.reduce(trace, {})
    assert set(bare.scope_s) == {pt.UNSCOPED}


def test_capture_hlo_fails_loudly_without_a_call():
    with pytest.raises(RuntimeError, match="_search_batch"):
        pt.capture_hlo(lambda: None)
