"""Observability: the latency histogram, the span tracer, Chrome trace
export, the latency-attribution conservation contract on the serving
loops, and the wall-clock spans on the profiler's clock.

The load-bearing claims: (1) histogram p50/p99 agree with the order
statistic ``np.percentile(..., method="higher")`` within the documented
``error_bound``; (2) a traced open-loop run's per-query spans sum back
to the reported latency exactly, and per-shard device spans reproduce
the shard window's busy time; (3) the exported Chrome trace validates
(well-formed, async spans balanced, flows resolve); (4) tracing off is
invisible — identical reports, zero recorded state; (5) wall-clock spans
record nothing without a profiler, land in the profiler's trace with the
batch's tags under one, and leave search results bit-identical.
"""
import numpy as np
import pytest

from repro import sanitize
from repro.core import get_preset
from repro.obs import (CONSERVATION_TOL_US, Histogram, Tracer, span,
                       validate_chrome_trace)
from repro.serving.ann_server import (AnnServer, ServerConfig,
                                      _latency_summary)
from repro.serving.fleet import FleetConfig, FleetServer


# --- metrics ----------------------------------------------------------------


def test_histogram_percentiles_within_documented_bound():
    gen = np.random.default_rng(11)
    vals = np.exp(gen.normal(5.0, 1.5, size=20_000)) + 1.0
    h = Histogram.from_values(vals, name="lat")
    assert h.count == 20_000
    assert np.isclose(h.mean, vals.mean())
    for q in (0.5, 0.9, 0.99):
        # the histogram prices the order statistic at ceil(q * (n-1)) —
        # np.percentile's "higher" method — within sqrt(growth) - 1
        exact = float(np.percentile(vals, q * 100, method="higher"))
        assert abs(h.quantile(q) - exact) / exact <= h.error_bound


def test_histogram_empty_and_rejects_bad_samples():
    h = Histogram(name="empty")
    assert np.isnan(h.quantile(0.99))
    assert h.quantile(0.99, default=0.0) == 0.0
    assert np.isnan(h.mean) and np.isnan(h.min) and np.isnan(h.max)
    with pytest.raises(ValueError):
        h.observe(-1.0)
    with pytest.raises(ValueError):
        h.observe(float("nan"))
    with pytest.raises(ValueError):
        Histogram(growth=1.0)


def test_histogram_merge_and_registry_contracts():
    a = Histogram.from_values([1.0, 2.0, 3.0])
    b = Histogram.from_values([10.0, 20.0])
    a.merge(b)
    assert a.count == 5 and a.max == 20.0 and a.min == 1.0
    assert a.total == pytest.approx(36.0)
    for q, v in ((0.0, 1.0), (1.0, 20.0)):
        assert abs(a.quantile(q) - v) / v <= a.error_bound
    with pytest.raises(ValueError):
        a.merge(Histogram(growth=1.01))   # other buckets: no merge


def test_latency_summary_empty_is_finite_and_schema_stable():
    """The zero-admitted report path prices its latency columns off an
    empty histogram: finite 0.0s, never NaN, never np.percentile on []."""
    _, mean, p50, p99 = _latency_summary(np.zeros(0))
    assert (mean, p50, p99) == (0.0, 0.0, 0.0)


# --- tracer -----------------------------------------------------------------


def test_tracer_disabled_records_nothing():
    tr = Tracer(enabled=False)
    tr.span("x", "batch", 0.0, 5.0)
    tr.instant("y", "admission", 1.0)
    assert not tr and len(tr) == 0 and tr.spans == []


# --- traced open loop: conservation + device-time agreement -----------------


@pytest.fixture(scope="module")
def traced_open(base_index, small_dataset):
    cfg = get_preset("baseline", L=16)
    srv = AnnServer(base_index, cfg,
                    server_cfg=ServerConfig(max_batch=8, shards=2))
    tracer = Tracer()
    rep = srv.serve_open_loop(small_dataset.queries, rate_qps=4000.0,
                              duration_us=20_000.0, seed=7, tracer=tracer)
    return srv, tracer, rep


def test_open_loop_attribution_conserves_latency(traced_open):
    _, tracer, rep = traced_open
    at = rep.attribution
    assert rep.completed > 0 and at is not None
    resid = np.abs(at["queue_us"] + at["service_us"]
                   + at["interference_us"] - at["latency_us"])
    assert float(resid.max()) <= CONSERVATION_TOL_US
    assert float(at["queue_us"].min()) >= 0.0
    assert float(at["interference_us"].min()) >= 0.0
    assert np.isclose(rep.mean_queue_us, at["queue_us"].mean())
    assert np.isclose(rep.mean_service_us, at["service_us"].mean())
    # the same contract holds span-side, per query, inside the trace
    s = tracer.summary()
    assert s.queries == rep.completed
    assert s.max_residual_us <= CONSERVATION_TOL_US
    svc = [sp for sp in tracer.spans if sp.cat == "service"]
    assert len(svc) == rep.completed
    assert np.isclose(sum(sp.dur_us for sp in svc),
                      float(at["service_us"].sum()))


def test_open_loop_device_spans_match_shard_windows(traced_open):
    """Summing the per-shard device spans reproduces the shard windows'
    busy time (issued reads x the model's read unit) — the trace and the
    per_shard utilization column are the same accounting."""
    srv, tracer, rep = traced_open
    rd_us = srv.model.read_service_us(srv.cfg.page_bytes)
    assert rep.per_shard is not None and len(rep.per_shard) == 2
    for s, row in rep.per_shard.items():
        span_sum = sum(sp.dur_us for sp in tracer.spans
                       if sp.cat == "device" and sp.track == f"shard{s}")
        assert np.isclose(span_sum, row["issued"] * rd_us, rtol=1e-9)


def test_open_loop_trace_exports_valid_chrome_json(traced_open):
    _, tracer, rep = traced_open
    doc = tracer.to_chrome()
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    # flows: one s/t/f triple per completed query
    for ph in ("s", "t", "f"):
        assert sum(e["ph"] == ph for e in evs) == rep.completed
    # per-hop markers rode along (collect_trace forced by the tracer)
    assert any(e.get("cat") == "hop" for e in evs)


def test_open_loop_tracing_is_invisible_to_results(base_index,
                                                   small_dataset,
                                                   traced_open):
    _, _, rep = traced_open
    cfg = get_preset("baseline", L=16)
    srv = AnnServer(base_index, cfg,
                    server_cfg=ServerConfig(max_batch=8, shards=2))
    plain = srv.serve_open_loop(small_dataset.queries, rate_qps=4000.0,
                                duration_us=20_000.0, seed=7)
    assert plain.completed == rep.completed
    assert np.array_equal(plain.attribution["latency_us"],
                          rep.attribution["latency_us"])
    assert plain.p50_latency_us == rep.p50_latency_us
    assert plain.p99_latency_us == rep.p99_latency_us


def test_sanitizer_checks_attribution_when_armed(traced_open):
    _, _, rep = traced_open
    at = rep.attribution
    prev = sanitize.set_enabled(True)
    try:
        sanitize.check_attribution(at["queue_us"], at["service_us"],
                                   at["interference_us"],
                                   at["latency_us"])
        bad = at["latency_us"].copy()
        bad[0] += 1.0             # one unattributed microsecond
        with pytest.raises(sanitize.SanitizeError):
            sanitize.check_attribution(at["queue_us"], at["service_us"],
                                       at["interference_us"], bad)
    finally:
        sanitize.set_enabled(prev)
    # disarmed: the same broken input is a no-op (zero-cost path)
    sanitize.check_attribution(at["queue_us"], at["service_us"],
                               at["interference_us"], bad)


# --- traced fleet -----------------------------------------------------------


def test_fleet_traced_run_conserves_and_validates(base_index,
                                                  small_dataset):
    cfg = get_preset("baseline", L=16)
    srv = FleetServer(base_index, cfg,
                      server_cfg=ServerConfig(max_batch=8),
                      fleet_cfg=FleetConfig(replica_groups=2))
    tracer = Tracer()
    prev = sanitize.set_enabled(True)   # conservation checked live
    try:
        rep = srv.serve_fleet(small_dataset.queries, rate_qps=6000.0,
                              duration_us=15_000.0, seed=5,
                              tracer=tracer)
    finally:
        sanitize.set_enabled(prev)
    assert rep.completed > 0
    at = rep.attribution
    resid = np.abs(at["queue_us"] + at["service_us"]
                   + at["interference_us"] - at["latency_us"])
    assert float(resid.max()) <= CONSERVATION_TOL_US
    assert validate_chrome_trace(tracer.to_chrome()) == []
    # spans landed on both replica groups' lanes
    assert {sp.pid for sp in tracer.spans if sp.cat == "batch"} == {0, 1}


# --- wall-clock spans on the profiler's clock -------------------------------


def _profiled(tmp_path, fn):
    """Run fn() under the profiler; (fn's result, host events by name as
    [(args, duration_ns)])."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    events = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (dict(ev.stats), ev.duration_ns))
    return out, events


def test_spans_record_nothing_without_a_profiler(tmp_path):
    with span("ann.before", batch=5):         # no profiler runs yet
        pass

    def work():
        with span("ann.during", batch=6):
            return 1
    _, events = _profiled(tmp_path, work)
    assert [n for n in events if n.startswith("ann.")] == ["ann.during"]
    assert events["ann.during"][0][0] == {"batch": 6}


def test_spans_land_in_the_profile_with_batch_tags(tmp_path, base_index,
                                                   small_dataset):
    srv = AnnServer(base_index, get_preset("baseline", L=16))
    q = small_dataset.queries[:8]
    srv.serve_closed_loop(q, workers=8)             # compile outside
    _, events = _profiled(
        tmp_path, lambda: [srv.serve_closed_loop(q, workers=8)
                           for _ in range(2)])
    batches = [a for a, _ in events["ann.serve.batch"]]
    assert batches == [{"batch": 2, "size": 8}, {"batch": 3, "size": 8}]
    for name in ("ann.search.launch", "ann.search.pull", "ann.serve.price",
                 "ann.serve.report"):
        assert len(events[name]) == 2, name
        assert all(d > 0 for _, d in events[name]), name
    assert "ann.search.memgraph" not in events      # baseline: no MemGraph


def test_search_batched_bit_identical_with_and_without_profiler(
        tmp_path, base_index, small_dataset):
    from repro.core.search_kernel import search_batched
    from repro.io import build_store
    store = build_store(base_index.layout, batched=True)
    cfg = get_preset("baseline", L=24)

    def run():
        return search_batched(store, base_index.pq, cfg,
                              small_dataset.queries, batch=16,
                              medoid=base_index.medoid)
    off = run()
    on, events = _profiled(tmp_path, run)
    n = len(small_dataset.queries) // 16
    assert len(events["ann.search.pull"]) == n
    for f in ("ids", "dists", "hops", "page_reads", "pq_evals",
              "full_evals", "visited_pages"):
        a, b = getattr(off, f), getattr(on, f)
        assert a.dtype == b.dtype and np.array_equal(a, b), f


def test_mutation_spans_land_in_the_profile(tmp_path, base_index,
                                            small_dataset):
    """The mutation path's spans: one per insert, delete, flush (with its
    candidate search and wiring nested in it) and compaction run, and
    `ann.search.merge` per batch served over a MutableIndex."""
    from repro.mutation import MutableIndex, MutationConfig
    mi = MutableIndex(base_index, MutationConfig(flush_threshold=4,
                                                 insert_L=16))
    srv = AnnServer(mi, get_preset("baseline", L=16))
    q = small_dataset.queries[:8]
    srv.serve_closed_loop(q, workers=8)             # compile outside

    def work():
        for v in small_dataset.vectors[:4]:
            mi.insert(v)
        mi.flush()
        mi.delete(0)
        mi.compact(max_pages=2)
        srv.serve_closed_loop(q, workers=8)
    _, events = _profiled(tmp_path, work)
    counts = {n: len(events.get(n, ())) for n in (
        "ann.mutate.insert", "ann.mutate.delete", "ann.mutate.flush",
        "ann.mutate.flush.candidates", "ann.mutate.flush.wire",
        "ann.mutate.compact", "ann.search.merge")}
    assert counts == {"ann.mutate.insert": 4, "ann.mutate.delete": 1,
                      "ann.mutate.flush": 1,
                      "ann.mutate.flush.candidates": 1,
                      "ann.mutate.flush.wire": 1, "ann.mutate.compact": 1,
                      "ann.search.merge": 1}
