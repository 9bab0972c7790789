"""Streaming-mutation subsystem tests: delta index, tombstones, the mutable
store's invalidation contract, flush/compaction mechanics, and the serving
integration (serve_open_loop(mutation_mix=)).

Fast tier: pure delta/store units on synthetic layouts. Default tier: the
merged search path over the session-scoped `base_index` Vamana fixture.
Slow tier (`-m slow`): the decay-and-repair property — overlap_ratio and
pages-per-query degrade under sustained inserts without compaction and
recover under it."""
import warnings

import numpy as np
import pytest

from repro.core.pages import build_layout, overlap_ratio
from repro.io import (LRUPageCache, PartitionedPageCache, TwoQPageCache,
                      build_store, make_placement)
from repro.mutation import (Compactor, DeltaIndex, MutableIndex,
                            MutablePageStore, MutationConfig, MutationMix)


# --------------------------------------------------------------------------
# fast: DeltaIndex


@pytest.mark.fast
def test_delta_bruteforce_matches_numpy_topk():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(20, 8)).astype(np.float32)
    delta = DeltaIndex(8)
    for i, v in enumerate(X):
        delta.insert(100 + i, v)
    q = rng.normal(size=(3, 8)).astype(np.float32)
    ids, dists, evals = delta.search(q, k=5)
    assert evals == 20
    ref = np.sum((q[:, None, :] - X[None]) ** 2, axis=-1)
    for b in range(3):
        expect = 100 + np.argsort(ref[b], kind="stable")[:5]
        assert np.array_equal(ids[b], expect)
        assert np.allclose(dists[b], np.sort(ref[b])[:5], rtol=1e-4)


@pytest.mark.fast
def test_delta_padding_and_remove():
    delta = DeltaIndex(4)
    ids, dists, evals = delta.search(np.zeros((1, 4), np.float32), k=3)
    assert evals == 0 and (ids == -1).all() and np.isinf(dists).all()
    delta.insert(7, np.ones(4))
    delta.insert(8, 2 * np.ones(4))
    assert delta.remove(7) and not delta.remove(7)
    assert 8 in delta and 7 not in delta
    ids, dists, _ = delta.search(np.zeros((1, 4), np.float32), k=3)
    assert ids[0].tolist() == [8, -1, -1]
    vids, vecs = delta.drain()
    assert vids.tolist() == [8] and len(delta) == 0
    with pytest.raises(ValueError, match="dim"):
        delta.insert(9, np.ones(3))


# --------------------------------------------------------------------------
# fast: cache invalidation + placement growth


@pytest.mark.fast
@pytest.mark.parametrize("mk", [lambda: LRUPageCache(4),
                                lambda: TwoQPageCache(8)])
def test_cache_invalidate_forces_next_miss(mk):
    c = mk()
    c.access(3)
    c.access(3)
    assert 3 in c
    assert c.invalidate(3) is True
    assert 3 not in c
    assert c.access(3) is False          # rewritten bytes: charged re-read
    assert c.invalidate(99) is False


@pytest.mark.fast
def test_twoq_ghost_survives_invalidation():
    """Invalidation drops stale BYTES; the id-only re-use evidence stays,
    so a rewritten hot page re-enters the protected queue on its next
    touch cycle."""
    c = TwoQPageCache(8)
    for p in range(10):
        c.access(p)                      # pushes early pages into the ghost
    assert c.invalidate(9)
    assert 9 in c._ghost or 9 not in c   # resident copy gone either way
    assert 9 not in c


@pytest.mark.fast
def test_partitioned_invalidate_hits_every_tenant():
    c = PartitionedPageCache(8, 2)
    c.access(5, 0)
    c.access(5, 1)
    assert c.invalidate(5) is True
    assert 5 not in c
    assert c.access(5, 0) is False and c.access(5, 1) is False


@pytest.mark.fast
def test_placement_extend_keeps_homes_and_balances_appends():
    pl = make_placement("contiguous", 9, 3)
    grown = pl.extend(15)
    assert np.array_equal(grown.page_to_shard[:9], pl.page_to_shard)
    assert not grown.replicated[9:].any()
    counts = np.bincount(grown.page_to_shard, minlength=3)
    assert counts.max() - counts.min() <= 1   # appends fill the lightest
    with pytest.raises(ValueError, match="shrink"):
        grown.extend(9)
    assert grown.extend(15) is grown


# --------------------------------------------------------------------------
# fast: MutablePageStore


@pytest.fixture()
def tiny_layout():
    rng = np.random.default_rng(0)
    n, d, R = 64, 8, 4
    vectors = rng.normal(size=(n, d)).astype(np.float32)
    graph = rng.integers(0, n, (n, R)).astype(np.int32)
    return build_layout(vectors, graph, page_bytes=256)


@pytest.mark.fast
def test_mutable_store_passthrough_mirrors_inner(tiny_layout):
    st = build_store(tiny_layout, batched=True, mutable=True)
    assert isinstance(st, MutablePageStore)
    st.fetch([0, 1, 1, 2])
    assert st.counters.as_dict() == st.inner.counters.as_dict()
    vis = np.zeros((2, tiny_layout.num_pages), bool)
    vis[0, [0, 1]] = True
    vis[1, [1, 2]] = True
    acct = st.coalesce(vis)
    assert acct["issued"] == 3
    assert st.counters.pages_fetched == st.inner.counters.pages_fetched
    assert st.savings() == st.inner.savings()      # public delegation


@pytest.mark.fast
def test_mutable_store_invalidation_evicts_warm_cache(tiny_layout):
    st = build_store(tiny_layout, batched=True, cache_policy="lru",
                     cache_bytes=8 * tiny_layout.page_bytes, mutable=True)
    trace = np.asarray([[[0, 1, -1], [2, -1, -1]]], np.int32)
    st.replay_batch(trace)
    warm = st.replay_batch(trace)
    assert warm["hits"] == 3                       # fully warm
    assert st.version_of(1) == 0
    evicted = st.invalidate([1])
    assert evicted == 1 and st.invalidations == 1
    assert st.version_of(1) == 1
    after = st.replay_batch(trace)
    assert after["hits"] == 2 and after["issued"] == 1   # 1 is a re-read
    with pytest.raises(IndexError, match="out of range"):
        st.invalidate([tiny_layout.num_pages + 5])


@pytest.mark.fast
def test_mutable_store_invalidation_reaches_shard_caches(tiny_layout):
    st = build_store(tiny_layout, batched=True, shards=2,
                     cache_policy="lru",
                     cache_bytes=8 * tiny_layout.page_bytes, mutable=True)
    trace = np.asarray([[[0, 1, -1], [2, 3, -1]]], np.int32)
    st.replay_batch(trace)
    assert st.replay_batch(trace)["hits"] == 4
    st.invalidate([0, 3])
    after = st.replay_batch(trace)
    assert after["hits"] == 2 and after["issued"] == 2


@pytest.mark.fast
def test_mutable_store_notify_append_extends_versions_and_placement(
        tiny_layout):
    st = build_store(tiny_layout, batched=True, shards=2, mutable=True)
    P = tiny_layout.num_pages
    st.notify_append(P + 4)
    assert len(st.page_version) == P + 4
    assert len(st.placement.page_to_shard) == P + 4
    st.note_write([0, 1, 2])
    assert st.counters.pages_written == 3
    assert st.counters.data_writes == 3
    # PR 8: writes forward down the spine like reads (conservation)
    assert st.inner.counters.pages_written == 3
    with pytest.raises(ValueError, match="shrink"):
        st.notify_append(P)


# --------------------------------------------------------------------------
# fast: build_store composition validation (satellite)


@pytest.mark.fast
def test_build_store_rejects_silently_ignored_knobs(tiny_layout):
    with pytest.raises(ValueError, match="cache_bytes=4096 with"):
        build_store(tiny_layout, cache_bytes=4096)
    with pytest.raises(ValueError, match="tenant_shares with tenants=1"):
        build_store(tiny_layout, cache_policy="lru",
                    cache_bytes=8 * tiny_layout.page_bytes,
                    tenant_shares=(0.5, 0.5))
    with pytest.raises(ValueError, match="rebalance_every=16 with"):
        build_store(tiny_layout, cache_policy="lru",
                    cache_bytes=8 * tiny_layout.page_bytes,
                    rebalance_every=16)
    with pytest.raises(ValueError, match="placement='contiguous' with"):
        build_store(tiny_layout, placement="contiguous")


@pytest.mark.fast
def test_mutation_config_validation():
    with pytest.raises(ValueError, match="flush_threshold"):
        MutationConfig(flush_threshold=0)
    with pytest.raises(ValueError, match="insert_alpha"):
        MutationConfig(insert_alpha=0.5)
    with pytest.raises(ValueError, match="leaves no reads"):
        MutationMix(insert_frac=0.7, delete_frac=0.4)
    with pytest.raises(ValueError, match="compaction="):
        MutationMix(insert_frac=0.1, compaction="eager")
    assert MutationMix(insert_frac=0.2, delete_frac=0.1).read_frac \
        == pytest.approx(0.7)
    assert not MutationMix().mutating


# --------------------------------------------------------------------------
# default tier: the merged search path over the Vamana fixture


@pytest.fixture(scope="module")
def mutable_index(base_index):
    return MutableIndex(base_index, MutationConfig(
        flush_threshold=16, growth_chunk=128, insert_L=16,
        compaction_pages=8))


def _fresh(base_index, **kw):
    cfg = dict(flush_threshold=16, growth_chunk=128, insert_L=16,
               compaction_pages=8)
    cfg.update(kw)
    return MutableIndex(base_index, MutationConfig(**cfg))


def test_unmutated_wrapper_is_bit_identical(base_index, small_dataset):
    """The golden facade contract extends to the wrapper: zero mutations =>
    the same bits as DiskIndex.search."""
    mi = _fresh(base_index)
    q = small_dataset.queries[:8]
    a = base_index.search(q)
    b = mi.search(q)
    assert np.array_equal(a.ids, b.ids)
    assert np.array_equal(a.dists, b.dists)
    assert np.array_equal(a.page_reads, b.page_reads)
    assert np.array_equal(a.hops, b.hops)


def test_insert_is_searchable_before_and_after_flush(base_index,
                                                     small_dataset):
    mi = _fresh(base_index)
    rng = np.random.default_rng(3)
    v = (small_dataset.vectors[11]
         + 1e-3 * rng.normal(size=small_dataset.d)).astype(np.float32)
    vid = mi.insert(v)
    assert len(mi.delta) == 1
    res = mi.search(v[None])
    assert res.ids[0, 0] == vid              # delta merge wins the heap
    acct = mi.flush()
    assert acct["flushed"] == 1 and len(mi.delta) == 0
    assert mi.n_disk == vid + 1
    res2 = mi.search(v[None])
    assert res2.ids[0, 0] == vid             # now served from pages
    assert (mi.graph[vid] >= 0).any()        # wired into the graph


def test_delete_filters_and_backfills(base_index, small_dataset):
    mi = _fresh(base_index)
    q = small_dataset.queries[:4]
    before = mi.search(q)
    victim = int(before.ids[0, 0])
    assert mi.delete(victim)
    assert not mi.delete(victim)             # double delete is a no-op
    after = mi.search(q)
    assert victim not in after.ids[0]
    # overfetch backfilled: still k results with finite distances
    assert (after.ids[0] >= 0).all()
    assert np.isfinite(after.dists[0]).all()


def test_delete_of_delta_vid_resolves_in_memory(base_index, small_dataset):
    mi = _fresh(base_index)
    vid = mi.insert(small_dataset.vectors[0])
    assert mi.delete(vid)
    assert len(mi.delta) == 0
    assert len(mi.pending_tombstones) == 0   # never reached disk
    acct = mi.flush()
    assert acct["flushed"] == 0


def test_compaction_purges_tombstones_and_frees_pages(base_index,
                                                      small_dataset):
    mi = _fresh(base_index)
    lay = mi.layout
    # tombstone every record of two pages -> compaction must free them
    victims = np.concatenate([lay.page_vids[3], lay.page_vids[4]])
    for v in victims[victims >= 0]:
        mi.delete(int(v))
    pend = len(mi.pending_tombstones)
    assert pend > 0
    acct = mi.compact(max_pages=4)
    assert acct["purged"] == pend
    assert len(mi.pending_tombstones) == 0
    assert len(mi.free_pages) >= 1           # wholly-freed pages reclaimed
    # no live edge points at a purged vertex any more
    live_rows = mi.graph[:mi.n_disk][~mi.deleted[:mi.n_disk]]
    assert not np.isin(live_rows[live_rows >= 0],
                       victims[victims >= 0]).any()
    # purged vertices never come back
    res = mi.search(small_dataset.vectors[int(victims[0])][None])
    assert victims[0] not in res.ids[0]


def test_reverse_index_stays_consistent_through_mutations(base_index,
                                                          small_dataset):
    """The incrementally maintained reverse adjacency (what purge uses to
    find in-edges without a full-graph scan) must equal a from-scratch
    rebuild after any interleaving of flushes, deletes and compactions."""
    mi = _fresh(base_index)
    rng = np.random.default_rng(21)
    for wave in range(2):
        for i in range(20):
            a, b = rng.integers(0, small_dataset.n, 2)
            mi.insert(0.5 * (small_dataset.vectors[a]
                             + small_dataset.vectors[b]))
        for _ in range(6):
            vid = mi.random_live_vid(rng)
            if vid is not None:
                mi.delete(vid)
        mi.flush()
        mi.compact(max_pages=8)
    rebuilt = [set() for _ in range(mi.capacity)]
    src, col = np.nonzero(mi.graph >= 0)
    for u, v in zip(src.tolist(), mi.graph[src, col].tolist()):
        rebuilt[v].add(int(u))
    bad = [v for v in range(mi.capacity) if rebuilt[v] != mi._rev[v]]
    assert not bad, (bad[:5], [(rebuilt[v], mi._rev[v]) for v in bad[:2]])
    # and no live edge points at a PURGED vertex (pending tombstones may
    # still be routed through — only purge severs them)
    purged_mask = mi.deleted.copy()
    for t in mi.pending_tombstones:
        purged_mask[t] = False
    live_rows = mi.graph[:mi.n_disk][~mi.deleted[:mi.n_disk]]
    edges = live_rows[live_rows >= 0]
    assert not purged_mask[edges].any()


def test_purging_the_medoid_reelects_an_entry_point(base_index,
                                                    small_dataset):
    """Deleting the medoid keeps routing through its record; PURGING it
    would strand every search at an edgeless entry — compaction must
    re-elect a live medoid."""
    mi = _fresh(base_index)
    old = mi.medoid
    mi.delete(old)
    mid = mi.search(small_dataset.queries[:4])      # tombstone still routes
    assert (mid.ids[0] >= 0).all()
    mi.compact(max_pages=2)                         # its page is dirty
    assert old not in mi.pending_tombstones
    assert mi.medoid != old
    assert not mi.deleted[mi.medoid]
    res = mi.search(small_dataset.queries[:4])
    assert (res.ids >= 0).all()
    assert np.isfinite(res.dists).all()


def test_flush_reuses_freed_pages(base_index, small_dataset):
    mi = _fresh(base_index)
    lay = mi.layout
    for v in lay.page_vids[5]:
        if v >= 0:
            mi.delete(int(v))
    mi.compact(max_pages=1)
    assert 5 in mi.free_pages
    P = lay.num_pages
    for i in range(lay.n_p):
        mi.insert(small_dataset.vectors[i])
    mi.flush()
    assert lay.num_pages == P                # appended into the freed page
    assert (lay.page_vids[5] >= 0).any()


def test_serving_mutation_mix_reports_outcomes(base_index, small_dataset):
    from repro.serving import AnnServer, ServerConfig
    mi = _fresh(base_index)
    srv = AnnServer(mi, server_cfg=ServerConfig(max_batch=8))
    pool = small_dataset.vectors[:64].astype(np.float32)
    mix = MutationMix(insert_frac=0.25, delete_frac=0.1,
                      compaction="threshold", threshold=0.05, max_pages=8,
                      seed=5)
    rep = srv.serve_open_loop(small_dataset.queries, rate_qps=4000.0,
                              duration_us=40000.0, mutation_mix=mix,
                              insert_pool=pool)
    assert rep.inserts > 0 and rep.deletes > 0
    assert rep.flushes >= 1
    assert rep.compactions >= 1
    assert rep.bg_pages_written > 0 and rep.bg_io_us > 0
    assert 0 < rep.bg_util < 1
    assert rep.overlap_ratio > 0
    row = rep.row()
    for col in ("inserts", "deletes", "flushes", "compactions", "bg_util",
                "overlap_ratio"):
        assert col in row
    # reads completed despite the mutation interleave
    assert rep.completed == rep.admitted > 0
    # a pure-read report keeps its columns clean
    rep0 = srv.serve_open_loop(small_dataset.queries, rate_qps=2000.0,
                               duration_us=10000.0)
    assert "inserts" not in rep0.row()


def test_serving_mutation_requires_mutable_index(base_index, small_dataset):
    from repro.serving import AnnServer, ServerConfig
    srv = AnnServer(base_index, server_cfg=ServerConfig(max_batch=8))
    with pytest.raises(ValueError, match="MutableIndex"):
        srv.serve_open_loop(small_dataset.queries, rate_qps=100.0,
                            duration_us=1000.0,
                            mutation_mix=MutationMix(insert_frac=0.5))
    mi = _fresh(base_index)
    srv2 = AnnServer(mi, server_cfg=ServerConfig(max_batch=8))
    with pytest.raises(ValueError, match="insert_pool"):
        srv2.serve_open_loop(small_dataset.queries, rate_qps=100.0,
                             duration_us=1000.0,
                             mutation_mix=MutationMix(insert_frac=0.5))


def test_replicated_placement_without_profile_warns_and_falls_back(
        base_index, small_dataset):
    """Satellite: AnnServer over placement='replicated' with no
    page_profile must not crash — it warns and serves round-robin."""
    from repro.serving import AnnServer, ServerConfig
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        srv = AnnServer(base_index,
                        server_cfg=ServerConfig(max_batch=4, shards=2,
                                                placement="replicated"))
    assert any("round-robin" in str(x.message) for x in w)
    assert not srv.store.placement.replicated.any()
    rep = srv.serve_closed_loop(small_dataset.queries[:8], workers=2)
    assert rep.queries == 2


def test_mutable_serving_matches_facade_results(base_index, small_dataset):
    """Through the server, a mutated index returns the same merged results
    the facade returns for the same queries (order of dispatch aside)."""
    from repro.serving import AnnServer, ServerConfig
    mi = _fresh(base_index)
    for i in range(20):
        mi.insert(small_dataset.vectors[i])
    mi.flush()
    mi.delete(int(mi.search(small_dataset.queries[:1]).ids[0, 0]))
    srv = AnnServer(mi, server_cfg=ServerConfig(max_batch=4))
    q = small_dataset.queries[:8]
    rep = srv.serve_closed_loop(q, workers=2, rounds=4)
    facade = mi.search(q)
    for qi, ids in zip(rep.query_indices, rep.stats.ids):
        assert np.array_equal(ids, facade.ids[qi])


# --------------------------------------------------------------------------
# slow: the decay-and-repair property (the PR's acceptance story)


@pytest.fixture(scope="module")
def shuffled_index(small_dataset, small_graph):
    """A page-shuffled index: high build-time overlap_ratio, so locality
    decay under appends is unambiguous."""
    from repro.core import build_index, get_preset
    G, med, _ = small_graph
    return build_index(small_dataset, get_preset("pageshuffle"),
                       graph=G, medoid_id=med)


@pytest.mark.slow
def test_overlap_decays_without_compaction_and_recovers_with_it(
        shuffled_index, small_dataset):
    """Sustained inserts through append flushes degrade live-vertex
    overlap_ratio monotonically with compaction=none; the same workload
    under bounded compaction lands strictly better on overlap AND purges
    the tombstone backlog."""
    n = small_dataset.n

    def drive(compaction: bool):
        rng = np.random.default_rng(9)
        mi = _fresh(shuffled_index, flush_threshold=16)
        ors = [mi.overlap_ratio()]
        for wave in range(4):
            for j in range(32):
                a, b = rng.integers(0, n, 2)
                mid = 0.5 * (small_dataset.vectors[a]
                             + small_dataset.vectors[b])
                mi.insert(mid.astype(np.float32))
                if j % 8 == 0:
                    vid = mi.random_live_vid(rng)
                    if vid is not None:
                        mi.delete(vid)
                if mi.needs_flush:
                    mi.flush()
                    if compaction:
                        while mi.dirty_fraction > 0.05:
                            mi.compact(max_pages=16)
            ors.append(mi.overlap_ratio())
        return mi, ors

    mi_none, ors_none = drive(False)
    mi_comp, ors_comp = drive(True)
    # monotone decay without repair
    assert all(b <= a for a, b in zip(ors_none, ors_none[1:])), ors_none
    assert ors_none[-1] < ors_none[0]
    # repair recovers locality and consumes the backlog
    assert ors_comp[-1] > ors_none[-1]
    assert len(mi_comp.pending_tombstones) < len(mi_none.pending_tombstones)
    assert len(mi_comp.dirty_pages) < len(mi_none.dirty_pages)


# --------------------------------------------------------------------------
# default tier: a reserved capacity under a seeded churn stream through
# AnnServer — the calls bench/run.py's make_mutate makes


class _CompileCount:
    """Backend compiles in this process, from jax.monitoring (the event the
    benchmark's window line counts)."""

    count = 0
    _on = False

    @classmethod
    def start(cls):
        if not cls._on:
            import jax

            def hear(event, _secs, **_kw):
                if event == "/jax/core/compile/backend_compile_duration":
                    cls.count += 1
            jax.monitoring.register_event_duration_secs_listener(hear)
            cls._on = True
        return cls.count


def _live_topk(vecs, live_vids, q, k):
    """Plain numpy brute force: the k nearest live vids of each query."""
    X = vecs[live_vids].astype(np.float64)
    d = np.square(q[:, None, :].astype(np.float64) - X[None]).sum(-1)
    return live_vids[np.argsort(d, axis=1, kind="stable")[:, :k]]


def _churn(base_index, small_dataset, *, capacity, steps, seed=17):
    """A seeded stream: 2 served batches of 8, then 16 inserts (held-out
    midpoints of base rows) and 16 uniform deletes of live vids, each
    insert followed by maybe_flush and the compactor's after_mutation, each
    delete by after_mutation, each batch by after_batch. Returns the
    server, the compiles after its warm call, every answer with the live
    set it was served over, and the books."""
    from repro.serving import AnnServer, ServerConfig
    n, k = small_dataset.n, base_index.cfg.k
    mi = MutableIndex(base_index, MutationConfig(
        flush_threshold=16, insert_L=16, capacity=capacity))
    srv = AnnServer(mi, server_cfg=ServerConfig(max_batch=8))
    comp = Compactor(mi, MutationMix(compaction="threshold", threshold=0.02,
                                     max_pages=8))
    rng = np.random.default_rng(seed)
    vecs = np.zeros((n + 16 * steps, small_dataset.d), np.float32)
    vecs[:n] = small_dataset.vectors
    live = list(range(n))
    queries = small_dataset.queries
    srv.serve_closed_loop(queries[:8], workers=8)          # the warm call
    before = _CompileCount.start()
    served = []
    for step in range(steps):
        for _ in range(2):
            q = queries[rng.choice(len(queries), 8, replace=False)]
            rep = srv.serve_closed_loop(q, workers=8)
            inv = np.argsort(rep.query_indices, kind="stable")
            served.append((q, rep.stats.ids[inv], np.asarray(live)))
            comp.after_batch()
        for _ in range(16):
            a, b = rng.integers(0, n, 2)
            vec = 0.5 * (small_dataset.vectors[a] + small_dataset.vectors[b])
            vid = mi.insert(vec)
            vecs[vid] = vec
            live.append(vid)
            mi.maybe_flush()
            comp.after_mutation()
        for _ in range(16):
            vid = live.pop(int(rng.integers(len(live))))
            assert mi.delete(vid)
            comp.after_mutation()
    compiles = _CompileCount.start() - before
    return srv, compiles, served, vecs, np.asarray(live)


def _check_against_brute_force(mi, served, vecs, live, floor):
    k = mi.cfg.k
    hits = total = 0
    for q, ids, live_then in served:
        ref = _live_topk(vecs, live_then, q, k)
        assert np.isin(ids[ids >= 0], live_then).all(), "dead id served"
        hits += sum(len(np.intersect1d(a, b)) for a, b in zip(ids, ref))
        total += ref.size
    assert hits / total >= floor, hits / total
    # every inserted row still live finds its own vid
    fresh = live[live >= len(mi.base.layout.vid2page)]
    got = mi.search(vecs[fresh]).ids
    assert (got == fresh[:, None]).any(axis=1).all()


@pytest.fixture(scope="module")
def churned(base_index, small_dataset):
    return _churn(base_index, small_dataset,
                  capacity=small_dataset.n + 16 * 12, steps=12)


def test_churn_stream_matches_brute_force_over_the_live_set(churned):
    """Recall@10 of every served answer against a numpy brute force over
    the vids live when it was served (floor 0.90), no dead id, and every
    live insert finds itself; the reserve held."""
    srv, _, served, vecs, live = churned
    mi = srv.index
    st = mi.mutation_stats()
    assert st["flushes"] >= 10 and st["compactions"] >= 1
    assert st["capacity_growths"] == 0
    assert st["rows_flushed"] == 16 * st["flushes"]
    assert st["live"] == len(live)
    _check_against_brute_force(mi, served, vecs, live, floor=0.90)


def test_churn_stream_compiles_nothing_after_the_warm_call(churned):
    """Construction (MutableIndex compiles flush's two programs at the
    reserved shapes) and one served call (the over-fetch kernel shape)
    warm everything the stream runs: no compile after them."""
    srv, compiles, _, _, _ = churned
    assert compiles == 0
    st = srv.index.mutation_stats()
    # counters of what the device was sent again
    per_flush = srv.index.vectors.nbytes + srv.index.graph.nbytes
    assert st["flush_upload_bytes"] == st["flushes"] * per_flush
    assert st["reupload_bytes"] > 0


def test_churn_past_the_reserve_grows_and_stays_correct(base_index,
                                                        small_dataset):
    """A stream that outgrows `capacity` grows the arrays (and recompiles,
    counted by capacity_growths) and still answers over the live set."""
    srv, _, served, vecs, live = _churn(
        base_index, small_dataset, capacity=small_dataset.n + 16, steps=4,
        seed=5)
    mi = srv.index
    assert mi.mutation_stats()["capacity_growths"] >= 1
    assert mi.capacity > small_dataset.n + 16
    _check_against_brute_force(mi, served, vecs, live, floor=0.90)


def test_reserve_and_overfetch_change_nothing_unmutated(base_index,
                                                        small_dataset):
    """A never-mutated MutableIndex with a reserve, served through AnnServer
    (whose disk side always runs k + overfetch), returns the frozen
    path's ids, distances and counters; so does its facade."""
    from repro.serving import AnnServer, ServerConfig
    mi = MutableIndex(base_index, MutationConfig(
        flush_threshold=16, insert_L=16, capacity=small_dataset.n + 500))
    assert mi.capacity == small_dataset.n + 500
    assert mi.layout.num_pages > base_index.layout.num_pages
    assert mi.disk_cfg().k == mi.cfg.k + mi.mcfg.overfetch
    q = small_dataset.queries[:16]
    scfg = ServerConfig(max_batch=8)
    a = AnnServer(base_index, server_cfg=scfg).serve_closed_loop(q, 8, 2)
    b = AnnServer(mi, server_cfg=scfg).serve_closed_loop(q, 8, 2)
    for f in ("ids", "dists", "page_reads", "hops", "pq_evals",
              "full_evals"):
        assert np.array_equal(getattr(a.stats, f), getattr(b.stats, f)), f
    assert np.array_equal(a.query_indices, b.query_indices)
    fa, fb = base_index.search(q), mi.search(q)
    assert np.array_equal(fa.ids, fb.ids)
    assert np.array_equal(fa.dists, fb.dists)
    assert mi.mutation_stats()["capacity_growths"] == 0


@pytest.mark.fast
def test_mutation_config_rejects_negative_capacity():
    with pytest.raises(ValueError, match="capacity"):
        MutationConfig(capacity=-1)
