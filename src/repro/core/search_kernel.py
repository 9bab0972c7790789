"""Kernel layer: the pure jitted beam-search over store-provided page arrays.

This is the compute core of the engine split (I/O layer: repro/io/,
serving layer: repro/serving/ann_server.py). `_search_batch` is a pure
function of the page arrays a `PageStore` exposes — it never touches the
store object itself, so the same kernel serves the in-memory facade, the
cached store and the batch-coalescing server path.

Besides the per-query scalar counters, the kernel emits `visited_pages`, a
(B, num_pages) bitmap of the pages each query charged to the device. The
scalar `page_reads` counter dedups pages only *within* a step (exactly the
pre-refactor accounting, kept bit-identical for the golden facade test);
the bitmap is what lets `BatchedPageStore` dedup across queries and steps.

When `track_trace` is set it additionally emits `page_trace`, a
(B, max_iters, w_cap) int32 array: row (b, h) holds the distinct pages
query b charged at hop h, -1 padded — the same pages as the bitmap but in
TEMPORAL order, which is what the stateful cache subsystem
(repro/io/page_cache.py: LRU/FIFO/2Q replay, look-ahead prefetch) consumes.
Both trackers are static flags, so untracked carries compile out entirely.

The kernel's stages run under `jax.named_scope` — `pq_lut`, `pq_lookup`,
`select`, `page_gather`, `exact_dist`, `merge`, `rerank` — so each device
op's `op_name` metadata names its stage; scopes change no arithmetic.

The ADC table lookup under `pq_lookup` has one result in two forms,
picked per platform when the step is lowered (`jax.lax.platform_dependent`):
the TPU runs `adc_lookup_select`, a compare of each code with the 256
centroid indices and a max over them, since its gather of single table
elements took about four fifths of the chip's time in the step; every other
platform runs `adc_lookup_gather`, which the CPU does natively (0.6 ms
against the select's 313 ms for 16 queries x 2,048 neighbours x 16
sub-quantizers on a Xeon CPU). Both give the same values, bit for bit.

Technique mapping (SearchConfig):
  PQ            — always on (the paper's §6 baseline): neighbors ranked by
                  memory-resident ADC distances; exact distances only for
                  records whose page was fetched.
  Cache         — `cached` vertex mask: frontier reads of cached vertices are
                  free (served from memory).
  MemGraph      — entry points supplied by the navigation layer instead of
                  the medoid.
  PageShuffle   — a different PageLayout (perm); kernel unchanged.
  AiS           — smaller n_p / bigger records (layout), memory freed.
  DynamicWidth  — beam width schedule: w starts at w_min, doubles each
                  iteration the best candidate set stops improving (approach
                  -> converge phase detection, PipeANN-style).
  Pipeline      — speculative frontier: issues reads for `spec` extra
                  candidates per step (extra I/O, overlapped latency —
                  reproduces Finding 5); on TPU this is the double-buffered
                  DMA in kernels/page_scan.py.
  PageSearch    — every record of a fetched page is scored exactly and
                  inserted into the pool (raises per-page utility).
"""
from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.searchutils import (INF, SENTINEL, dedup_merge_topL, sq_dists,
                                    top_w_unexpanded)
from repro.core.stats import QueryStats
from repro.obs import span


def adc_lookup_gather(lut, codes):
    """ADC table lookup `lut[m, codes[n, m]]`, (N, M), as one gather of
    table elements: lut (M, 256) f32, codes (N, M) uint8. The CPU's form."""
    return jnp.take_along_axis(lut.T, codes.astype(jnp.int32), axis=0)


def adc_lookup_select(lut, codes):
    """The same (N, M) values as `adc_lookup_gather`, bit for bit, without a
    gather: each code is compared with every centroid index and the hit
    entry kept by a max over the 256 axis (the table holds squared
    distances, all >= 0, so a fill of 0 is exact). The 256 axis is major
    and reduced inside one fusion, so N stays in lanes and the
    (256, M, N) select is never materialized. The TPU's form."""
    hit = (codes.T.astype(jnp.int32)[None]
           == jnp.arange(lut.shape[1], dtype=jnp.int32)[:, None, None])
    return jnp.max(jnp.where(hit, lut.T[:, :, None], 0.0), axis=0).T


@functools.partial(
    jax.jit,
    static_argnames=("k", "L", "width", "max_iters", "n_p", "page_search",
                     "dynamic_width", "dw_min", "dw_max", "pipeline", "spec",
                     "track_visited", "track_trace"))
def _search_batch(page_vids, page_vecs, page_nbrs, vid2page, vid2slot,
                  pq_centroids, pq_codes, cached, q, entries, entry_valid, *,
                  k, L, width, max_iters, n_p, page_search, dynamic_width,
                  dw_min, dw_max, pipeline, spec, track_visited=True,
                  track_trace=False):
    n = vid2page.shape[0]
    num_pages = page_vids.shape[0]
    m, ksub, dsub = pq_centroids.shape
    width = max(width, dw_max) if dynamic_width else width
    width = min(width, L)   # frontier can never exceed the candidate pool
    w_cap = min(width + (spec if pipeline else 0), L)

    def one(qv, ent, ent_ok):
        with jax.named_scope("pq_lut"):
            lut = jnp.sum(jnp.square(pq_centroids
                                     - qv.reshape(m, 1, dsub)),
                          axis=-1)                      # (M,256)

        def pq_dist(ids):
            with jax.named_scope("pq_lookup"):
                safe = jnp.minimum(jnp.maximum(ids, 0), n - 1)
                codes = pq_codes[safe]                  # (N, M)
                d = jax.lax.platform_dependent(
                    lut, codes, tpu=adc_lookup_select,
                    default=adc_lookup_gather)          # (N, M)
                return jnp.sum(d, axis=-1)

        # candidate list: keys = [rank_key, exact_dist]; flags = [expanded,
        # exact_known]
        cap = L + w_cap * (n_p if page_search else 0) + w_cap * page_nbrs.shape[2]
        e_pq = pq_dist(ent)
        with jax.named_scope("merge"):
            ids0 = jnp.where(ent_ok, ent, SENTINEL)
            pad = cap - ids0.shape[0]
            ids = jnp.concatenate([ids0,
                                   jnp.full((pad,), SENTINEL, jnp.int32)])
            keys = jnp.stack([jnp.where(ent_ok, e_pq, INF),
                              jnp.full(ids0.shape, INF)], 1)
            keys = jnp.concatenate([keys, jnp.full((pad, 2), INF)], 0)
            flags = jnp.zeros((cap, 2), bool)
            ids, keys, flags = dedup_merge_topL(ids, keys, flags, L)

        zero = jnp.zeros((), jnp.float32)
        # visited[p] = page p was charged to the device at least once; slot
        # num_pages is the trash slot for "-1 / cached" entries. When the
        # caller doesn't track bitmaps the carry shrinks to one element and
        # the per-step scatter compiles out entirely (track_visited is
        # static).
        visited0 = jnp.zeros(((num_pages + 1) if track_visited else 1,), bool)
        # trace[h] = the distinct pages charged at hop h (-1 padded); shrinks
        # to (1, 1) and the row write compiles out when untracked
        trace0 = jnp.full((max_iters, w_cap) if track_trace else (1, 1),
                          -1, jnp.int32)
        # metrics: pages, cache_hits, nread, neff, fulle, pqe, hops
        met0 = (zero,) * 6
        st0 = (ids, keys, flags, jnp.int32(0), jnp.float32(dw_min),
               zero, visited0, trace0) + met0

        def cond(st):
            ids, keys, flags, it = st[0], st[1], st[2], st[3]
            with jax.named_scope("select"):
                open_ = jnp.any((ids < SENTINEL) & ~flags[:, 0]
                                & (keys[:, 0] < INF))
                return open_ & (it < max_iters)

        def body(st):
            (ids, keys, flags, it, w_dyn, stall, visited, trace,
             pages_m, cache_m, nread_m, neff_m, full_m, pq_m_) = st
            best_before = keys[0, 0]

            with jax.named_scope("select"):
                w_now = (jnp.minimum(jnp.float32(dw_max), w_dyn)
                         if dynamic_width else jnp.float32(width))
                w_sel = jnp.minimum(w_now,
                                    jnp.float32(width)).astype(jnp.int32)
                fidx, active = top_w_unexpanded(
                    keys[:, 0], flags[:, 0], ids < SENTINEL, w_cap,
                    w_dynamic=(w_sel + (spec if pipeline else 0)))
                # pipeline: the first w_sel are confirmed, the rest
                # speculative
                fids = jnp.where(active, ids[fidx], SENTINEL)
                neff_m = neff_m + jnp.sum(
                    active & (jnp.arange(w_cap) < w_sel))

                # --- page fetch accounting ----------------------------------
                safe_f = jnp.minimum(jnp.maximum(fids, 0), n - 1)
                fpages = jnp.where(fids < SENTINEL, vid2page[safe_f], -1)
                is_cached = (fids < SENTINEL) & cached[safe_f]
                # unique non-cached pages this step
                chargeable = jnp.where(is_cached, -1, fpages)
                srt = jnp.sort(chargeable)
                uniq = (srt >= 0) & jnp.concatenate(
                    [jnp.ones((1,), bool), srt[1:] != srt[:-1]])
                pages_step = jnp.sum(uniq).astype(jnp.float32)
                pages_m = pages_m + pages_step
                cache_m = cache_m + jnp.sum(is_cached).astype(jnp.float32)
                nread_m = nread_m + pages_step * n_p
                if track_visited:
                    visited = visited.at[
                        jnp.where(chargeable >= 0, chargeable,
                                  num_pages)].set(True)
                if track_trace:
                    # the step's distinct charged pages, in one row of the
                    # trace
                    trace = trace.at[it].set(jnp.where(uniq, srt, -1))

            # --- fetch records ----------------------------------------------
            with jax.named_scope("page_gather"):
                pg = jnp.maximum(fpages, 0)
                rec_vids = page_vids[pg]                    # (w_cap, n_p)
                rec_vecs = page_vecs[pg]                    # (w_cap, n_p, d)
                rec_nbrs = page_nbrs[pg, vid2slot[safe_f]]  # (w_cap, R)
                page_ok = (fids < SENTINEL)

            with jax.named_scope("exact_dist"):
                # exact distance for every record on fetched pages
                rd = jax.vmap(lambda vs: sq_dists(qv, vs))(
                    rec_vecs)                               # (w_cap, n_p)
                rec_valid = (rec_vids >= 0) & page_ok[:, None]
                full_m = full_m + jnp.sum(rec_valid).astype(jnp.float32)

                # frontier's own exact distances (re-rank info, always used)
                own = rec_vids == jnp.where(fids < SENTINEL, fids,
                                            -2)[:, None]
                own_ids = jnp.where(page_ok, fids, SENTINEL)
                own_d = jnp.where(page_ok,
                                  jnp.sum(jnp.where(own, rd, 0.0), 1), INF)

            # --- assemble merge inputs --------------------------------------
            with jax.named_scope("merge"):
                parts_ids = [ids, own_ids]
                parts_rank = [keys[:, 0], own_d]
                parts_exact = [keys[:, 1], own_d]
                parts_exp = [flags[:, 0], page_ok]
                parts_exk = [flags[:, 1], page_ok]

                if page_search:
                    pr_ids = jnp.where(rec_valid, rec_vids,
                                       SENTINEL).reshape(-1)
                    pr_d = jnp.where(rec_valid, rd, INF).reshape(-1)
                    parts_ids.append(pr_ids)
                    parts_rank.append(pr_d)
                    parts_exact.append(pr_d)
                    parts_exp.append(jnp.zeros_like(pr_ids, bool))
                    parts_exk.append(pr_ids < SENTINEL)

                nb = jnp.where(page_ok[:, None] & (rec_nbrs >= 0),
                               rec_nbrs, SENTINEL).reshape(-1)
            nb_d = pq_dist(nb)
            with jax.named_scope("merge"):
                nb_pq = jnp.where(nb < SENTINEL, nb_d, INF)
                pq_m_ = pq_m_ + jnp.sum(nb < SENTINEL).astype(jnp.float32)
                parts_ids.append(nb)
                parts_rank.append(nb_pq)
                parts_exact.append(jnp.full_like(nb_pq, INF))
                parts_exp.append(jnp.zeros_like(nb, bool))
                parts_exk.append(jnp.zeros_like(nb, bool))

                all_ids = jnp.concatenate(parts_ids)
                all_keys = jnp.stack([jnp.concatenate(parts_rank),
                                      jnp.concatenate(parts_exact)], 1)
                all_flags = jnp.stack([jnp.concatenate(parts_exp),
                                       jnp.concatenate(parts_exk)], 1)
                ids, keys, flags = dedup_merge_topL(all_ids, all_keys,
                                                    all_flags, L)
                # expanded entries keep exact distance as ranking key
                keys = keys.at[:, 0].set(
                    jnp.where(flags[:, 1], keys[:, 1], keys[:, 0]))

                # dynamic width phase detection: no improvement => converge
                # phase
                improved = keys[0, 0] < best_before
                stall = jnp.where(improved, 0.0, stall + 1.0)
                w_dyn = jnp.where(dynamic_width & (stall > 0),
                                  jnp.minimum(w_dyn * 2.0,
                                              jnp.float32(dw_max)),
                                  w_dyn)
            return (ids, keys, flags, it + 1, w_dyn, stall, visited, trace,
                    pages_m, cache_m, nread_m, neff_m, full_m, pq_m_)

        out = jax.lax.while_loop(cond, body, st0)
        ids, keys, flags, it = out[0], out[1], out[2], out[3]
        visited, trace = out[6], out[7]
        pages_m, cache_m, nread_m, neff_m, full_m, pq_m_ = out[8:14]

        # final top-k by exact distance (re-rank among exact-known)
        with jax.named_scope("rerank"):
            final_key = jnp.where(flags[:, 1], keys[:, 1], INF)
            order = jnp.argsort(final_key)[:k]
            topk = jnp.where(final_key[order] < INF, ids[order], -1)
            topd = final_key[order]
        out = {"ids": topk, "dists": topd, "hops": it,
               "page_reads": pages_m, "cache_hits": cache_m,
               "n_read": nread_m, "n_eff": neff_m,
               "full_evals": full_m, "pq_evals": pq_m_}
        if track_visited:
            out["visited_pages"] = visited[:num_pages]
        if track_trace:
            out["page_trace"] = trace
        return out

    return jax.vmap(one)(q, entries, entry_valid)


# ---------------------------------------------------------------------------
# Fused-pipeline measurement surface (SearchConfig.pipeline == "fused"):
# results still come from _search_batch above (bit-identical to
# pipeline=True — the golden facade test pins it); the traced page schedule
# is then RE-EXECUTED through the fused double-buffered Pallas kernel
# (kernels/fused_search.py) to produce a measured wall-clock step time the
# analytic prefetch_overlap rebate can be compared against.

# interpret-mode grid steps are Python-priced, so cap the measured slice of
# the schedule and extrapolate by the per-page rate
MEASURE_PAGES_CAP = int(os.environ.get("REPRO_FUSED_MEASURE_PAGES", 256))


def hop_major_schedule(page_trace: np.ndarray) -> np.ndarray:
    """The batch's page stream in hop-major order: hop t's distinct pages
    (the batch union — what one pipelined grid would stage for the whole
    dispatch), then hop t+1's, exactly the order the LAANN-style look-ahead
    issues them. page_trace (B, max_iters, w), -1 padded."""
    trace = np.asarray(page_trace)
    out = []
    for h in range(trace.shape[1]):
        pages = np.unique(trace[:, h, :])
        out.append(pages[pages >= 0])
    return (np.concatenate(out) if out else np.zeros(0, np.int64))


def query_luts(pq_centroids, queries):
    """Per-query ADC LUTs (Q, M, 256): squared subspace distances from each
    query's subvectors to every centroid — the fused kernel's stacked-LUT
    operand (one MXU matmul per subspace covers the whole query block)."""
    cent = jnp.asarray(pq_centroids)
    m, ksub, dsub = cent.shape
    qs = jnp.asarray(queries, jnp.float32).reshape(-1, m, 1, dsub)
    return jnp.sum(jnp.square(cent[None] - qs), axis=-1)


def _page_codes(store, pq):
    """(P, n_p, M) uint8 page-aligned PQ codes (the residents' codes laid
    out like the vector tiles, so the fused kernel's code DMA mirrors the
    page DMA). Memoized on the store next to its kernel arrays."""
    cached = getattr(store, "_device_page_codes", None)
    if cached is None or cached.shape[0] != store.layout.num_pages:
        vids = store.layout.page_vids
        safe = np.clip(vids, 0, pq.codes.shape[0] - 1)
        codes = np.ascontiguousarray(pq.codes[safe])
        codes[vids < 0] = 0
        cached = jnp.asarray(codes)
        store._device_page_codes = cached
    return cached


def measure_step_us(store, pq, queries, page_trace, *,
                    mode: str = "fused",
                    max_pages: int | None = None) -> dict:
    """Wall-clock one batch's page schedule through the kernel hot path.

    mode="fused": kernels.fused_page_rank — ONE pipelined grid, page DMA of
    step i+1 double-buffered behind the fused exact-scan + ADC compute of
    step i. mode="split": the two separately-jitted grids it replaces
    (kernels.page_scan, then kernels.page_adc), run back to back.

    Returns {"wall_us", "pages", "us_per_page"}; the schedule is capped at
    `max_pages` (default MEASURE_PAGES_CAP) and the per-page rate is what
    callers scale by a query's own page count. Compilation is excluded (one
    warm-up call per shape bucket; the bucketed wrappers in kernels/ops.py
    keep the bucket count small)."""
    from repro import kernels as ops
    sched = hop_major_schedule(page_trace)
    cap = MEASURE_PAGES_CAP if max_pages is None else max_pages
    if cap > 0:
        sched = sched[:cap]
    if len(sched) == 0:
        return {"wall_us": 0.0, "pages": 0, "us_per_page": 0.0}
    _, vecs, _, _, _ = store.kernel_arrays()
    codes = _page_codes(store, pq)
    qb = jnp.asarray(queries, jnp.float32)
    lut = query_luts(pq.centroids, qb)
    ids = jnp.asarray(sched, jnp.int32)
    if mode == "fused":
        def fn():
            return ops.fused_page_rank(vecs, codes, ids, qb, lut)
    elif mode == "split":
        def fn():
            return (ops.page_scan(vecs, ids, qb),
                    ops.page_adc(codes, ids, lut))
    else:
        raise ValueError(f"mode={mode!r} must be 'fused' or 'split'")
    jax.block_until_ready(fn())      # compile + warm the bucket
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    wall = (time.perf_counter() - t0) * 1e6
    return {"wall_us": wall, "pages": len(sched),
            "us_per_page": wall / len(sched)}


def search_batched(store, pq, cfg, queries: np.ndarray, *,
                   medoid: int, memgraph=None, batch: int = 256,
                   collect_visited: bool = True,
                   collect_trace: bool = False,
                   account_kernel_io: bool = True) -> QueryStats:
    """Python driver: feed query batches through the jitted kernel, with page
    data and the cache mask supplied by `store` (any repro.io PageStore).

    This is the single search path behind both `DiskIndex.search` (the
    compatibility facade) and the serving layer's batch executor.
    `collect_trace` adds the temporally ordered per-hop page trace the
    stateful cache subsystem replays (QueryStats.page_trace).

    With `cfg.pipeline == "fused"` the trace is collected regardless (it IS
    the fused kernel's page schedule), the search results stay bit-identical
    to `pipeline=True`, and each batch's schedule is re-executed through the
    fused pipelined kernel: QueryStats.measured_step_us carries each query's
    measured kernel wall clock (its page count x the batch's measured
    per-page rate) next to the modeled device time.

    Each batch's host work runs under the wall-clock spans
    `ann.search.memgraph`, `ann.search.launch` and `ann.search.pull`
    (repro.obs.span: recorded only while a profiler trace runs).
    """
    fused = cfg.pipeline == "fused"
    track_trace = collect_trace or fused
    vids, vecs, nbrs, v2p, v2s = store.kernel_arrays()
    # the device copy of the vertex cache mask is memoized on the store
    # (same rationale as kernel_arrays: the serving layer calls this once
    # per dispatched micro-batch)
    cached = getattr(store, "_device_cache_mask", None)
    if cached is None:
        cached = jnp.asarray(store.vertex_cache_mask())
        store._device_cache_mask = cached
    # device copies of the PQ tables are memoized on the PQ object — the
    # serving layer calls this once per dispatched micro-batch, and
    # re-uploading the (n, m) code matrix each time would dominate
    pq_dev = getattr(pq, "_device_arrays", None)
    if pq_dev is None:
        pq_dev = (jnp.asarray(pq.centroids), jnp.asarray(pq.codes))
        pq._device_arrays = pq_dev
    pq_cent, pq_codes = pq_dev
    parts = []
    for s in range(0, len(queries), batch):
        qb = np.asarray(queries[s:s + batch], np.float32)
        if memgraph is not None and cfg.memgraph_frac > 0:
            with span("ann.search.memgraph"):
                mg = memgraph.entry_points(
                    qb, n_entries=cfg.memgraph_entries, L=cfg.memgraph_L)
            entries = mg["entries"]
            mem_hops, mem_evals = mg["hops"], mg["dist_evals"]
        else:
            entries = np.full((len(qb), 1), medoid, np.int32)
            mem_hops = np.zeros(len(qb), np.int32)
            mem_evals = np.zeros(len(qb), np.int32)
        with span("ann.search.launch"):
            valid = entries >= 0
            out = _search_batch(
                vids, vecs, nbrs, v2p, v2s,
                pq_cent, pq_codes, cached,
                jnp.asarray(qb), jnp.asarray(entries), jnp.asarray(valid),
                k=cfg.k, L=cfg.L, width=cfg.beam_width,
                max_iters=cfg.max_iters, n_p=store.layout.n_p,
                page_search=cfg.page_search,
                dynamic_width=cfg.dynamic_width, dw_min=cfg.dw_min,
                dw_max=cfg.dw_max, pipeline=cfg.pipeline,
                spec=cfg.pipeline_spec, track_visited=collect_visited,
                track_trace=track_trace)
        with span("ann.search.pull"):
            out = {k_: np.asarray(v) for k_, v in out.items()}
            out["mem_hops"] = mem_hops
            out["mem_evals"] = mem_evals
            st = QueryStats.from_kernel(out)
        if fused:
            m = measure_step_us(store, pq, qb, out["page_trace"])
            st.measured_step_us = (st.page_reads.astype(np.float64)
                                   * m["us_per_page"])
        if account_kernel_io:
            store.note_kernel_io(st)
        parts.append(st)
    return QueryStats.concat(parts)
