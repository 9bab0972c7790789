"""Vamana graph construction (DiskANN's logical graph), batched in JAX.

Algorithm (Subramanya et al. 2019), batch-parallel variant (parlayANN-style):
start from a random R-regular graph, then two refinement passes (alpha=1.0,
then alpha) — for each batch of nodes: greedy-search the current graph to
collect the visited set V, RobustPrune(V ∪ N(x)) into new out-edges, then add
reverse edges and re-prune overfull nodes. Deterministic given the seed.

Also exports `beam_search_mem`, the in-memory best-first search used for
build, for the MemGraph navigation layer, and as the oracle the page engine
is validated against.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.searchutils import (INF, SENTINEL, dedup_merge_topL, sq_dists,
                                    top_w_unexpanded)


def medoid(x: np.ndarray) -> int:
    mean = x.mean(0)
    return int(np.argmin(((x - mean) ** 2).sum(1)))


# ---------------------------------------------------------------------------
# in-memory best-first / beam search


@functools.partial(jax.jit, static_argnames=("L", "width", "max_iters",
                                             "visited_cap"))
def _beam_search_mem_batch(X, G, entries, entry_valid, q, *, L, width,
                           max_iters, visited_cap):
    """Batched over queries. entries (B, E) int32 (SENTINEL padded).
    Returns dict(ids (B,L), dists (B,L), visited_ids (B,V), visited_dists,
    hops (B,))."""

    def one(qv, ent, ent_ok):
        d0 = jnp.where(ent_ok, sq_dists(qv, X[jnp.minimum(ent, X.shape[0] - 1)]),
                       INF)
        ids = jnp.where(ent_ok, ent, SENTINEL)
        pad = L + width - ids.shape[0]
        ids = jnp.concatenate([ids, jnp.full((pad,), SENTINEL, jnp.int32)])
        keys = jnp.concatenate([d0, jnp.full((pad,), INF)])[:, None]
        flags = jnp.zeros((ids.shape[0], 1), bool)
        ids, keys, flags = dedup_merge_topL(ids, keys, flags, L)

        vis_ids = jnp.full((visited_cap,), SENTINEL, jnp.int32)
        vis_d = jnp.full((visited_cap,), INF)

        def cond(st):
            ids, keys, flags, vis_ids, vis_d, it, vn = st
            frontier_open = jnp.any((ids < SENTINEL) & ~flags[:, 0])
            return frontier_open & (it < max_iters)

        def body(st):
            ids, keys, flags, vis_ids, vis_d, it, vn = st
            fidx, active = top_w_unexpanded(keys[:, 0], flags[:, 0],
                                            ids < SENTINEL, width)
            fids = jnp.where(active, ids[fidx], SENTINEL)
            # record visited (expanded) nodes
            vis_ids = jax.lax.dynamic_update_slice(
                vis_ids, fids, (vn,))
            vis_d = jax.lax.dynamic_update_slice(
                vis_d, jnp.where(active, keys[fidx, 0], INF), (vn,))
            vn = vn + width
            flags = flags.at[fidx, 0].set(flags[fidx, 0] | active)
            # expand neighbors
            nbrs = G[jnp.minimum(fids, X.shape[0] - 1)]          # (w, R)
            nbrs = jnp.where((active[:, None]) & (nbrs >= 0), nbrs, SENTINEL)
            nflat = nbrs.reshape(-1)
            nd = jnp.where(nflat < SENTINEL,
                           sq_dists(qv, X[jnp.minimum(nflat, X.shape[0] - 1)]),
                           INF)
            all_ids = jnp.concatenate([ids, nflat])
            all_keys = jnp.concatenate([keys[:, 0], nd])[:, None]
            all_flags = jnp.concatenate(
                [flags, jnp.zeros((nflat.shape[0], 1), bool)])
            ids, keys, flags = dedup_merge_topL(all_ids, all_keys, all_flags, L)
            return ids, keys, flags, vis_ids, vis_d, it + 1, vn

        st = (ids, keys, flags, vis_ids, vis_d, jnp.int32(0), jnp.int32(0))
        ids, keys, flags, vis_ids, vis_d, it, vn = jax.lax.while_loop(
            cond, body, st)
        return {"ids": ids, "dists": keys[:, 0], "visited_ids": vis_ids,
                "visited_dists": vis_d, "hops": it}

    return jax.vmap(one)(q, entries, entry_valid)


def beam_search_mem(X, G, entry: int, q, L=64, width=1, max_iters=None,
                    visited_cap=None):
    """q: (B, d). Single fixed entry point (the medoid)."""
    B = q.shape[0]
    max_iters = max_iters or (4 * L)
    visited_cap = visited_cap or (width * max_iters)
    entries = jnp.full((B, 1), entry, jnp.int32)
    valid = jnp.ones((B, 1), bool)
    return _beam_search_mem_batch(
        jnp.asarray(X), jnp.asarray(G), entries, valid, jnp.asarray(q),
        L=L, width=width, max_iters=max_iters, visited_cap=visited_cap)


@functools.partial(jax.jit, static_argnames=("L", "width"))
def _insert_candidates(X, G, entry, q, *, L, width):
    """An insert's candidate search (streaming flush): `beam_search_mem`
    from the one entry `entry` (an int32 scalar), compiled as a program of
    its own name so a device trace tells insert wiring from MemGraph
    navigation. Returns (visited ids (B, V), top-L ids (B, L))."""
    B = q.shape[0]
    max_iters = 4 * L
    res = _beam_search_mem_batch(
        X, G, jnp.full((B, 1), entry, jnp.int32), jnp.ones((B, 1), bool), q,
        L=L, width=width, max_iters=max_iters, visited_cap=width * max_iters)
    return res["visited_ids"], res["ids"]


# ---------------------------------------------------------------------------
# RobustPrune


@functools.partial(jax.jit, static_argnames=("R", "alpha"))
def _robust_prune_batch(X, xs_ids, cand_ids, cand_dists, *, R, alpha):
    """Batched RobustPrune. xs_ids (B,), cand_ids (B, C) (SENTINEL pad,
    deduped, may include x itself — removed here), cand_dists (B, C) dist to x.
    Returns (B, R) int32 new out-neighbors (-1 padded)."""

    def one(xid, cids, cd):
        cids = jnp.where(cids == xid, SENTINEL, cids)
        cd = jnp.where(cids == SENTINEL, INF, cd)
        cvecs = X[jnp.minimum(cids, X.shape[0] - 1)]             # (C, d)
        alive = cids < SENTINEL

        def step(i, st):
            alive, out, order_d = st
            key = jnp.where(alive, order_d, INF)
            j = jnp.argmin(key)
            ok = key[j] < INF
            out = out.at[i].set(jnp.where(ok, cids[j], -1))
            # kill candidates dominated by the pick: alpha*d(p,c) <= d(x,c)
            dpc = sq_dists(cvecs[j], cvecs)
            kill = (alpha * alpha) * dpc <= order_d
            alive = alive & ~kill & ok
            alive = alive.at[j].set(False)
            return alive, out, order_d

        out0 = jnp.full((R,), -1, jnp.int32)
        _, out, _ = jax.lax.fori_loop(0, R, step, (alive, out0, cd))
        return out

    return jax.vmap(one)(xs_ids, cand_ids, cand_dists)


# ---------------------------------------------------------------------------
# build


def build_vamana(x: np.ndarray, R=64, L=125, alpha=1.2, seed=0,
                 batch=1024, passes=(1.0, None), log=lambda *a: None):
    """Returns (G (n, R) int32 with -1 padding, medoid id, build stats)."""
    t0 = time.time()
    n, d = x.shape
    rng = np.random.default_rng(seed)
    X = jnp.asarray(x, jnp.float32)
    med = medoid(x)

    # random initial R-regular graph
    G = rng.integers(0, n, (n, R), dtype=np.int64).astype(np.int32)
    G[G == np.arange(n)[:, None]] = (G[G == np.arange(n)[:, None]] + 1) % n
    G = jnp.asarray(G)

    max_iters = max(2 * L // 1, 48)
    vcap = max_iters
    peak_candidates = 0

    for p_i, a in enumerate(passes):
        a = float(a or alpha)
        order = rng.permutation(n)
        for s in range(0, n, batch):
            ids = order[s:s + batch]
            qb = X[ids]
            res = _beam_search_mem_batch(
                X, G, jnp.full((len(ids), 1), med, jnp.int32),
                jnp.ones((len(ids), 1), bool), qb,
                L=L, width=1, max_iters=max_iters, visited_cap=vcap)
            # candidate pool = visited ∪ current out-neighbors
            cur = G[jnp.asarray(ids)]
            cur = jnp.where(cur >= 0, cur, SENTINEL)
            cand = jnp.concatenate([res["visited_ids"], res["ids"], cur], axis=1)
            cd = jnp.concatenate(
                [res["visited_dists"], res["dists"],
                 jax.vmap(lambda q_, c_: sq_dists(
                     q_, X[jnp.minimum(c_, n - 1)]))(qb, cur)], axis=1)
            cd = jnp.where(cand < SENTINEL, cd, INF)
            # dedup candidates per row
            def dd(c_, d_):
                i_, k_, _ = dedup_merge_topL(
                    c_, d_[:, None], jnp.zeros((c_.shape[0], 1), bool),
                    c_.shape[0])
                return i_, k_[:, 0]
            cand, cd = jax.vmap(dd)(cand, cd)
            peak_candidates = max(peak_candidates, int(cand.shape[1]))
            newn = _robust_prune_batch(X, jnp.asarray(ids), cand, cd,
                                       R=R, alpha=a)
            G = G.at[jnp.asarray(ids)].set(newn)
            # reverse edges: u in newn[x] -> try add x to N(u)
            G = _add_reverse_edges(X, G, jnp.asarray(ids), newn, R, a)
        log(f"pass {p_i} (alpha={a}) done at {time.time()-t0:.1f}s")

    stats = {"build_s": time.time() - t0, "R": R, "L": L, "alpha": alpha,
             "n": n, "d": d}
    return np.asarray(G), med, stats


@functools.partial(jax.jit, static_argnames=("R",), donate_argnums=(1,))
def _add_reverse_edges(X, G, xs_ids, newn, R, alpha):
    """For each edge x->u, append x to N(u) if capacity remains; overfull
    nodes are handled by slot-replacement of the farthest neighbor."""
    n = X.shape[0]
    flat_u = newn.reshape(-1)
    flat_x = jnp.repeat(xs_ids, newn.shape[1])
    ok = flat_u >= 0
    # current degree of u
    deg = (G[jnp.maximum(flat_u, 0)] >= 0).sum(-1)
    # distance of the proposed reverse edge
    dxu = jnp.sum(jnp.square(X[jnp.maximum(flat_u, 0)]
                             - X[flat_x]), axis=-1)
    slot_free = jnp.minimum(deg, R - 1)
    # farthest current neighbor of u (replacement victim when full)
    nb = G[jnp.maximum(flat_u, 0)]
    nbd = jnp.where(nb >= 0,
                    jnp.sum(jnp.square(
                        X[jnp.maximum(nb, 0)] - X[jnp.maximum(flat_u, 0)][:, None, :]),
                        axis=-1), -INF)
    far_slot = jnp.argmax(nbd, axis=-1)
    far_d = jnp.max(nbd, axis=-1)
    full = deg >= R
    slot = jnp.where(full, far_slot, slot_free)
    accept = ok & (~full | (dxu < far_d))
    tgt_row = jnp.where(accept, flat_u, n)  # row n = scratch discard
    Gp = jnp.concatenate([G, jnp.zeros((1, R), jnp.int32)], 0)
    Gp = Gp.at[tgt_row, slot].set(jnp.where(accept, flat_x, 0))
    return Gp[:-1]
