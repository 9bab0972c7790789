"""The plain reference: exact nearest neighbours by brute force.

Independent of the program: jax.numpy and numpy only, nothing imported from
`repro`, nothing taken from the index. Candidates come from a blocked
float32 distance matrix on the default device at `highest` precision; the
final order and every distance returned are recomputed in float64 on the
host, so rounding in the matrix never decides a neighbour.

`exact_topk_live` is the same search over a changing set of vectors: each
query is answered over the vids live at its own version (bench/live.py),
the others masked out.

`control_topk` is the same search computed in bfloat16, the precision one
step below the float32 the configurations state: it is the control that the
correctness check must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024        # queries per device block


def sq_l2(base: np.ndarray, queries: np.ndarray, ids: np.ndarray):
    """Float64 squared L2 from each query to each of its ids, (q, k); NaN
    where an id is out of range."""
    ids = np.asarray(ids)
    ok = (ids >= 0) & (ids < len(base))
    x = base[np.where(ok, ids, 0)].astype(np.float64)
    d = np.square(x - queries[:, None, :].astype(np.float64)).sum(-1)
    return np.where(ok, d, np.nan)


@functools.partial(jax.jit, static_argnames=("c",))
def _candidates(x, xn, qb, *, c):
    dot = jnp.dot(qb, x.T, precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(-(xn[None, :] - 2.0 * dot), c)[1]


@functools.partial(jax.jit, static_argnames=("c",))
def _live_candidates(x, xn, born, died, qb, vb, *, c):
    dot = jnp.dot(qb, x.T, precision=jax.lax.Precision.HIGHEST)
    live = (born[None, :] <= vb[:, None]) & (vb[:, None] < died[None, :])
    return jax.lax.top_k(jnp.where(live, -(xn[None, :] - 2.0 * dot),
                                   -jnp.inf), c)[1]


@functools.partial(jax.jit, static_argnames=("k",))
def _bf16_topk(x, qb, *, k):
    xb, qh = x.astype(jnp.bfloat16), qb.astype(jnp.bfloat16)
    xn = jnp.sum(xb * xb, axis=1)
    qn = jnp.sum(qh * qh, axis=1)
    d = qn[:, None] + xn[None, :] - 2 * jnp.dot(
        qh, xb.T, preferred_element_type=jnp.bfloat16)
    neg, ids = jax.lax.top_k(-d, k)
    return ids, -neg


def exact_topk(base: np.ndarray, queries: np.ndarray, k: int):
    """Exact top-k ids (q, k) int64 and their float64 distances, ties broken
    by the lower id."""
    c = min(len(base), 4 * k)
    x = jnp.asarray(base, jnp.float32)
    xn = jnp.sum(x * x, axis=1)
    ids_out, d_out = [], []
    for s in range(0, len(queries), BLOCK):
        qb = queries[s:s + BLOCK]
        cand = np.asarray(_candidates(x, xn, jnp.asarray(qb, jnp.float32),
                                      c=c)).astype(np.int64)
        d = sq_l2(base, qb, cand)
        order = np.lexsort((cand, d), axis=1)[:, :k]
        ids_out.append(np.take_along_axis(cand, order, 1))
        d_out.append(np.take_along_axis(d, order, 1))
    return np.concatenate(ids_out), np.concatenate(d_out)


def live_mask(born, died, ids, versions):
    """Per id: whether it is in range and live at its row's version."""
    ids = np.asarray(ids)
    ok = (ids >= 0) & (ids < len(born))
    j = np.where(ok, ids, 0)
    v = np.asarray(versions, np.int64)[:, None]
    return ok & (born[j] <= v) & (v < died[j])


def exact_topk_live(vectors: np.ndarray, born: np.ndarray, died: np.ndarray,
                    queries: np.ndarray, versions: np.ndarray, k: int):
    """Exact top-k over the vids live at each query's version: `vectors`
    holds every vid's vector, and vid v is live at version t when
    born[v] <= t < died[v]. exact_topk's method, with the other ids at
    +inf: float32 candidates at `highest`, float64 order and distances on
    the host, ties broken by the lower id."""
    c = min(len(vectors), 4 * k)
    x = jnp.asarray(vectors, jnp.float32)
    xn = jnp.sum(x * x, axis=1)
    top = np.iinfo(np.int32).max
    b_dev = jnp.asarray(np.minimum(born, top), jnp.int32)
    d_dev = jnp.asarray(np.minimum(died, top), jnp.int32)
    versions = np.asarray(versions, np.int64)
    ids_out, d_out = [], []
    for s in range(0, len(queries), BLOCK):
        qb, vb = queries[s:s + BLOCK], versions[s:s + BLOCK]
        cand = np.asarray(_live_candidates(
            x, xn, b_dev, d_dev, jnp.asarray(qb, jnp.float32),
            jnp.asarray(vb, jnp.int32), c=c)).astype(np.int64)
        d = np.where(live_mask(born, died, cand, vb),
                     sq_l2(vectors, qb, cand), np.inf)
        order = np.lexsort((cand, d), axis=1)[:, :k]
        ids_out.append(np.take_along_axis(cand, order, 1))
        d_out.append(np.take_along_axis(d, order, 1))
    return np.concatenate(ids_out), np.concatenate(d_out)


def control_topk(base: np.ndarray, queries: np.ndarray, k: int):
    """The reference in bfloat16: top-k ids and the distances it computed."""
    x = jnp.asarray(base, jnp.float32)
    ids_out, d_out = [], []
    for s in range(0, len(queries), BLOCK):
        ids, d = _bf16_topk(x, jnp.asarray(queries[s:s + BLOCK], jnp.float32),
                            k=k)
        ids_out.append(np.asarray(ids).astype(np.int64))
        d_out.append(np.asarray(d.astype(jnp.float32), np.float64))
    return np.concatenate(ids_out), np.concatenate(d_out)
