"""The plain reference: exact nearest neighbours by brute force.

Independent of the program: jax.numpy and numpy only, nothing imported from
`repro`, nothing taken from the index. Candidates come from a blocked
float32 distance matrix on the default device at `highest` precision; the
final order and every distance returned are recomputed in float64 on the
host, so rounding in the matrix never decides a neighbour.

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
