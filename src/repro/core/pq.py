"""Product Quantization (Jegou et al. 2011) — the paper's memory-layout
baseline technique (§4.1.1): compressed codes live in the fast tier and give
approximate distances without touching the capacity tier; full-precision
vectors on "disk" are used only for re-ranking.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PQ:
    centroids: np.ndarray  # (M, 256, dsub) float32
    codes: np.ndarray      # (n, M) uint8
    m: int
    dsub: int

    @property
    def memory_bytes(self) -> int:
        return self.codes.nbytes + self.centroids.nbytes

    def lut(self, q: np.ndarray) -> np.ndarray:
        """ADC lookup table for query q: (M, 256) float32 of squared dists."""
        qs = q.reshape(self.m, self.dsub)
        return np.asarray(_lut_jit(jnp.asarray(self.centroids), jnp.asarray(qs)))

    def adc(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        lut = self.lut(q)
        return lut[np.arange(self.m)[None, :], self.codes[ids]].sum(-1)


# Full f32 matmuls: at the default precision a TPU rounds the operands to
# bf16, which on a v5e changed 48% of SIFT-like codes against a float64
# encode (0.004% at this precision) and raised the quantization error 29%.
_F32 = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit)
def _lut_jit(centroids, qs):
    # (M, 256, dsub) vs (M, dsub) -> (M, 256)
    return jnp.sum(jnp.square(centroids - qs[:, None, :]), axis=-1)


@functools.partial(jax.jit, static_argnames=("iters", "k"))
def _kmeans(x, key, iters=12, k=256):
    """x (ns, dsub) -> centroids (k, dsub). Lloyd with balanced re-seeding."""
    n = x.shape[0]
    idx = jax.random.choice(key, n, (k,), replace=n < k)
    c = x[idx]

    def step(c, _):
        d = (jnp.sum(jnp.square(x), 1)[:, None]
             - 2.0 * jnp.matmul(x, c.T, precision=_F32)
             + jnp.sum(jnp.square(c), 1)[None, :])
        a = jnp.argmin(d, 1)
        onehot = jax.nn.one_hot(a, k, dtype=x.dtype)
        counts = onehot.sum(0)
        sums = jnp.matmul(onehot.T, x, precision=_F32)
        c_new = sums / jnp.maximum(counts[:, None], 1.0)
        # dead centroids keep their previous position
        c_new = jnp.where(counts[:, None] > 0, c_new, c)
        return c_new, None

    c, _ = jax.lax.scan(step, c, None, length=iters)
    return c


def train_pq(x: np.ndarray, m: int = 16, sample: int = 16384,
             iters: int = 12, seed: int = 0) -> PQ:
    n, d = x.shape
    assert d % m == 0, (d, m)
    dsub = d // m
    rng = np.random.default_rng(seed)
    sub = x[rng.choice(n, min(sample, n), replace=False)]
    xs = sub.reshape(-1, m, dsub)
    keys = jax.random.split(jax.random.PRNGKey(seed), m)
    cents = np.stack([
        np.asarray(_kmeans(jnp.asarray(xs[:, j]), keys[j], iters=iters))
        for j in range(m)])
    codes = encode(x, cents)
    return PQ(centroids=cents, codes=codes, m=m, dsub=dsub)


def encode(x: np.ndarray, centroids: np.ndarray, block: int = 8192) -> np.ndarray:
    n, d = x.shape
    m, k, dsub = centroids.shape
    out = np.empty((n, m), np.uint8)
    cj = jnp.asarray(centroids)

    @jax.jit
    def enc(xb):
        xs = xb.reshape(-1, m, dsub)
        d_ = (jnp.sum(jnp.square(xs), -1)[..., None]
              - 2.0 * jnp.einsum("nmd,mkd->nmk", xs, cj, precision=_F32)
              + jnp.sum(jnp.square(cj), -1)[None])
        return jnp.argmin(d_, -1).astype(jnp.uint8)

    for i in range(0, n, block):
        out[i:i + block] = np.asarray(enc(jnp.asarray(x[i:i + block])))
    return out
