"""Seeded vectors in the shape of a public ANN dataset.

A copy of the arithmetic of the program's synthetic generator
(`repro.core.dataset.make_dataset`), kept here so that the yardstick does not
move with the program: clustered points on a low-dimensional nonlinear
manifold lifted to the dataset's dimension, quantized to the integer range
when the dataset is stored as integers. The same seed gives the same arrays.
"""
from __future__ import annotations

import zlib

import numpy as np

INT_RANGES = {"uint8": (0, 255, 128), "int8": (-128, 127, 0)}


def make(spec: dict, n: int, nq: int, seed: int):
    """(base (n, d), queries (nq, d)), both float32.

    `spec` is a configuration's "dataset" entry: `name` (salts the seed),
    `d`, `dtype` ("float", "uint8" or "int8") and `clusters`.
    """
    d, tag, n_clusters = spec["d"], spec["dtype"], spec["clusters"]
    rng = np.random.default_rng(seed + zlib.crc32(spec["name"].encode())
                                % 10000)
    k_lat = int(np.clip(d // 12, 8, 16))
    centers = rng.normal(0, 1.0, (n_clusters, k_lat)).astype(np.float32)
    w1 = (rng.normal(0, 1.0, (k_lat, 4 * k_lat)).astype(np.float32)
          / np.sqrt(k_lat))
    w2 = (rng.normal(0, 1.0, (4 * k_lat, d)).astype(np.float32)
          / np.sqrt(4 * k_lat))

    def lift(z):
        return (np.tanh(z @ w1) @ w2 + 0.05 * rng.normal(
            0, 1.0, (len(z), d))).astype(np.float32)

    z = centers[rng.integers(0, n_clusters, n)] + 0.6 * rng.normal(
        0, 1.0, (n, k_lat)).astype(np.float32)
    x = lift(z)
    zq = centers[rng.integers(0, n_clusters, nq)] + 0.6 * rng.normal(
        0, 1.0, (nq, k_lat)).astype(np.float32)
    q = lift(zq)
    if tag in INT_RANGES:
        lo, hi, mid = INT_RANGES[tag]
        scale = 80.0 / max(np.abs(x).max(), 1e-6)
        x = np.clip(np.round(x * scale + mid), lo, hi).astype(np.float32)
        q = np.clip(np.round(q * scale + mid), lo, hi).astype(np.float32)
    elif tag != "float":
        raise ValueError(f"dataset dtype {tag!r} is not float, uint8 or int8")
    return x, q
