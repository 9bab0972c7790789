"""Closed loop: `clients` callers that each wait for their reply.

Every call of the entry carries the next min(clients, max_batch) queries of
the pool, round-robin over the pool in rounds of `clients` queries, each
round in the seed's order (load.pool_order), so one call is one device
batch and every seed serves the same queries in another order. The window ends with the first call that returns after
`seconds`.
"""
from __future__ import annotations

import time

import numpy as np

from bench.load import Call, annotate, pool_order


def generate(entry, pool_size: int, max_batch: int, seconds: float,
             mix: dict, seed: int, trace: bool = False):
    clients = int(mix["clients"])
    b = min(clients, max_batch)
    order = pool_order(pool_size, pool_size, seed, clients)
    calls, nxt = [], 0
    t0 = time.perf_counter()
    while True:
        idx = order[(nxt + np.arange(b)) % pool_size]
        nxt = (nxt + b) % pool_size
        s = time.perf_counter() - t0
        with annotate(trace, "bench.entry"):
            out = entry(idx)
        e = time.perf_counter() - t0
        calls.append(Call(s, e, idx, np.full(b, s), out))
        if e >= seconds:
            return calls, {}
