"""Closed loop over a changing index: `clients` callers as in closed.py, and
after every `searches_per_step` search calls one mutation step.

A step inserts `inserts_per_step` rows and then deletes `deletes_per_step`
victims, chosen `victims` ("uniform" over the live vids, or "oldest"
first), through the entry's live set (bench/live.py), which reaches the
program in one call. The step runs inside the window on the host clock,
because a user waits through it; in a traced run it is annotated
`bench.mutate`, and `bench.entry` covers the searches alone. So is the
compaction policy's hook after each served batch.

What the steps apply comes from the configuration's data_seed: every --seed
applies the same mutations after the same search calls, and orders the
search rounds as closed.py does. Each search Call records the version it
saw. The window ends with the first search call that returns after
`seconds`.
"""
from __future__ import annotations

import time

import numpy as np

from bench.live import VICTIMS
from bench.load import Call, Step, annotate, pool_order


def generate(entry, pool_size: int, max_batch: int, seconds: float,
             mix: dict, seed: int, trace: bool = False):
    stream = getattr(entry, "stream", None)
    if stream is None:
        raise ValueError("the stream generator needs a configuration with "
                         "a `stream` block")
    clients = int(mix["clients"])
    per_step = int(mix["searches_per_step"])
    inserts, deletes = int(mix["inserts_per_step"]), int(
        mix["deletes_per_step"])
    victims = mix["victims"]
    if per_step < 1 or victims not in VICTIMS:
        raise ValueError(f"searches_per_step={per_step} must be >= 1 and "
                         f"victims={victims!r} one of {VICTIMS}")
    b = min(clients, max_batch)
    order = pool_order(pool_size, pool_size, seed, clients)
    calls, steps, nxt = [], [], 0
    t0 = time.perf_counter()
    while True:
        idx = order[(nxt + np.arange(b)) % pool_size]
        nxt = (nxt + b) % pool_size
        s = time.perf_counter() - t0
        with annotate(trace, "bench.entry"):
            out = entry(idx)
        e = time.perf_counter() - t0
        calls.append(Call(s, e, idx, np.full(b, s), out, stream.version))
        if e >= seconds:
            return calls, {"steps": steps}
        with annotate(trace, "bench.mutate"):
            stream.after_batch()
        if len(calls) % per_step == 0:
            s = time.perf_counter() - t0
            with annotate(trace, "bench.mutate"):
                ack = stream.step(inserts, deletes, victims)
            steps.append(Step(s, time.perf_counter() - t0, **ack))
