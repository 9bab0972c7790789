"""Traffic: a mix file in bench/traffic/ names its `generator` and gives its
parameters; nothing else about a mix is code. A generator is a file of its
own, bench/generators/<generator>.py, whose

    generate(entry, pool_size, max_batch, seconds, mix, seed, trace=False)

drives the entry for `seconds` and returns (calls, info): the list of Call
records on the host clock, in seconds from the window's start, and a dict of
what it wants printed about the run.

The seed orders the queries within each round of the pool
(`pool_order`); it never changes which queries a window draws, bar the last
round, so every seed does the same work in another order.

Over a mutating configuration the entry also carries `entry.stream`, the
run's live set (bench/live.py): a generator that mutates applies its steps
through it, stamps each search Call with the live set's `version`, and
returns its `Step` records in `info["steps"]`.
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from pathlib import Path
from typing import Callable

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Call:
    start: float               # the call began
    end: float                 # the call returned
    pool_idx: np.ndarray       # (b,) query-pool rows served
    arrivals: np.ndarray       # (b,) scheduled arrival of each request
    out: dict                  # per-request arrays from the entry
    version: int = 0           # mutations acknowledged before the call


@dataclasses.dataclass
class Step:
    start: float               # the mutation step began
    end: float                 # the step's acknowledgements returned
    inserts: int               # inserts acknowledged
    deletes: int               # deletes that took
    flushes: int               # delta flushes the step set off
    compactions: int           # compaction runs the step set off


def annotate(trace: bool, name: str):
    """A profiler span named `name` in a traced run, else nothing."""
    if not trace:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def pool_order(pool_size: int, count: int, seed: int,
               block: int) -> np.ndarray:
    """`count` pool rows: the pool in rounds of `block` rows (rows 0 to
    block-1, then the next `block`, and so on), each round in the seed's
    order, repeated. Every seed asks for the same queries in each round, so
    a window that ends partway through the pool has served the same set
    whatever the seed, bar the last round."""
    rng = np.random.default_rng([seed, 2])
    order = np.arange(pool_size)
    for s in range(0, pool_size, block):
        order[s:s + block] = rng.permutation(order[s:s + block])
    return np.resize(order, count)


def generate(mix: dict, entry: Callable, pool_size: int, max_batch: int,
             seconds: float, seed: int, trace: bool = False,
             bench_dir: Path = BENCH):
    from bench import registry
    gen = registry.generator(mix["generator"], bench_dir)
    return gen(entry, pool_size, max_batch, seconds, mix, seed, trace)
