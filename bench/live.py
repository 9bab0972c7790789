"""The books of a mutating run: which vector each vid holds, and when it was
live.

A configuration with a `stream` block serves while rows are inserted and
deleted. The harness keeps its own books of what it asked the program to do
and what the program acknowledged: the vector behind every vid, and the
versions at which the vid was inserted (`born`) and deleted (`died`).
Version v is the state after the first v mutations, counted in the order
they were applied; a search made at version v must answer over the vids
with `born <= v < died`. The reference (reference.exact_topk_live) and the
checks (checks.judge_live) read the books after the window.

What is mutated, and in what order, comes from the configuration's
`data_seed`, never from `--seed`, so every seed applies the same mutations:

- inserts take the rows held back from the build, in their draw order, and
  after them the vectors of the vids deleted earliest, re-inserted under new
  vids, so a long window never runs dry;
- delete victims are drawn `uniform` over the live vids (FreshDiskANN's
  churn) or taken `oldest` first (the streaming track's sliding window),
  from the live set as it stood before the step.

The program is reached through two callables (bench/run.py: make_mutate):
`mutate(insert_vecs, delete_vids)`, which returns the vids it assigned,
whether each delete took, and the flushes and compaction runs it set off;
and `after_batch()`, the compaction policy's hook after a served batch,
which returns the compaction runs it made.

An acknowledgement is bad when an insert's vid lies outside the vid space
(`n` plus the inserts asked for so far) or was handed out before, or when a
delete of a live vid answers False. Bad acknowledgements fail the run.
"""
from __future__ import annotations

import collections
from typing import Callable, List

import numpy as np

NEVER = np.iinfo(np.int64).max     # `died` of a live vid; `born` of none
VICTIMS = ("uniform", "oldest")


class LiveSet:
    def __init__(self, base: np.ndarray, held: np.ndarray, data_seed: int,
                 mutate: Callable, after_batch: Callable):
        n, d = base.shape
        self.n = n
        self._mutate = mutate
        self._after_batch = after_batch
        self._rng = np.random.default_rng([data_seed, 3])
        cap = n + len(held)
        self._vecs = np.zeros((cap, d), np.float32)
        self._vecs[:n] = base
        self._born = np.full(cap, NEVER, np.int64)
        self._born[:n] = 0
        self._died = np.full(cap, NEVER, np.int64)
        self._queue = collections.deque(np.asarray(held, np.float32))
        self._live: List[int] = list(range(n))    # for uniform draws
        self._pos = {v: v for v in range(n)}      # vid -> slot in _live
        self._age = collections.deque(range(n))   # insertion order
        self.version = 0          # mutations applied
        self.issued = 0           # inserts asked for
        self.attempted = 0        # inserts and deletes asked for
        self.inserts = self.deletes = 0
        self.flushes = self.compactions = 0
        self.bad_acks: List[str] = []

    # -- the live list ------------------------------------------------------

    def _add(self, vid: int) -> None:
        self._pos[vid] = len(self._live)
        self._live.append(vid)

    def _drop(self, vid: int) -> None:
        pos = self._pos.pop(vid)
        last = self._live.pop()
        if last != vid:
            self._live[pos] = last
            self._pos[last] = pos

    def _choose(self, count: int, victims: str) -> List[int]:
        chosen = []
        for _ in range(min(count, len(self._live))):
            if victims == "uniform":
                vid = self._live[int(self._rng.integers(len(self._live)))]
            else:
                vid = self._age.popleft()
                while vid not in self._pos:
                    vid = self._age.popleft()
            self._drop(vid)
            chosen.append(vid)
        return chosen

    def _grow(self, size: int) -> None:
        cap = len(self._born)
        if size <= cap:
            return
        cap = max(size, 2 * cap)
        self._vecs = np.concatenate(
            [self._vecs, np.zeros((cap - len(self._vecs),
                                   self._vecs.shape[1]), np.float32)])
        self._born = np.concatenate(
            [self._born, np.full(cap - len(self._born), NEVER, np.int64)])
        self._died = np.concatenate(
            [self._died, np.full(cap - len(self._died), NEVER, np.int64)])

    # -- steps --------------------------------------------------------------

    def step(self, inserts: int, deletes: int, victims: str) -> dict:
        """One mutation step: up to `inserts` rows from the insert queue,
        then `deletes` victims chosen by `victims`, through the program in
        one call. Returns what was acknowledged: inserts, deletes that took,
        flushes and compaction runs."""
        take = min(inserts, len(self._queue))
        vecs = np.asarray([self._queue.popleft() for _ in range(take)],
                          np.float32).reshape(take, self._vecs.shape[1])
        dead = self._choose(deletes, victims)
        ack = self._mutate(vecs, np.asarray(dead, np.int64))
        self.issued += take
        self.attempted += take + len(dead)
        self._grow(self.n + self.issued)
        vids = [int(v) for v in ack["vids"]]
        took = [bool(t) for t in ack["took"]]
        if len(vids) != take or len(took) != len(dead):
            self.bad_acks.append(
                f"{len(vids)} vids for {take} inserts, {len(took)} answers "
                f"for {len(dead)} deletes")
        ins = dels = 0
        for vec, vid in zip(vecs, vids):
            self.version += 1
            if not self.n <= vid < self.n + self.issued:
                self.bad_acks.append(f"insert acknowledged as vid {vid}, "
                                     f"outside [{self.n}, "
                                     f"{self.n + self.issued})")
            elif self._born[vid] != NEVER:
                self.bad_acks.append(f"insert acknowledged as vid {vid}, "
                                     f"handed out before")
            else:
                self._vecs[vid] = vec
                self._born[vid] = self.version
                self._add(vid)
                self._age.append(vid)
                ins += 1
        for vid, ok in zip(dead, took):
            self.version += 1
            if ok:
                self._died[vid] = self.version
                self._queue.append(self._vecs[vid].copy())
                dels += 1
            else:
                self.bad_acks.append(f"delete of live vid {vid} answered "
                                     f"False")
                self._add(vid)
                self._age.appendleft(vid)
        self.inserts += ins
        self.deletes += dels
        self.flushes += int(ack["flushes"])
        self.compactions += int(ack["compactions"])
        return {"inserts": ins, "deletes": dels,
                "flushes": int(ack["flushes"]),
                "compactions": int(ack["compactions"])}

    def after_batch(self) -> None:
        self.compactions += int(self._after_batch())

    def detach(self) -> None:
        """Let go of the program, so that its state can be freed before the
        reference runs; the books stay."""
        self._mutate = self._after_batch = None

    # -- what the checks read -----------------------------------------------

    def books(self):
        """(vectors, born, died) over the vid space [0, n + inserts asked
        for): a vid never handed out has `born` NEVER."""
        m = self.n + self.issued
        return self._vecs[:m], self._born[:m], self._died[:m]

    def inserted_live(self) -> np.ndarray:
        """Vids inserted in the window and live at its end."""
        vids = np.asarray(sorted(self._live), np.int64)
        return vids[self._born[vids] > 0]

    def deleted(self) -> np.ndarray:
        """Vids deleted in the window."""
        return np.flatnonzero(self._died[:self.n + self.issued] != NEVER)

    def summary(self) -> dict:
        return {"inserts": self.inserts, "deletes": self.deletes,
                "flushes": self.flushes, "compactions": self.compactions,
                "live": len(self._live), "bad_acks": len(self.bad_acks)}
