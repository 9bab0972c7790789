"""The program's own wall-clock spans and the named scopes of
`_search_batch`, read from the same profiler trace as bench/trace_reduce.py,
and the per-layer readings they give.

What it reads, beside what bench/trace_reduce.py reads:

- host spans named `ann.*` (`repro.obs.span`, recorded whenever a profiler
  trace runs); nesting on the one thread makes parent and child;
- each device op's scope path, its HLO `op_name` metadata. A TPU's op
  events carry no such stat (only their offset, duration and time scale),
  so the path comes from the executed program's optimized HLO text
  (`capture_hlo`), where every instruction carries
  `metadata={op_name="jit(_search_batch)/.../merge/..."}` and the op's
  event is named after the instruction (on a TPU by its HLO text, on the
  CPU by its `hlo_op`).

bench/run.py does not call this module yet: only a change of the
benchmark's own kind may edit the files it has. Use:

    hlo = capture_hlo(lambda: entry(batch))          # after the warm-up
    ...                                              # the traced window
    red = reduce(read(xplane_file), hlo_paths(hlo))
    readings(red)
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce as tr  # noqa: E402

SEARCH = "_search_batch"
SCOPES = ("pq_lut", "pq_lookup", "select", "page_gather", "exact_dist",
          "merge", "rerank")
UNSCOPED = "(no scope)"
BATCH = "ann.serve.batch"
HLO_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?'
                         r'metadata=\{[^}]*?op_name="([^"]*)"')

Span = Tuple[str, float, float]               # (name, start, end)


def scope_of(path: Optional[str]) -> Optional[str]:
    """The innermost of SCOPES named on an op_name path, leaving out its
    last part (the primitive); a scope may sit inside a transform's
    parentheses: `jit(f)/vmap()/while/body/merge/sort` and
    `jit(f)/vmap(merge)/sort` -> `merge`."""
    if not path:
        return None
    found = [w for part in path.split("/")[:-1]
             for w in re.findall(r"[\w.]+", part) if w in SCOPES]
    return found[-1] if found else None


def hlo_paths(text: str) -> Dict[str, str]:
    """Instruction name -> op_name metadata, from optimized HLO text."""
    out = {}
    for line in text.splitlines():
        m = HLO_OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def instruction(name: str) -> str:
    """An op's instruction name: `fusion.127 f32[524288]` (trace_reduce's
    name) or `%fusion.127 = f32[524288]{...} fusion(...)` -> `fusion.127`."""
    return re.match(r"^%?([^\s=]*)", name.strip()).group(1)


def read(path: str) -> tr.Trace:
    """trace_reduce's Trace of an `.xplane.pb`, with the program's `ann.*`
    host spans among its spans."""
    import jax
    trace = tr.read(path)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            trace.spans += [(ev.name, *tr._ns(ev)) for ev in line.events
                            if ev.name.startswith("ann.")]
    return trace


def innermost(spans: List[Span]) -> List[Span]:
    """Disjoint (name, start, end) segments covering the spans, each named
    after the innermost span open over it (spans nest on one thread)."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []
    t = float("-inf")

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][1] <= limit:
            name, end = stack.pop()
            if end > t:
                out.append((name, t, end))
                t = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_until(s)
        if stack and s > t:
            out.append((stack[-1][0], t, s))
        stack.append((name, e))
        t = max(t, s)
    close_until(float("inf"))
    return out


@dataclasses.dataclass
class ProgramReduction:
    base: tr.Reduction                    # trace_reduce's, over all spans
    span_self_s: Dict[str, float]         # less the spans nested in it
    scope_s: Dict[str, float]             # scope -> device self seconds
    idle_gaps: List[Tuple[str, float]]    # (innermost span, seconds)

    @property
    def search(self) -> Dict[str, float]:
        """{count, device_s} of SEARCH in the window."""
        return self.base.programs.get(SEARCH, {"count": 0, "device_s": 0.0})

    @property
    def scoped_share(self) -> Optional[float]:
        """Share of SEARCH's op self time that lies under a named scope."""
        total = sum(self.scope_s.values())
        if total <= 0:
            return None
        return sum(self.scope_s.get(s, 0.0) for s in SCOPES) / total

    @property
    def ann_idle_share(self) -> Optional[float]:
        """Share of the idle time in gaps labelled by an `ann.*` span."""
        idle = sum(t for _, t in self.idle_gaps)
        if idle <= 0:
            return None
        return sum(t for n, t in self.idle_gaps
                   if n.startswith("ann.")) / idle


def reduce(trace: tr.Trace, paths: Dict[str, str]
           ) -> Optional[ProgramReduction]:
    """`trace` reduced, each op's scope read from `paths` (`hlo_paths` of
    the executed `_search_batch`); None where trace_reduce gives None."""
    base = tr.reduce(trace)
    if base is None:
        return None
    lo, hi = base.window
    inner = [sp for sp in tr.clip(trace.spans, lo, hi) if sp[0] != tr.WINDOW]
    segs = innermost(inner)
    scope_s: Dict[str, float] = defaultdict(float)
    idle: List[Tuple[str, float]] = []
    for chip in trace.chips:
        ops = tr.clip(chip.ops, lo, hi)
        runs = sorted((s, e) for n, s, e in chip.programs
                      if n == SEARCH and s >= lo and e <= hi)
        run_starts = [s for s, _ in runs]
        # each op's exclusive time, by trace_reduce's rule, keyed by index
        own = tr.self_times([(i, s, e) for i, (_, s, e) in enumerate(ops)])
        for j, (name, s, _) in enumerate(ops):
            i = bisect.bisect_right(run_starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                scope = scope_of(paths.get(instruction(name)))
                scope_s[scope or UNSCOPED] += own[j]
        busy = tr.union([(s, e) for _, s, e in ops])
        idle += [(tr.label(g, segs), g[1] - g[0])
                 for g in tr.gaps(busy, lo, hi)]
    return ProgramReduction(
        base=base, span_self_s=tr.self_times(inner), scope_s=dict(scope_s),
        idle_gaps=sorted(idle, key=lambda g: -g[1]))


def readings(red: ProgramReduction) -> Dict[str, float]:
    """The per-layer readings, in ms per batch; a reading whose input the
    trace does not hold (no `ann.*` spans, no scopes) is left out."""
    out: Dict[str, float] = {}
    runs = red.search["count"]
    if runs and any(red.scope_s.get(s) for s in SCOPES):
        for scope, name in (("pq_lookup", "search.pq_lookup_ms_per_batch"),
                            ("merge", "search.merge_ms_per_batch")):
            out[name] = 1e3 * red.scope_s.get(scope, 0.0) / runs
    b = red.base
    batches = b.span_count.get(BATCH, 0)
    if not batches:
        return out
    if "ann.serve.price" in red.span_self_s:
        out["serving.price_ms_per_batch"] = \
            1e3 * red.span_self_s["ann.serve.price"] / batches
    for span, name in (("ann.search.pull", "search.pull_ms_per_batch"),
                       ("ann.search.memgraph", "memgraph.host_ms_per_batch")):
        if span in b.span_s:
            host = b.span_s[span] - b.span_busy_s.get(span, 0.0)
            out[name] = 1e3 * host / batches
    return out


def device_scopes(red: ProgramReduction, top: int = 10) -> list:
    """[scope, seconds] of `_search_batch`, the most device time first."""
    return [[n, t] for n, t in
            sorted(red.scope_s.items(), key=lambda kv: -kv[1])[:top]]


def capture_hlo(call) -> str:
    """Optimized HLO text of the `_search_batch` that `call()` runs: its
    arguments are recorded on the way through, then lowered and compiled
    again (a hit in the compile caches). Raises if `call()` runs none."""
    from repro.core import search_kernel
    orig = search_kernel._search_batch
    seen: dict = {}

    def record(*args, **kw):
        seen.setdefault("call", (args, kw))
        return orig(*args, **kw)

    search_kernel._search_batch = record
    try:
        call()
    finally:
        search_kernel._search_batch = orig
    if "call" not in seen:
        raise RuntimeError("capture_hlo: the call ran no "
                           "search_kernel._search_batch")
    args, kw = seen["call"]
    return orig.lower(*args, **kw).compile().as_text()
