"""The profiler trace reduced to device time by XLA program and op,
busy and idle time, and idle gaps labelled by what the host was doing.

Sources in an `.xplane.pb` (read with `jax.profiler.ProfileData`):

- a TPU's plane `/device:TPU:<i>`: its `XLA Modules` line holds one event per
  execution of a compiled program, named after the jitted function
  (`jit__search_batch(12)`), and its `XLA Ops` line one event per operation,
  named by its HLO text (`%fusion.127 = f32[524288]{...} fusion(...)`);
- the host's plane: the benchmark's own `TraceAnnotation` spans (`bench.*`).

A CPU rehearsal has no device plane; there the ops are the host events that
carry an `hlo_module` stat, and a program execution is the span of one
(`hlo_module`, `run_id`). Such numbers are never chip results.

Busy time is the union of the op intervals inside the window (the span of
the `bench.window` annotation), averaged over the chips that ran ops.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start, end), seconds
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.window"
NO_SPAN = "host (no bench span)"


def program_name(name: str) -> str:
    """`jit__search_batch(12)` -> `_search_batch`."""
    name = re.sub(r"\(\d+\)$", "", name.strip())
    name = re.sub(r"\.\d+$", "", name)
    return re.sub(r"^jit_", "", name)


def op_name(name: str) -> str:
    """`%fusion.127 = f32[524288]{0:T(1024)} fusion(...)` -> `fusion.127
    f32[524288]`: the op and the shape it produces (the op alone where it
    produces a tuple)."""
    m = re.match(r"^%?([^\s=]+) = (\S+?)(\{|\s|$)", name)
    if not m:
        return name
    return m.group(1) if m.group(2).startswith("(") else \
        f"{m.group(1)} {m.group(2)}"


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(spans: list, lo: float, hi: float) -> list:
    """(name, start, end) spans cut to [lo, hi]; those outside dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in spans
            if min(e, hi) > max(s, lo)]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi] given the sorted busy union."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Exclusive time by name of (name, start, end) events on one line,
    where an event that lies inside another is its child."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []                  # [name, end] of open events
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        stack.append([name, e])
        out[name] += e - s
        if len(stack) > 1:
            out[stack[-2][0]] -= min(e, stack[-2][1]) - s
    return dict(out)


def label(gap: Interval, spans: List[Tuple[str, float, float]]) -> str:
    """The bench span that covers most of a gap."""
    best, cover = NO_SPAN, 0.0
    for name, s, e in spans:
        c = min(e, gap[1]) - max(s, gap[0])
        if c > cover:
            best, cover = name, c
    return best


@dataclasses.dataclass
class Chip:
    ops: List[Tuple[str, float, float]]        # (op, start, end)
    programs: List[Tuple[str, float, float]]   # (program, start, end)


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    spans: List[Tuple[str, float, float]]      # bench.* host spans


def _ns(ev) -> Interval:
    s = float(ev.start_ns) * 1e-9
    return s, s + float(ev.duration_ns) * 1e-9


def read(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    chips, spans, host_ops = [], [], []
    runs: Dict[tuple, list] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, progs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(op_name(ev.name), *_ns(ev))
                            for ev in line.events]
                elif line.name == "XLA Modules":
                    progs += [(program_name(ev.name), *_ns(ev))
                              for ev in line.events]
            if ops:
                chips.append(Chip(ops, progs))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.name, *_ns(ev)))
                    continue
                stats = dict(ev.stats)
                if "hlo_module" in stats:
                    s, e = _ns(ev)
                    host_ops.append((ev.name, s, e))
                    key = (stats["hlo_module"], stats.get("run_id"))
                    r = runs.setdefault(key, [s, e])
                    r[0], r[1] = min(r[0], s), max(r[1], e)
    if not chips and host_ops:              # CPU rehearsal
        chips.append(Chip(host_ops, [(program_name(k[0]), s, e)
                                     for k, (s, e) in runs.items()]))
    return Trace(chips, spans)


@dataclasses.dataclass
class Reduction:
    window: Interval
    busy_s: float                           # mean over chips
    programs: Dict[str, Dict[str, float]]   # name -> {count, device_s}
    op_self_s: Dict[str, float]             # op -> exclusive seconds
    idle_gaps: List[Tuple[str, float]]      # (bench span, seconds)
    span_s: Dict[str, float]                # bench span -> seconds
    span_busy_s: Dict[str, float]           # device busy inside the spans
    span_count: Dict[str, int]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def reduce(trace: Trace) -> Optional[Reduction]:
    """None when the trace holds no window span or no device op."""
    wins = [(s, e) for n, s, e in trace.spans if n == WINDOW]
    if not wins or not trace.chips:
        return None
    lo, hi = wins[0]
    spans = [sp for sp in trace.spans if sp[0] != WINDOW]
    busy_total = 0.0
    programs: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "device_s": 0.0})
    op_self: Dict[str, float] = defaultdict(float)
    idle: List[Tuple[str, float]] = []
    span_busy: Dict[str, float] = defaultdict(float)
    for chip in trace.chips:
        ops = clip(chip.ops, lo, hi)
        busy = union([(s, e) for _, s, e in ops])
        busy_total += sum(e - s for s, e in busy)
        for name, s, e in chip.programs:
            if s >= lo and e <= hi:
                programs[name]["count"] += 1
                programs[name]["device_s"] += e - s
        for name, t in self_times(ops).items():
            op_self[name] += t
        idle += [(label(g, spans), g[1] - g[0]) for g in gaps(busy, lo, hi)]
        for name in {n for n, _, _ in spans}:
            mine = union([(s, e) for n, s, e in clip(spans, lo, hi)
                          if n == name])
            span_busy[name] += overlap(mine, busy) / len(trace.chips)
    span_s: Dict[str, float] = defaultdict(float)
    span_count: Dict[str, int] = defaultdict(int)
    for name, s, e in clip(spans, lo, hi):
        span_s[name] += e - s
        span_count[name] += 1
    return Reduction(
        window=(lo, hi), busy_s=busy_total / len(trace.chips),
        programs={k: dict(v) for k, v in programs.items()},
        op_self_s=dict(op_self),
        idle_gaps=sorted(idle, key=lambda g: -g[1]),
        span_s=dict(span_s), span_busy_s=dict(span_busy),
        span_count=dict(span_count))


def breakdown(red: Reduction, top: int = 10) -> dict:
    ops = sorted(red.op_self_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in red.idle_gaps[:top]]}
