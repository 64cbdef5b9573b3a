"""Reduction of the program's own spans in a profiler trace.

The program marks its layer boundaries and blocking waits with
``jax.profiler.TraceAnnotation`` (the names in ``PROGRAM_SPANS``). They
land in the same ``.xplane.pb`` as the device planes, on the same clock,
one host line per thread; their keyword arguments arrive as event stats.
The main thread is the host line that carries the harness's
``chipbench.step``.

For each instant of the ``chipbench.window`` the reduction knows whether
the chip idles (no ``XLA Ops`` interval of the first chip that ran
anything covers it, the holes ``trace.reduce_trace`` labels) and which
program spans the main thread is inside. Idle time splits exactly in
three: inside a blocking fetch (``prefetch.acquire`` or
``executor.fetch_at_use``), elsewhere inside ``serving.step``, and
outside every ``serving.step``. ``link.copy`` spans, one host-to-HBM
transfer each, count on any thread. Each idle gap is labelled with the
innermost program span on the main thread at its midpoint (a transfer's
``link.copy`` gives way to the fetch around it), falling back to the
harness's label where none covers it.

A trace from a program that emits no spans reduces to zero counts, and
the metric readers then read nothing.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import trace as tr

# every span the program emits
PROGRAM_SPANS = frozenset({
    "serving.step", "serving.admit", "serving.decode", "serving.sample",
    "executor.pass", "executor.pass_end", "executor.fetch_at_use",
    "prefetch.acquire", "prefetch.stage", "link.copy", "planner.rebind",
})
STEP = "serving.step"
ADMIT = "serving.admit"
LINK = "link.copy"
FETCH = frozenset({"prefetch.acquire", "executor.fetch_at_use"})
TRACES = Path(__file__).resolve().parents[3] / ".chipbench" / "traces"


@dataclass
class Span:
    line: int                 # index of its host line: one per thread
    name: str
    start: int                # ns, the profiler's clock
    end: int
    args: dict


@dataclass
class SpanSummary:
    window_s: float
    chips: int                # 1 when a chip ran anything in the window
    idle_s: float
    fetch_idle_s: float       # idle, main thread in a blocking fetch
    host_idle_s: float        # idle, main thread elsewhere in serving.step
    outside_idle_s: float     # idle, main thread in no serving.step
    link_busy_s: float        # union of link.copy spans, any thread
    admit_s: float            # serving.admit time of admissions in the window
    counts: Dict[str, int] = field(default_factory=dict)  # started inside
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def top_gaps(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.gaps,
                                          key=lambda g: -g[1])[:n]]


def program_spans(pd) -> Tuple[List[Span], Optional[int]]:
    """Every program span on the host lines of a loaded trace, and the
    index of the main thread's line (``None`` if no line carries
    ``chipbench.step``)."""
    out, main, index = [], None, 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name in PROGRAM_SPANS:
                    out.append(Span(index, name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    {k: v for k, v in ev.stats}))
                elif name == tr.STEP:
                    main = index
            index += 1
    return out, main


def stack_segments(spans: List[Span]):
    """The main thread's nested spans as consecutive segments: parallel
    lists of starts, ends and the names open over each, outermost
    first."""
    events = []
    for i, s in enumerate(spans):
        events.append((s.start, 1, -(s.end - s.start), i))
        events.append((s.end, 0, s.end - s.start, i))
    events.sort()
    starts, ends, stacks = [], [], []
    open_, prev = [], None
    for t, kind, _, i in events:
        if open_ and t > prev:
            starts.append(prev)
            ends.append(t)
            stacks.append(tuple(spans[j].name for j in open_))
        if kind:
            open_.append(i)
        else:
            open_.remove(i)
        prev = t
    return starts, ends, stacks


def _category(stack) -> str:
    if FETCH.intersection(stack):
        return "fetch"
    return "host" if STEP in stack else "outside"


def attribute(idle, starts, ends, stacks) -> Dict[str, int]:
    """ns of the ``idle`` intervals (sorted, disjoint) in each category
    by the main thread's open spans; idle where no span is open counts
    as outside."""
    out = {"fetch": 0, "host": 0, "outside": 0}
    j = 0
    for a, b in idle:
        t = a
        while j < len(starts) and ends[j] <= a:
            j += 1
        k = j
        while t < b:
            if k < len(starts) and starts[k] < b:
                s, e = max(starts[k], t), min(ends[k], b)
                if s > t:
                    out["outside"] += s - t
                out[_category(stacks[k])] += e - s
                t = e
                k += 1
            else:
                out["outside"] += b - t
                t = b
    return out


def innermost(stack) -> Optional[str]:
    """The innermost span of a stack, a transfer's ``link.copy`` giving
    way to the fetch or stage around it."""
    names = [n for n in stack if n != LINK] or list(stack)
    return names[-1] if names else None


def _union_len(intervals, lo, hi) -> int:
    clipped = [(max(s, lo), min(e, hi)) for s, e in intervals]
    return sum(e - s for s, e in tr._union([iv for iv in clipped
                                            if iv[1] > iv[0]]))


def reduce_spans(path: str) -> SpanSummary:
    pd = tr.load(path)
    spans, main = program_spans(pd)
    harness = {tr.WINDOW: [], tr.STEP: [], tr.CLIENTS: []}
    activity = {what: [] for what, _ in tr.HOST_ACTIVITY}
    ops = None
    for plane in pd.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            if ops is None:
                found = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                         for line in plane.lines if line.name == "XLA Ops"
                         for ev in line.events]
                ops = found or None
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name in harness:
                    harness[ev.name].append(iv)
                    continue
                for what, prefixes in tr.HOST_ACTIVITY:
                    if ev.name.startswith(prefixes):
                        activity[what].append(iv)
                        break
    if not harness[tr.WINDOW]:
        raise ValueError(f"{path}: no {tr.WINDOW!r} annotation")
    lo, hi = harness[tr.WINDOW][0]
    in_window = [s for s in spans if lo <= s.start < hi]
    counts = {}
    for s in in_window:
        counts[s.name] = counts.get(s.name, 0) + 1
    link_ns = _union_len([(s.start, s.end) for s in spans if s.name == LINK],
                         lo, hi)
    main_spans = [s for s in spans if s.line == main]
    admit_ns = sum(s.end - s.start for s in main_spans
                   if s.name == ADMIT and lo <= s.start < hi)
    starts, ends, stacks = stack_segments(main_spans)
    idle, gaps, chips = [], [], 0
    if ops is not None:
        busy = tr._union([(max(s, lo), min(e, hi)) for s, e in ops
                          if min(e, hi) > max(s, lo)])
        chips = 1 if busy else 0
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        labeller = tr._Labeller(harness[tr.STEP], harness[tr.CLIENTS],
                                activity)
        for s, e in idle:
            mid = (s + e) / 2
            label = labeller(mid)
            k = bisect.bisect_right(starts, mid) - 1
            if k >= 0 and mid < ends[k]:
                span = innermost(stacks[k])
                label = f"{label.split(':', 1)[0]}: {span}"
            gaps.append((label, (e - s) * 1e-9))
    part = attribute(idle, starts, ends, stacks)
    return SpanSummary(
        window_s=(hi - lo) * 1e-9, chips=chips,
        idle_s=sum(e - s for s, e in idle) * 1e-9,
        fetch_idle_s=part["fetch"] * 1e-9, host_idle_s=part["host"] * 1e-9,
        outside_idle_s=part["outside"] * 1e-9, link_busy_s=link_ns * 1e-9,
        admit_s=admit_ns * 1e-9, counts=counts, gaps=gaps)


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> SpanSummary:
    return reduce_spans(path)


def for_window(w) -> Optional[SpanSummary]:
    """The span reduction of the trace a traced run wrote for ``w``: the
    newest trace under the benchmark's trace directory, taken only when
    its window is the one ``trace.reduce_trace`` read for ``w``. One
    reduction serves every reader of the run."""
    if w.trace is None:
        return None
    files = glob.glob(str(TRACES / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    summary = _reduce_file(path, os.stat(path).st_mtime_ns)
    if abs(summary.window_s - w.trace.window_s) > 1e-9:
        return None
    return summary
