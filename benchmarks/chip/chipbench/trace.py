"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The harness wraps its measured window in a ``chipbench.window`` host
annotation, each ``batcher.step()`` in ``chipbench.step`` and its client
bookkeeping in ``chipbench.clients``. On a TPU the trace holds one plane
per chip (``/device:TPU:<n>``) whose ``XLA Modules`` line has one event per
executable run, named ``jit_<function>(<fingerprint>)``, and whose
``XLA Ops`` line has the operations inside them. Host planes carry the
annotations and the runtime's transfer events on the same clock.

Busy time is the union of the ``XLA Ops`` intervals inside the window,
averaged over the chips that ran anything; the idle gaps are the holes in
that union, each labelled with what the host was doing at its midpoint:
inside a step or the client loop, and copying to the device, reading
back from it, dispatching an executable, or none of these.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW = "chipbench.window"
STEP = "chipbench.step"
CLIENTS = "chipbench.clients"
DEVICE_PREFIX = "/device:TPU:"
# host runtime events, by what the host was doing while the chip idled
HOST_ACTIVITY = (
    ("host-to-device copy", ("tpu::System::TransferToDevice",
                             "TpuClient::LinearizeIntoImpl", "XlaLinearize",
                             "Linearize", "H2D Dispatch", "DevicePut",
                             "Transpose")),
    ("device-to-host read", ("np.asarray(jax.Array)",
                             "tpu::System::TransferFromDevice",
                             "D2H Dispatch")),
    ("dispatch", ("PjitFunction(",)),
)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over chips with device work
    chips: int
    module_s: Dict[str, float] = field(default_factory=dict)
    module_calls: Dict[str, int] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_modules(self, n: int = 10) -> List[list]:
        rows = sorted(self.module_s.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in rows[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.gaps,
                                          key=lambda g: -g[1])[:n]]


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def module_name(event_name: str) -> str:
    """``jit__ffn_step(1234)`` -> ``_ffn_step``."""
    base = event_name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def load(path: str):
    """A trace file, plain or gzipped."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def reduce_trace(path: str) -> TraceSummary:
    pd = load(path)
    spans = defaultdict(list)
    activity = defaultdict(list)
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if name in (WINDOW, STEP, CLIENTS):
                    spans[name].append(iv)
                    continue
                for what, prefixes in HOST_ACTIVITY:
                    if name.startswith(prefixes):
                        activity[what].append(iv)
                        break
    if not spans[WINDOW]:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    lo, hi = spans[WINDOW][0]
    window_s = (hi - lo) * 1e-9
    busy = []
    module_s = defaultdict(float)
    module_calls = defaultdict(int)
    merged_all = []
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e > s:
                        ops.append((s, e))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e > s:
                        name = module_name(ev.name)
                        module_s[name] += (e - s) * 1e-9
                        module_calls[name] += 1
        if ops:
            merged = _union(ops)
            busy.append(sum(e - s for s, e in merged) * 1e-9)
            merged_all.append(merged)
    chips = len(busy)
    busy_s = sum(busy) / chips if chips else 0.0
    gaps = []
    if merged_all:
        labeller = _Labeller(spans[STEP], spans[CLIENTS], activity)
        edges = [lo] + [t for iv in merged_all[0] for t in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((labeller((s + e) / 2), (e - s) * 1e-9))
    return TraceSummary(window_s=window_s, busy_s=busy_s, chips=chips,
                        module_s=dict(module_s),
                        module_calls=dict(module_calls), gaps=gaps)


class _Labeller:
    """What the host was doing at an instant: where (in a step, in the
    client loop, elsewhere) and what (the first of ``HOST_ACTIVITY`` in
    flight, else plain host work)."""

    def __init__(self, steps, clients, activity):
        self.where = [("step", _union(steps)), ("clients", _union(clients))]
        self.what = [(what, _union(activity.get(what, [])))
                     for what, _ in HOST_ACTIVITY]

    @staticmethod
    def _inside(spans, t) -> bool:
        i = bisect.bisect_right(spans, [t, float("inf")]) - 1
        return i >= 0 and spans[i][0] <= t <= spans[i][1]

    def __call__(self, t) -> str:
        where = next((w for w, s in self.where if self._inside(s, t)),
                     "other")
        what = next((w for w, s in self.what if self._inside(s, t)), "host")
        return f"{where}: {what}"
