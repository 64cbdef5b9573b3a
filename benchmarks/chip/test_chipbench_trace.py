"""The trace reduction against a small trace the harness recorded on a
TPU v5e (``record_trace.py``), committed under ``testdata/``."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parent
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

from chipbench import trace  # noqa: E402

TRACE = CHIP / "testdata" / "chip_trace.xplane.pb.gz"
EXPECTED = CHIP / "testdata" / "chip_trace.expected.json"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_trace(str(TRACE))


def test_reduction_matches_the_recorded_numbers(summary):
    exp = json.loads(EXPECTED.read_text())
    assert summary.chips == exp["chips"] == 1
    assert summary.window_s == pytest.approx(exp["window_s"], rel=1e-12)
    assert summary.busy_s == pytest.approx(exp["busy_s"], rel=1e-12)
    assert summary.module_calls == exp["module_calls"]
    assert summary.module_s == pytest.approx(exp["module_s"], rel=1e-12)
    assert [g[0] for g in summary.gaps] == [g[0] for g in exp["gaps"]]


def _window_and_ops():
    pd = trace.load(str(TRACE))
    host = [ev for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name == trace.WINDOW]
    lo = host[0].start_ns
    hi = lo + host[0].duration_ns
    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
           for p in pd.planes if p.name.startswith(trace.DEVICE_PREFIX)
           for line in p.lines if line.name == "XLA Ops"
           for ev in line.events]
    return lo, hi, ops


def test_busy_is_the_union_of_device_ops(summary):
    """Recount busy time by a sweep over op starts and ends, independently
    of the interval merge."""
    lo, hi, ops = _window_and_ops()
    edges = []
    for s, e in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort(key=lambda x: (x[0], -x[1]))
    busy, depth, since = 0.0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert summary.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)


def test_the_parts_add_up(summary):
    assert 0 < summary.busy_s < summary.window_s
    assert sum(g for _, g in summary.gaps) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-9)
    assert summary.module_s["_ffn_step"] > 0
    assert summary.module_s["_attn_decode_step"] > 0
    assert sum(summary.module_s.values()) <= summary.window_s
    labels = {g[0] for g in summary.gaps}
    assert labels <= {f"{where}: {what}"
                      for where in ("step", "clients", "other")
                      for what in ("host", "host-to-device copy",
                                   "device-to-host read", "dispatch")}
    assert any(label.startswith("step: ") for label in labels)
    assert summary.top_gaps(3) == sorted(summary.top_gaps(3),
                                         key=lambda g: -g[1])


def test_module_names_drop_the_jit_prefix_and_fingerprint():
    assert trace.module_name("jit__ffn_step(1234)") == "_ffn_step"
    assert trace.module_name("jit_convert_element_type(9)") == \
        "convert_element_type"
