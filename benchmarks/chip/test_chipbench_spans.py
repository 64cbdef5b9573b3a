"""The reduction of the program's spans (``chipbench/spans.py``): on
synthetic intervals, on the trace recorded before the program had spans
(``chip_trace``), and on one recorded with them on a TPU v5e
(``record_spans_trace.py``, ``chip_trace_spans``)."""
from __future__ import annotations

import gzip
import json
import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parent
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

from chipbench import harness, spans, trace  # noqa: E402

TESTDATA = CHIP / "testdata"
OLD = TESTDATA / "chip_trace.xplane.pb.gz"
NEW = TESTDATA / "chip_trace_spans.xplane.pb.gz"
NEW_EXPECTED = TESTDATA / "chip_trace_spans.expected.json"
READERS = ("prefetch.link_busy_share", "executor.fetch_idle_share",
           "serving.host_idle_share", "serving.prefill_ms_per_admit")


def _span(name, start, end, line=0):
    return spans.Span(line, name, start, end, {})


# ---------------------------------------------------------------- synthetic
def test_segments_follow_the_nesting():
    main = [_span("serving.step", 0, 100), _span("executor.pass", 10, 90),
            _span("prefetch.acquire", 20, 30), _span("link.copy", 50, 60),
            _span("executor.fetch_at_use", 40, 70)]
    starts, ends, stacks = spans.stack_segments(main)
    assert list(zip(starts, ends)) == [(0, 10), (10, 20), (20, 30), (30, 40),
                                       (40, 50), (50, 60), (60, 70), (70, 90),
                                       (90, 100)]
    assert stacks[2] == ("serving.step", "executor.pass", "prefetch.acquire")
    assert stacks[5] == ("serving.step", "executor.pass",
                         "executor.fetch_at_use", "link.copy")
    assert spans.innermost(stacks[5]) == "executor.fetch_at_use"
    assert spans.innermost(("link.copy",)) == "link.copy"


def test_a_parent_and_child_that_start_together_nest():
    starts, ends, stacks = spans.stack_segments(
        [_span("executor.pass", 0, 10), _span("serving.step", 0, 20)])
    assert stacks == [("serving.step", "executor.pass"), ("serving.step",)]
    assert list(zip(starts, ends)) == [(0, 10), (10, 20)]


@pytest.mark.parametrize("idle", [
    [(0, 200)],
    [(5, 25), (35, 45), (55, 95), (150, 160)],
    [(100, 130)],
    [],
])
def test_idle_splits_exactly_in_three(idle):
    main = [_span("serving.step", 0, 100), _span("executor.pass", 10, 90),
            _span("prefetch.acquire", 20, 30),
            _span("executor.fetch_at_use", 40, 70),
            _span("serving.step", 120, 140)]
    part = spans.attribute(idle, *spans.stack_segments(main))
    assert sum(part.values()) == sum(e - s for s, e in idle)
    # the same split, instant by instant
    want = {"fetch": 0, "host": 0, "outside": 0}
    for a, b in idle:
        for t in range(a, b):
            if 20 <= t < 30 or 40 <= t < 70:
                want["fetch"] += 1
            elif t < 100 or 120 <= t < 140:
                want["host"] += 1
            else:
                want["outside"] += 1
    assert part == want


# ---------------------------------------------------------------- traces
@pytest.fixture(scope="module")
def old():
    return spans.reduce_spans(str(OLD))


def test_a_trace_without_program_spans_keeps_the_harness_labels(old):
    """The trace recorded before the program had spans: no program span,
    and every gap labelled and timed as ``trace.reduce_trace`` does."""
    base = trace.reduce_trace(str(OLD))
    assert old.counts == {}
    assert old.gaps == base.gaps
    assert old.idle_s / old.window_s == pytest.approx(base.idle_share,
                                                      abs=1e-12)
    assert old.fetch_idle_s == old.host_idle_s == 0.0
    assert old.outside_idle_s == pytest.approx(old.idle_s, abs=1e-12)
    assert old.link_busy_s == old.admit_s == 0.0


def _window_on(tmp_path, monkeypatch, recorded):
    """A window whose trace the harness wrote: ``recorded`` unpacked into
    a trace directory as a traced run leaves it."""
    d = tmp_path / "traces" / "cell" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    with gzip.open(recorded, "rb") as f:
        (d / "host.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(spans, "TRACES", tmp_path / "traces")
    return types.SimpleNamespace(
        trace=trace.reduce_trace(str(d / "host.xplane.pb")))


def test_readers_read_nothing_from_a_program_without_spans(
        tmp_path, monkeypatch):
    w = _window_on(tmp_path, monkeypatch, OLD)
    assert spans.for_window(w) is not None
    for name in READERS:
        assert harness.reader(name)(w) is None


def test_readers_read_nothing_without_a_trace():
    w = types.SimpleNamespace(trace=None)
    for name in READERS:
        assert harness.reader(name)(w) is None


def test_a_trace_of_another_window_is_not_read(tmp_path, monkeypatch):
    w = _window_on(tmp_path, monkeypatch, OLD)
    w.trace.window_s += 1e-3
    assert spans.for_window(w) is None


@pytest.fixture(scope="module")
def new():
    return spans.reduce_spans(str(NEW))


def test_the_recorded_reduction_matches_its_numbers(new):
    exp = json.loads(NEW_EXPECTED.read_text())
    for key in ("window_s", "idle_s", "fetch_idle_s", "host_idle_s",
                "outside_idle_s", "link_busy_s", "admit_s"):
        assert getattr(new, key) == pytest.approx(exp[key], rel=1e-12)
    assert new.counts == exp["counts"] and new.chips == exp["chips"] == 1
    assert [g[0] for g in new.gaps] == [g[0] for g in exp["gaps"]]


def test_the_idle_parts_add_up_to_the_idle_share(new):
    base = trace.reduce_trace(str(NEW))
    parts = new.fetch_idle_s + new.host_idle_s + new.outside_idle_s
    assert parts / new.window_s == pytest.approx(base.idle_share, abs=1e-9)
    assert [g[1] for g in new.gaps] == pytest.approx(
        [g[1] for g in base.gaps], abs=1e-15)


def test_the_recorded_trace_holds_the_program_spans(new):
    assert set(new.counts) <= spans.PROGRAM_SPANS
    for name in ("serving.step", "executor.pass", "link.copy",
                 "serving.admit"):
        assert new.counts.get(name, 0) > 0, name
    assert 0.0 < new.link_busy_s <= new.window_s
    assert any(label.split(": ", 1)[1] in spans.PROGRAM_SPANS
               for label, _ in new.gaps)


def test_readers_read_the_recorded_window(tmp_path, monkeypatch, new):
    w = _window_on(tmp_path, monkeypatch, NEW)
    got = {name: harness.reader(name)(w) for name in READERS}
    assert 0.0 <= got["prefetch.link_busy_share"] <= 100.0
    assert got["serving.prefill_ms_per_admit"] > 0.0
    idle = 100.0 * w.trace.idle_share
    fetch = got["executor.fetch_idle_share"] or 0.0
    assert fetch + got["serving.host_idle_share"] <= idle + 1e-9


def test_the_recorded_traces_stay_small():
    for path in (OLD, NEW):
        assert path.stat().st_size < 1 << 20
