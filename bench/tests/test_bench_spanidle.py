"""Device idle time placed in the program's layer spans: hand-made events
with nested and overlapping ``repro.*`` spans, the recorded TPU v5e trace
(``data/v5e_wire.xplane.pb``, which predates program spans), and the
command line."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchlib import spanidle, tracefile

BENCH = Path(__file__).resolve().parents[1]
CHIP_TRACE = BENCH / "tests" / "data" / "v5e_wire.xplane.pb"
MS = 1e6  # ns


def test_idle_time_goes_to_the_innermost_program_span():
    devices = {
        "/device:TPU:0": [("a", 0 * MS, 1 * MS), ("a", 9 * MS, 10 * MS)],
        "/device:TPU:1": [("a", 0 * MS, 5 * MS)],
    }
    # one idle gap [1, 9] on chip 0, [5, 10] on chip 1; program spans:
    # flow.lookup [2, 8] holding flow.compact [3, 4] and, from another
    # thread, a span overlapping it without nesting
    spans = {"flow.lookup": [(2 * MS, 8 * MS)],
             "flow.compact": [(3 * MS, 4 * MS)],
             "egress.resolve": [(7 * MS, 9.5 * MS)]}
    red = spanidle.reduce(devices, spans, (0.0, 10 * MS))
    by = red["idle_by_span_s"]
    assert set(by) == {"outside", "flow.lookup", "flow.compact",
                       "egress.resolve"}
    # chip 0: outside [1,2]; lookup [2,3]+[4,7]; compact [3,4];
    # resolve [7,9] (it opened last).  chip 1: lookup [5,7]; resolve
    # [7,9.5]; outside [9.5,10]
    assert by["outside"] == pytest.approx((0.001 + 0.0005) / 2)
    assert by["flow.lookup"] == pytest.approx((0.004 + 0.002) / 2)
    assert by["flow.compact"] == pytest.approx(0.001 / 2)
    assert by["egress.resolve"] == pytest.approx((0.002 + 0.0025) / 2)
    host = {"window": [(0.0, 10 * MS)], "submit": [(1 * MS, 9 * MS)]}
    ref = tracefile.reduce(devices, host)
    assert sum(by.values()) == pytest.approx(
        sum(ref["idle_by_activity_s"].values()))
    # each gap is named by the span holding most of it
    assert red["span_gaps"] == [("flow.lookup", pytest.approx(0.008)),
                                ("egress.resolve", pytest.approx(0.005))]


def test_spans_that_end_together_and_gaps_no_span_covers():
    devices = {"/device:TPU:0": [("a", 4 * MS, 5 * MS)]}
    spans = {"ingress.stage": [(1 * MS, 3 * MS)],
             "ingress.dispatch": [(2 * MS, 3 * MS)],
             "ingress.device_wait": [(5 * MS, 6 * MS)]}
    red = spanidle.reduce(devices, spans, (0.0, 10 * MS))
    by = red["idle_by_span_s"]
    assert by["ingress.stage"] == pytest.approx(0.001)
    assert by["ingress.dispatch"] == pytest.approx(0.001)
    assert by["ingress.device_wait"] == pytest.approx(0.001)
    assert by["outside"] == pytest.approx(0.006)
    # [5, 10] is 4 ms outside, [0, 4] 2 ms outside and 1 ms in each span
    assert red["span_gaps"] == [("outside", pytest.approx(0.005)),
                                ("outside", pytest.approx(0.004))]


def test_the_recorded_chip_trace_reads_all_outside():
    """The recorded trace holds no program span: all of its idle time is
    ``outside``, and the gaps are the reducer's own longest gaps."""
    devices, spans, window = spanidle.load(str(CHIP_TRACE))
    assert spans == {}
    red = spanidle.reduce(devices, spans, window)
    ref = tracefile.reduce(*tracefile.load_events(str(CHIP_TRACE)))
    assert red["window_s"] == pytest.approx(ref["window_s"], rel=1e-12)
    idle = ref["window_s"] - ref["busy_s"]
    assert list(red["idle_by_span_s"]) == ["outside"]
    assert red["idle_by_span_s"]["outside"] == pytest.approx(idle, rel=1e-9)
    assert [n for n, _ in red["span_gaps"]] == ["outside"] * 10
    assert [s for _, s in red["span_gaps"]] == pytest.approx(
        [s for _, s in ref["longest_gaps"]])


def test_the_command_prints_one_json_line():
    p = subprocess.run([sys.executable, str(BENCH / "spanidle.py"),
                        str(CHIP_TRACE), "--gaps", "3"],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out) == {"window_s", "idle_by_span_s", "span_gaps"}
    assert [n for n, _ in out["span_gaps"]] == ["outside"] * 3


def test_a_trace_without_the_benchmark_window_spans_the_device_ops(
        monkeypatch):
    """An operator's own trace has no ``bench.window``: the window is
    then the extent of the device's ops."""
    devices = {"/device:TPU:0": [("a", 2 * MS, 3 * MS),
                                 ("a", 7 * MS, 8 * MS)]}
    monkeypatch.setattr(spanidle.tracefile, "load_events",
                        lambda path: (devices, {}))
    monkeypatch.setattr(spanidle, "load_spans",
                        lambda path: {"egress.resolve": [(4 * MS, 5 * MS)]})
    devs, spans, window = spanidle.load("any.xplane.pb")
    assert window == (2 * MS, 8 * MS)
    red = spanidle.reduce(devs, spans, window)
    assert red["idle_by_span_s"] == {"outside": pytest.approx(0.003),
                                     "egress.resolve": pytest.approx(0.001)}
    assert red["span_gaps"] == [("outside", pytest.approx(0.004))]
