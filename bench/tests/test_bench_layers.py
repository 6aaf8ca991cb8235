"""The per-layer readers of the program's layer spans: a traced run of
each closed-loop cell (CPU, tiny sizes) reads every counter metric the
cell declares, and their sum stays inside the client's own submit and
drain time; a program without the spans reads as nothing."""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from benchlib import harness

ROOT = Path(__file__).resolve().parents[2]
SIX = ("flow_lookup_us_per_pkt", "flow_update_us_per_pkt",
       "ingest_us_per_pkt", "dispatch_us_per_pkt", "device_wait_us_per_pkt",
       "egress_us_per_pkt")


@pytest.mark.parametrize("cell", ["mixed16.raw-cold", "mlp16-wire.unique"])
def test_a_traced_run_reads_every_layer(small_root, cell):
    out = harness.run_cell(cell, 2 ** 31 + 21, 0.4, True, root=small_root)
    assert out["correct"] is True, out["checks"]
    bench = json.loads((small_root / "BENCHMARK.json").read_text())
    declared = {m["name"] for m in harness.cell_metrics(bench, cell, True)
                if m["source"] == "program_counter"}
    assert declared and declared <= set(SIX)
    got = out["metrics"]
    assert declared <= set(got)
    assert all(got[m]["value"] >= 0 for m in declared)
    if cell == "mixed16.raw-cold":
        assert declared == set(SIX)
        assert got["flow_lookup_us_per_pkt"]["value"] > 0
        assert got["flow_update_us_per_pkt"]["value"] > 0
    # every span runs inside the client's submit and drain calls, and
    # together they cover most of that time
    client = (got["submit_us_per_pkt"]["value"]
              + got["drain_us_per_pkt"]["value"])
    layers = sum(got[m]["value"] for m in declared)
    assert 0.5 * client < layers <= client * 1.001


def _ctx(counters):
    return types.SimpleNamespace(counters=counters, trace=None,
                                 res=types.SimpleNamespace(answered=1000))


def test_a_program_without_spans_reads_as_nothing():
    """What the readers see on a program that keeps no span counters."""
    counters = {"ingress_packets_total": 1000.0}
    for name in SIX:
        read = harness.load_reader(ROOT, name)
        assert read(_ctx(counters)) is None, name


def test_the_readers_sum_their_spans():
    counters = {"flow_parse_seconds_total": 0.001,
                "flow_lookup_seconds_total": 0.002,
                "flow_compact_seconds_total": 0.0,
                "egress_encode_seconds_total": 0.004}
    read = harness.load_reader(ROOT, "flow_lookup_us_per_pkt")
    assert read(_ctx(counters)) == pytest.approx(3.0)
    read = harness.load_reader(ROOT, "egress_us_per_pkt")
    assert read(_ctx(counters)) == pytest.approx(4.0)
