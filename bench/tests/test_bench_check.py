"""The correctness check of every cell, driven through the whole harness
with the chip look skipped (CPU, tiny sizes): sound runs come out
correct; the control (the int8 lane) and planted faults come out not
correct."""

from __future__ import annotations

import numpy as np
import pytest

from benchlib import harness

CELLS = ["mixed16.raw-cold", "mlp16-wire.unique", "mixed16.raw-cold-rate"]
INT8_LANE = {"weight_bits": 8, "kernel_variant": "int8"}


def _run(root, cell, seed, **kw):
    return harness.run_cell(cell, seed, 0.4, False, root=root, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_runs_are_correct(small_root, cell):
    out = _run(small_root, cell, 2 ** 31 + 9)
    checks = out["checks"]
    assert out["correct"] is True, checks
    assert checks["mismatched_rows"]["value"] == 0
    assert checks["compared_rows"]["value"] >= 64
    assert out["failed"] == 0 and out["attempted"] > 0


def test_the_rate_loop_times_every_packet_from_its_due_time(small_root):
    out = _run(small_root, "mixed16.raw-cold-rate", 2 ** 31 + 13)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["p50_us"]["value"] > 0
    # every packet due in the 0.4 s window at the shrunk 50k pkt/s, answered
    assert abs(out["attempted"] - 0.4 * 50000) < 1000
    assert out["failed"] == 0 and out["loop"]["lateness_s"] >= 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_int8_lane_control_is_not_correct(small_root, cell):
    out = _run(small_root, cell, 5, overrides=INT8_LANE)
    assert out["correct"] is False
    assert out["checks"]["mismatched_rows"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced_is_caught(
        small_root, cell, monkeypatch):
    import repro.core.ingress as ingress
    emit = ingress.emit_results_np

    def altered(*a, **kw):
        rows = emit(*a, **kw).copy()
        rows[::16, -1] ^= 1  # one bit of every 16th egress row
        return rows
    monkeypatch.setattr(ingress, "emit_results_np", altered)
    out = _run(small_root, cell, 6)
    assert out["correct"] is False
    assert out["checks"]["mismatched_rows"]["value"] > 0


def test_flow_registers_left_unchanged_are_caught(small_root, monkeypatch):
    import repro.flow.frontend as frontend
    update = frontend.flow_update

    def stale(*a, **kw):
        kw["copy"] = True  # the register file is never written back
        return update(*a, **kw)
    monkeypatch.setattr(frontend, "flow_update", stale)
    out = _run(small_root, "mixed16.raw-cold", 10)
    assert out["correct"] is False
    assert out["checks"]["mismatched_rows"]["value"] > 0


@pytest.fixture(scope="module")
def sharded_root(small_root, tmp_path_factory):
    """The small root plus ``mixed16`` on a 4-shard fabric: the
    configuration key ``shards`` is all a multi-chip deployment needs."""
    import json
    import shutil
    root = tmp_path_factory.mktemp("sharded") / "root"
    shutil.copytree(small_root, root)
    cfg = json.loads((root / "bench/configs/mixed16.json").read_text())
    cfg["shards"] = 4
    (root / "bench/configs/mixed16-shards4.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "mixed16-shards4",
                             "file": "bench/configs/mixed16-shards4.json"})
    bench["workloads"].append({"name": "shards4.raw-cold",
                               "config": "mixed16-shards4",
                               "traffic": "raw-cold", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_sharded_deployment_is_correct(sharded_root):
    out = _run(sharded_root, "shards4.raw-cold", 2 ** 31 + 11)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["compared_rows"]["value"] >= 64


def test_a_broken_merge_across_shards_is_caught(sharded_root, monkeypatch):
    import repro.core.ingress as ingress
    drain = ingress.IngressPipeline.drain

    def rotated(self, *a, **kw):
        out = drain(self, *a, **kw)
        if self.shard_id == 1 and len(out) > 1:
            out = out[1:] + out[:1]  # shard 1's answers misplaced
        return out
    monkeypatch.setattr(ingress.IngressPipeline, "drain", rotated)
    out = _run(sharded_root, "shards4.raw-cold", 7)
    assert out["correct"] is False
    assert out["checks"]["mismatched_rows"]["value"] > 0


def test_packets_left_unanswered_are_caught(small_root, monkeypatch):
    from repro.core.ingress import PacketError
    from repro.launch.serve import PacketServer
    drain = PacketServer.drain_packets

    def lossy(self, *a, **kw):
        out = drain(self, *a, **kw)
        return [PacketError(ticket=0, reason="lost")] + out[1:]
    monkeypatch.setattr(PacketServer, "drain_packets", lossy)
    out = _run(small_root, "mlp16-wire.unique", 8)
    assert out["correct"] is False
    assert out["checks"]["unanswered"]["value"] > 0
    assert np.isfinite(out["metrics"]["answered_pps"]["value"])
