"""The harness finds a configuration, a traffic mix and a metric by name,
from files alone: a throwaway set in a temporary directory, no existing
file edited (CPU, tiny sizes)."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchlib import harness

BENCH = Path(__file__).resolve().parents[1]


TOY_GENERATOR = """
import numpy as np
from benchlib import gen


def build(mix, cfg, seed, seconds):
    # every window packet is one of the mix's few rows, in turn
    rows = gen.wire_pool(gen.stream_rng(seed, 2), n_rows=mix["rows"],
                         model_ids=gen.tenant_ids(cfg),
                         width=cfg["server"]["max_width"], lo=-99, hi=99,
                         frac=cfg["server"]["frac_bits"])
    n = gen.window_packets(mix, seconds)
    return gen.Traffic("wire", rows, True, np.zeros((0, 21), np.uint8),
                       rows, np.arange(0, n, 7))
"""

TOY_LOOP = """
import time
from benchlib.loop import Client


def run(srv, traffic, params, seconds, spans):
    # a fixed number of iterations of a fixed size, whatever the time
    client = Client(srv, traffic, params["chunk"], spans)
    t0 = time.perf_counter()
    for _ in range(params["iterations"]):
        client.iterate(params["max_burst"])
    return client.result(time.perf_counter() - t0)
"""


def test_a_new_cell_mix_and_metric_are_found_by_name(small_root, tmp_path):
    root = tmp_path
    for kind in ("configs", "traffic", "metrics", "generators", "loops"):
        (root / "bench" / kind).mkdir(parents=True)
    cfg = json.loads((small_root / "bench/configs/mlp16-wire.json")
                     .read_text())
    cfg["tenants"]["mlp"]["ids"] = [7, 9]
    (root / "bench/configs/toy-cfg.json").write_text(json.dumps(cfg))
    mix = json.loads((small_root / "bench/traffic/unique.json").read_text())
    mix["pool_rows"] = 4096
    (root / "bench/traffic/toy-mix.json").write_text(json.dumps(mix))
    (root / "bench/traffic/toy-few.json").write_text(json.dumps({
        "generator": "toy_few_rows", "rows": 5, "sized_for_pps": 1000,
        "loop": {"kind": "toy_counted", "iterations": 3, "max_burst": 512,
                 "chunk": 128},
        "min_compared": 64}))
    (root / "bench/generators/toy_few_rows.py").write_text(TOY_GENERATOR)
    (root / "bench/loops/toy_counted.py").write_text(TOY_LOOP)
    for kind, name in (("metrics", "answered_pps"), ("metrics", "setup_s"),
                       ("generators", "wire_pool"), ("loops", "closed")):
        (root / "bench" / kind / f"{name}.py").write_text(
            (BENCH / kind / f"{name}.py").read_text())
    (root / "bench/metrics/toy_iterations.py").write_text(
        "def read(ctx):\n    return float(ctx.res.iterations)\n")
    (root / "bench/metrics/toy_silent.py").write_text(
        "def read(ctx):\n    return None\n")
    bench = {
        "configs": [{"name": "toy-cfg", "file": "bench/configs/toy-cfg.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy-cfg",
                       "traffic": "toy-mix", "chips": 1},
                      {"name": "toy.few", "config": "toy-cfg",
                       "traffic": "toy-few", "chips": 1}],
        "end_to_end": [{"name": "answered_pps", "unit": "pkt/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "toy_iterations", "unit": "1",
                       "moves": "answered_pps"},
                      {"name": "toy_silent", "unit": "1",
                       "moves": "answered_pps"}]}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = harness.run_cell("toy.cell", 2 ** 31 + 77, 0.3, False, root=root)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"answered_pps", "setup_s"}
    assert list(out)[-1] == "checks"

    # a generator and a loop of its own, each a new file found by name
    out = harness.run_cell("toy.few", 2 ** 31 + 78, 0.3, True, root=root)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 3 * 512 and out["loop"]["iterations"] == 3
    # the sample, every 7th position of the 0.3 * 1000 + 512 packets
    # the window may take, all of them submitted
    assert out["checks"]["compared_rows"]["value"] == len(range(0, 812, 7))
    assert out["metrics"]["toy_iterations"]["value"] == 3.0

    # per-layer metrics without a ``workloads`` key follow the end-to-end
    # metric they move; a reader that finds nothing is left out
    per_layer = harness.cell_metrics(bench, "toy.cell", True)
    assert [m["name"] for m in per_layer] == ["toy_iterations", "toy_silent"]
    assert "toy_silent" not in out["metrics"]
    read = harness.load_reader(root, "toy_iterations")
    assert read.__module__ == "bench_metrics_toy_iterations"
    # a metric split by suffix, with no file of its own, reads as its base
    read = harness.load_reader(root, "toy_iterations.other")
    assert read.__module__ == "bench_metrics_toy_iterations"
    with pytest.raises(KeyError):
        harness.find_cell(bench, "no.such.cell")


def test_metric_selection_follows_workload_lists():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cold = [m["name"] for m in harness.cell_metrics(
        bench, "mixed16.raw-cold", True)]
    assert "forest_kernel_roofline" in cold and "setup_s" not in cold
    wire = [m["name"] for m in harness.cell_metrics(
        bench, "mlp16-wire.unique", True)]
    assert "forest_kernel_roofline" not in wire
    for w in bench["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(bench, w["name"],
                                                       False)}
        assert {"setup_s", "answered_pps"} <= e2e
        assert harness.cell_metrics(bench, w["name"], True)
    rate = {m["name"] for m in harness.cell_metrics(
        bench, "mixed16.raw-cold-rate", True)}
    assert rate == {"submit_us_per_pkt.rate", "drain_us_per_pkt.rate",
                    "short_circuit_share.rate", "device_idle_share.rate"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(BENCH.parent, m["name"]))
    for w in bench["workloads"]:
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (BENCH / "generators" / f"{mix['generator']}.py").is_file()
        assert (BENCH / "loops" / f"{mix['loop']['kind']}.py").is_file()


def test_the_command_refuses_to_run_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "mixed16.raw-cold", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
