"""The four-chip RSS cell ``mixed16-rss4.raw-cold`` as committed, at CPU
size on four virtual devices: its shards sit on four distinct devices, its
runs are correct, and a traced run reads the fabric's two layers, which a
one-shard server does not report."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

from benchlib import harness

ROOT = Path(__file__).resolve().parents[2]
CELL = "mixed16-rss4.raw-cold"
FABRIC_METRICS = ("route_us_per_pkt", "merge_us_per_pkt")

SCRIPT = textwrap.dedent("""
    import json, sys, tempfile
    from pathlib import Path
    root = Path(sys.argv[1])
    sys.path[:0] = [str(root / "bench"), str(root / "src"),
                    str(root / "bench" / "tests")]
    from bench_small import make_small_root
    from benchlib import deploy, harness
    small = make_small_root(Path(tempfile.mkdtemp()))
    placed = []
    build = deploy.build_server

    def spy(cfg, overrides=None):
        srv = build(cfg, overrides)
        placed.append([str(sh.engine.device) for sh in srv.shards])
        return srv
    deploy.build_server = spy
    out = harness.run_cell(sys.argv[2], 2 ** 31 + 41, 0.4, True, root=small)
    print(json.dumps({"out": out, "placed": placed}))
""")


def test_the_four_chip_cell_runs_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT), CELL],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    out, placed = got["out"], got["placed"]
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["mismatched_rows"]["value"] == 0
    assert out["failed"] == 0 and out["attempted"] > 0
    assert len(placed) == 1 and len(set(placed[0])) == 4, placed
    metrics = out["metrics"]
    for name in FABRIC_METRICS:
        assert metrics[name]["value"] > 0, name


def test_the_fabric_metrics_are_declared_for_the_four_chip_cell_only():
    bench = harness.load_benchmark(ROOT)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4
    for w in bench["workloads"]:
        names = {m["name"] for m in harness.cell_metrics(bench, w["name"],
                                                         True)}
        assert (set(FABRIC_METRICS) <= names) == (w["name"] == CELL)


def test_a_one_shard_server_reads_no_fabric_layer():
    from repro.launch.serve import PacketServer
    srv = PacketServer(max_width=8, ingress_batch=64, max_inflight=2)
    counters = harness.read_counters(srv)
    assert "flow_lookup_seconds_total" in counters
    ctx = types.SimpleNamespace(counters=counters, trace=None,
                                res=types.SimpleNamespace(answered=1000))
    for name in FABRIC_METRICS:
        assert harness.load_reader(ROOT, name)(ctx) is None, name
