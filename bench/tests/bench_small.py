"""The benchmark shrunk to CPU size in a directory of its own.

The copy keeps every width and semantics of the real configurations and
mixes; only scale shrinks (batch, flow table, flows, pool, window), so a
CPU run drives the same code paths the chip runs do.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

SMALL_SERVER = {"ingress_batch": 256, "max_inflight": 2,
                "cache_capacity_pow2": 10, "flow_capacity_pow2": 12}
SMALL_LOOP = {"max_burst": 1024, "chunk": 256}


def shrink_mix(mix: dict) -> dict:
    mix = json.loads(json.dumps(mix))
    mix["loop"].update(SMALL_LOOP)
    if "sized_for_pps" in mix:
        mix["sized_for_pps"] = 200000
    if "rate" in mix["loop"]:
        mix["loop"]["rate"] = 50000
    if "n_flows" in mix:
        mix["n_flows"] = 256
    if mix.get("pool_rows"):
        mix["pool_rows"] = 8192
    warm = mix.get("warm", {})
    small = {"wire_rows": 512, "raw_packets": 512, "raw_flows": 64}
    for k, v in small.items():
        if warm.get(k):
            warm[k] = v
    if "sample_flows" in mix:
        mix["sample_flows"] = 32
    if "sample_packets" in mix:
        mix["sample_packets"] = 512
    mix["min_compared"] = 64
    return mix


def make_small_root(dst: Path) -> Path:
    """A benchmark root under ``dst``: ``BENCHMARK.json``, shrunk configs
    and mixes, and the real generators, loops and metric readers."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dst / "bench" / "configs").mkdir(parents=True)
    (dst / "bench" / "traffic").mkdir(parents=True)
    for kind in ("generators", "loops", "metrics"):
        shutil.copytree(BENCH / kind, dst / "bench" / kind)
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["server"].update(SMALL_SERVER)
        (dst / c["file"]).write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        src = BENCH / "traffic" / f"{w['traffic']}.json"
        mix = shrink_mix(json.loads(src.read_text()))
        (dst / "bench" / "traffic" / src.name).write_text(json.dumps(mix))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst
