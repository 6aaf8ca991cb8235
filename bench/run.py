#!/usr/bin/env python3
"""Chip benchmark of the packet data plane: one cell, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs the cell ``<name>`` of ``BENCHMARK.json`` on the chips of this
machine: builds its configuration's server with tenants drawn from
``--seed``, generates its traffic mix, warms up, measures ``--seconds``
of serving from the client side, checks the sampled answers against the
plain reference, and prints one JSON line as the last line of standard
output.  ``--trace 1`` records a profiler trace of the window and reports
the per-layer metrics instead of the end-to-end ones.

Exits non-zero, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for.  It never falls back to the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from benchlib import harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    root = harness.ROOT
    harness.set_cache_env(root)
    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, args.workload)
    why = harness.require_chips(cell["chips"])
    if why:
        print(why, file=sys.stderr)
        return 1
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), root=root, t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
