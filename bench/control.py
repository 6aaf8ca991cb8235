#!/usr/bin/env python3
"""The control of a cell's correctness check: the same run with the
program's own lower-precision path switched on.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> ...

The configurations state an int16 weight lane; the control serves the same
tenants through the int8 lane (``weight_bits=8, kernel_variant="int8"``),
the step down that would tempt a later change.  Each seed prints the
run's compared numbers; the check has to come out not correct on every
seed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import harness  # noqa: E402

INT8_LANE = {"weight_bits": 8, "kernel_variant": "int8"}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    harness.set_cache_env(harness.ROOT)
    cell = harness.find_cell(harness.load_benchmark(harness.ROOT),
                             args.workload)
    why = harness.require_chips(cell["chips"])
    if why:
        print(why, file=sys.stderr)
        return 2
    failed_all = True
    for seed in args.seeds:
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               overrides=INT8_LANE)
        failed_all &= not out["correct"]
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
