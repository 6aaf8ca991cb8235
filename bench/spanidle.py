#!/usr/bin/env python3
"""Device idle time of a profiler trace by the program's layer span.

    python3 bench/spanidle.py <trace dir or .xplane.pb> [--gaps N]

Prints one JSON line: the window's length, the device's idle seconds by
the innermost ``repro.<span>`` open at the time (``outside`` where none
is; mean over chips), and the longest idle gaps, each named by the span
that covers most of it (``benchlib/spanidle.py``).  The program writes
its spans into a trace only while a profiler session runs
(``jax.profiler.start_trace``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import spanidle, tracefile  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace", help="a profiler trace directory or .xplane.pb")
    p.add_argument("--gaps", type=int, default=10)
    args = p.parse_args(argv)
    path = (tracefile.find_xplane(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    out = spanidle.reduce(*spanidle.load(path), n_gaps=args.gaps)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
