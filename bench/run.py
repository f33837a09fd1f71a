#!/usr/bin/env python3
"""The simulator's chip benchmark: one run of one cell.

    python3 bench/run.py --workload sync-static-n128 --seed 7 \
        --seconds 10 --trace 0

Run it from the root of a checkout, on a machine with the chips the cell
asks for.  ``BENCHMARK.json`` names each cell's configuration and
traffic mix; ``bench/harness/spec.py`` says where the rest is found.
The last line of standard output is one JSON object: ``correct``,
``attempted`` (rounds in the window), ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, and, last, ``checks``: each number the correctness check
compared, with its limit.  Earlier lines on standard error give set-up,
compile counts, round counts and the check.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    from harness import runner
    from harness.device import NoChip
    from harness.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro", "sim")):
        print(f"bench: no simulator under {src}; run from a checkout of "
              "the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    try:
        return runner.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except NoChip as e:
        return int(e.code)


if __name__ == "__main__":
    sys.exit(main())
