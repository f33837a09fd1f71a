#!/usr/bin/env python3
"""Readings for the correctness limits: for each seed, set a cell up as
a run does (engine, warm-up rounds) and read the numbers the check
compares, for the program and, on the control seeds, for the bfloat16
reference in the program's place.  No window is measured.

    python3 bench/readings.py --workload sync-static-n128 \
        --seeds 11,12,13 --control-seeds 11,12,13

One JSON line per seed and side; the last line gives, per number, the
largest program reading (the lower reading of its limit) and the
smallest control reading (the upper).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from harness import device as devlib
    from harness import runner
    from harness.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    try:
        devlib.require_tpu(jax, cell.chips)
    except devlib.NoChip as e:
        return int(e.code)
    devlib.enable_compile_cache(jax, ROOT)
    seeds = _seeds(args.seeds)
    controls = _seeds(args.control_seeds)
    program, control = {}, {}
    for seed in dict.fromkeys(seeds + controls):
        t0 = time.perf_counter()
        engine, cap, end, sim = runner.setup(cell, seed, False, None)
        set_s = time.perf_counter() - t0
        cap.engine = None
        del engine
        gc.collect()
        sides = ([False] if seed in seeds else []) + \
            ([True] if seed in controls else [])
        for is_control in sides:
            t1 = time.perf_counter()
            got = runner.check(cell, seed, sim, cap.ticks, end,
                               control=is_control)
            vals = {k: v["value"] for k, v in got.items()}
            (control if is_control else program)[seed] = vals
            print(json.dumps({"seed": seed, "side": "control" if is_control
                              else "program", "readings": vals,
                              "setup_s": set_s,
                              "check_s": time.perf_counter() - t1}),
                  flush=True)
    names = sorted({k for v in list(program.values()) +
                    list(control.values()) for k in v})
    print(json.dumps({
        "workload": args.workload,
        "lower": {k: max((v[k] for v in program.values() if k in v),
                         default=None) for k in names},
        "upper": {k: min((v[k] for v in control.values() if k in v),
                         default=None) for k in names},
        "n_program": len(program), "n_control": len(control)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
