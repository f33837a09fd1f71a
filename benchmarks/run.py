"""Benchmark harness entry point: run every paper-table benchmark (quick
variants by default) and print one CSV block per table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig4,fig8]
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks.common import save_rows

BENCHES = ["fig4", "fig5", "fig6", "fig8", "fig9", "table2", "roofline",
           "sim_warmstart", "sim_async", "sim_scale", "sim_drift",
           "solver_scaling"]


def _module(name: str):
    import importlib
    mod = {
        "fig4": "benchmarks.fig4_convergence",
        "fig5": "benchmarks.fig5_divergence_regimes",
        "fig6": "benchmarks.fig6_energy_sweep",
        "fig8": "benchmarks.fig8_alpha_baselines",
        "fig9": "benchmarks.fig9_psi_baselines",
        "table2": "benchmarks.table2_bound_tightness",
        "roofline": "benchmarks.roofline_table",
        "sim_warmstart": "benchmarks.sim_warmstart",
        "sim_async": "benchmarks.sim_async",
        "sim_scale": "benchmarks.sim_scale",
        "sim_drift": "benchmarks.sim_drift",
        "solver_scaling": "benchmarks.solver_scaling",
    }[name]
    return importlib.import_module(mod)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    names = args.only.split(",") if args.only else BENCHES
    quick = not args.full
    failures = []
    for name in names:
        print(f"\n===== {name} ({'quick' if quick else 'full'}) =====")
        t0 = time.time()
        try:
            rows = _module(name).main(quick=quick)
            save_rows(name, rows)
            print(f"[{name}] {len(rows)} rows in {time.time()-t0:.1f}s")
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            print(f"[{name}] FAILED: {e}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
