"""Paper-figure reproductions: one bench per table/figure of the paper.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only substr]

Prints ``name,us_per_call,derived`` CSV rows, one per measurement.  The
rows carry the paper's energies, placements and feature counts; the speed
of the system is measured on the chip by ``bench/run.py`` (see
``BENCHMARK.json``).  Benches match the paper artifacts:
  fig4      Table VI configuration study (latency / energy / accuracy)
  fig5_7    Opt vs MCP vs FIN(3,10) energy across (delta, alpha) targets
  fig6      computation/communication energy breakdown
  fig8      multi-application scenario (gain, tiers, failures, exits)
  table3    DNN block profiles extracted from the JAX models vs paper
"""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback

BENCHES = [
    "bench_fig4",
    "bench_fig5_7",
    "bench_fig6",
    "bench_fig8",
    "bench_table3",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only benches whose name contains this substring")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for mod_name in BENCHES:
        if args.only and args.only not in mod_name:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            for row in mod.run():
                print(row.csv())
                sys.stdout.flush()
        except Exception:
            failures.append((mod_name, traceback.format_exc()))
    if failures:
        for name, err in failures:
            print(f"# BENCH-FAILED {name}: {err.splitlines()[-1]}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
