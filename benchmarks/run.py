"""Benchmark harness — one bench per paper table/figure + system benches.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only substr] [--json]
                                                [--smoke]

Prints ``name,us_per_call,derived`` CSV rows (one per measurement) or, with
``--json``, a JSON document ``{"benches": {<bench>: [row...]}}`` with the
derived key-values parsed (the format of the committed BENCH_PR2.json).
``--smoke`` shrinks instance sizes / repeats (REPRO_BENCH_SMOKE=1) for the
CI perf-regression smoke job.  Benches match the paper artifacts:
  fig4      Table VI configuration study (latency / energy / accuracy)
  fig5_7    Opt vs MCP vs FIN(3,10) energy across (delta, alpha) targets
  fig6      computation/communication energy breakdown
  fig8      multi-application scenario (gain, tiers, failures, exits)
  table3    DNN block profiles extracted from the JAX models vs paper
  table7    solver execution times (+ large-instance scaling backends)
  online    warm plan-IR re-solves vs cold rebuilds under churn (+ e2e
            orchestrator throughput with hysteresis and failures)
  congestion shared-capacity coupled ticks: converged-tick throughput,
            fixed-point iterations and admission rate vs the uncoupled
            population path on self-calibrated over-subscription
  failover  contingency-library hits vs warm mask+re-solve vs cold rebuild
            (bit-exact, zero-relaxation), + tier-outage trace hit rate
  faults    crash consistency: boundary-checkpoint overhead (asserted
            bit-identical to the uncheckpointed run), cold restore+replay
            latency, and quarantine-policy throughput under injected
            telemetry corruption
  stream    streaming tick pipeline: double-buffered ticks vs the sync
            loop, fused vs chunked newborn relax, bounded re-relaxation
            (all asserted bit-exact), + 1e6/1e7-user scale rows
  kernels   Pallas kernel vs reference oracle timings (interpreted on CPU)
  roofline  dry-run derived roofline terms per (arch x shape)
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import traceback

BENCHES = [
    "bench_fig4",
    "bench_fig5_7",
    "bench_fig6",
    "bench_fig8",
    "bench_table3",
    "bench_table7",
    "bench_online",
    "bench_congestion",
    "bench_failover",
    "bench_faults",
    "bench_stream",
    "bench_kernels",
    "bench_engine",
    "bench_roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only benches whose name contains this substring")
    ap.add_argument("--json", action="store_true",
                    help="emit a JSON document with parsed derived key-values"
                         " instead of CSV rows")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes/repeats (sets REPRO_BENCH_SMOKE=1) — "
                         "the CI perf smoke mode")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    if not args.json:
        print("name,us_per_call,derived")
    collected = {}
    failures = []
    for mod_name in BENCHES:
        if args.only and args.only not in mod_name:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
        except ModuleNotFoundError as e:
            failures.append((mod_name, f"missing: {e}"))
            continue
        try:
            for row in mod.run():
                if args.json:
                    collected.setdefault(mod_name.replace("bench_", ""),
                                         []).append(row.to_dict())
                else:
                    print(row.csv())
                    sys.stdout.flush()
        except Exception:
            failures.append((mod_name, traceback.format_exc()))
    if args.json:
        print(json.dumps({"smoke": bool(args.smoke), "benches": collected},
                         indent=1))
    if failures:
        for name, err in failures:
            print(f"# BENCH-FAILED {name}: {err.splitlines()[-1]}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
