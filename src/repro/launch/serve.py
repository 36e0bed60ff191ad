"""Serving launcher:  PYTHONPATH=src python -m repro.launch.serve \
    --arch qwen3-4b --requests 16 --max-new 8 [--cache-len 512] \
    [--threshold 0.7] [--reduced]

Runs the split-serving engine (exit-aware continuous batching) with a FIN
placement over the paper's mobile-edge-cloud system, and reports
throughput / exit usage / placement-model energy.  The model runs at its
published widths with random weights; ``--reduced`` swaps in the tiny
same-family config for CPU smoke runs.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_NAMES, get
from repro.core import AppRequirements, paper_profile
from repro.core.scenarios import paper_scenario
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.runtime.serve_engine import SplitServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--threshold", type=float, default=0.7)
    ap.add_argument("--reduced", action="store_true",
                    help="the tiny same-family config (CPU smoke runs)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get(args.arch, reduced=args.reduced)
    if not cfg.has_decoder:
        raise SystemExit(f"{args.arch} is encoder-only; no serve path")
    params = jax.jit(T.init_model, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    eng = SplitServeEngine(
        cfg, params, batch_size=args.batch, cache_len=args.cache_len,
        thresholds=[args.threshold] * (len(cfg.exit_layer_list)),
        network=paper_scenario(), profile=paper_profile("h2"),
        req=AppRequirements(alpha=0.55, delta=8e-3))
    for i in range(args.requests):
        eng.submit([1 + i % 7, 2, 3], max_new_tokens=args.max_new)
    stats = eng.run()
    print(f"steps={stats.steps} tokens={stats.tokens_out} "
          f"phi={stats.measured_phi} energy={stats.energy_j*1e3:.2f}mJ "
          f"blocks saved={stats.blocks_saved}")


if __name__ == "__main__":
    main()
