"""Split serving demo: FIN-placed early-exit LM with continuous batching.

Builds a small early-exit LM, derives its Plane-2 profile from its
architecture (``profile_from_arch``), solves the FIN
placement over the mobile-edge-cloud system, then serves a request stream
with exit-aware continuous batching — including a mid-run node failure that
triggers an elastic FIN re-placement.

Run:  PYTHONPATH=src python examples/serve_split.py
"""
import sys

import jax

from repro.configs import get
from repro.core import AppRequirements, profile_from_arch
from repro.core.scenarios import paper_scenario
from repro.models import transformer as T
from repro.runtime.serve_engine import SplitServeEngine


def main() -> int:
    cfg = get("qwen3-4b", reduced=True)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    # the placement lands off the mobile tier, so the mid-run failure below
    # actually re-places (warm, via the plan IR); alpha 1.0 keeps the final
    # head deployed (the profile claims no accuracy for the early exits)
    network = paper_scenario(uplink_bps=0.3e9)
    profile = profile_from_arch(cfg)
    req = AppRequirements(alpha=1.0, delta=1e-3)

    eng = SplitServeEngine(cfg, params, batch_size=4, cache_len=128,
                           thresholds=[0.6, 0.6], network=network,
                           profile=profile, req=req)
    tiers = [n.tier for n in network.nodes]
    print("FIN placement:",
          [f"l{i+1}@{tiers[n]}" for i, n in
           enumerate(eng.placement.placement)],
          f"exit-{eng.placement.final_exit + 1}")

    for i in range(12):
        eng.submit([1 + i, 2, 3], max_new_tokens=6)

    # serve half the load, then lose the deepest-tier node
    for _ in range(24):
        eng.step()
    victim = max(p for p in eng.placement.placement)
    if victim != network.source_node:
        print(f"\n!! node {network.nodes[victim].name} fails — warm re-solve")
        eng.fail_node(victim)
        print("new placement:",
              [f"l{i+1}@{eng.network.tier_of(n)}" for i, n in
               enumerate(eng.placement.placement)],
              f"({eng.stats.blocks_migrated} blocks migrated, "
              f"{eng.stats.migration_bits/8e6:.2f} MB of cut state)")
        for _ in range(12):
            eng.step()
        print(f"   node {network.nodes[victim].name} recovers")
        eng.recover_node(victim)
    stats = eng.run(max_steps=500)

    print(f"\nsteps            : {stats.steps}")
    print(f"tokens generated : {stats.tokens_out}")
    print(f"exit usage (phi) : {stats.measured_phi}")
    print(f"blocks executed  : {stats.blocks_executed} "
          f"(saved by exits: {stats.blocks_saved})")
    print(f"placement energy : {stats.energy_j*1e3:.3f} mJ")
    print(f"re-placements    : {stats.replacements}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
