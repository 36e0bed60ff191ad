"""Fig. 8: multi-application scenario — energy gain, tier deployment
probabilities, failure probability, and exit-point distribution, as the user
population grows.  FIN gamma=10, per paper.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core import run_multiapp

from .common import Row, kv, timed

APPS = ("h1", "h2", "h3", "h4", "h5", "h6")


def run(user_counts=(10, 25, 50), seed: int = 1) -> List[Row]:
    rows: List[Row] = []
    for n in user_counts:
        res, us = timed(run_multiapp, n, seed=seed, repeats=1)
        for app in APPS:
            fin = res.stats[app]["fin"]
            mcp = res.stats[app]["mcp"]
            tiers_f = fin.tier_probs()
            tiers_m = mcp.tier_probs()
            rows.append(Row(
                f"fig8/{app}/users{n}", us / len(APPS),
                kv(energy_ratio_fin_over_mcp=res.energy_gain(app),
                   fail_fin=fin.failure_prob, fail_mcp=mcp.failure_prob,
                   fin_mobile=tiers_f.get("mobile", 0.0),
                   fin_edge=tiers_f.get("edge", 0.0),
                   fin_cloud=tiers_f.get("cloud", 0.0),
                   mcp_mobile=tiers_m.get("mobile", 0.0),
                   mcp_edge=tiers_m.get("edge", 0.0),
                   mcp_cloud=tiers_m.get("cloud", 0.0),
                   fin_exits="/".join(f"{p:.2f}" for p in fin.exit_probs()),
                   mcp_exits="/".join(f"{p:.2f}" for p in mcp.exit_probs()))))
    # hard-contention variant (app slice divided across users)
    res, us = timed(run_multiapp, 40, seed=seed, repeats=1,
                    divide_slice_by_users=True)
    for app in APPS:
        fin = res.stats[app]["fin"]
        mcp = res.stats[app]["mcp"]
        rows.append(Row(
            f"fig8-contention/{app}/users40", us / len(APPS),
            kv(energy_ratio=res.energy_gain(app),
               fail_fin=fin.failure_prob, fail_mcp=mcp.failure_prob)))

    # population-scale variant: uplink qualities snapped to 16 buckets, so
    # users in a bucket share an identical network — the MCP baseline loop
    # serves repeats from its per-bucket solution cache and the batched FIN
    # path dedups extended graphs per bucket; continuous-draw run of the
    # same size timed alongside for the speedup
    n_pop = 200
    res_c, us_c = timed(run_multiapp, n_pop, seed=seed, repeats=2)
    res_b, us_b = timed(run_multiapp, n_pop, seed=seed, repeats=2,
                        uplink_buckets=16)
    hits = sum(res_b.stats[app]["mcp"].solve_cache_hits for app in APPS)
    rows.append(Row(
        f"fig8-population/users{n_pop}", us_b,
        kv(buckets=16, mcp_cache_hits=hits,
           continuous_ms=us_c / 1e3, bucketed_ms=us_b / 1e3,
           speedup=us_c / us_b,
           mean_energy_ratio=float(np.mean(
               [res_b.energy_gain(app) for app in APPS])))))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r.csv())
