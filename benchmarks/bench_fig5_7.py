"""Figs. 5 & 7: total energy of Opt / MCP / FIN(gamma=3,10) vs (delta, alpha).

Fig. 5 uses B-AlexNet (h2, CIFAR10); Fig. 7 uses B-LeNet (h6, EMNIST).
Also validates the paper's headline claims:
  * FIN(gamma=10) matches Opt (within the 1+1/gamma competitive ratio);
  * FIN(gamma=3) still never loses to MCP;
  * tighter latency targets force split deployments with higher energy.

The competitive-ratio claim is also a tier-1 test
(``tests/test_fin.py::test_fin_matches_opt_on_paper_apps``, one case per
point of ``SWEEPS``).
"""
from __future__ import annotations

from typing import List

from repro.core import (AppRequirements, paper_profile, solve_fin, solve_mcp,
                        solve_opt)
from repro.core.scenarios import paper_scenario

from .common import Row, kv, timed

#: (figure, app, accuracy targets, latency targets ms)
SWEEPS = [
    ("fig5", "h2", (0.55, 0.80), (2.0, 5.0, 8.0, 12.0)),
    ("fig7", "h6", (0.93, 0.99), (0.05, 0.1, 0.5, 1.0)),
]


def run() -> List[Row]:
    nw = paper_scenario()
    rows: List[Row] = []
    for fig, app, alphas, deltas in SWEEPS:
        prof = paper_profile(app)
        for alpha in alphas:
            for delta_ms in deltas:
                req = AppRequirements(alpha=alpha, delta=delta_ms * 1e-3)
                opt, us_o = timed(solve_opt, nw, prof, req)
                fin10, us_f10 = timed(solve_fin, nw, prof, req, gamma=10)
                fin3, us_f3 = timed(solve_fin, nw, prof, req, gamma=3)
                mcp, us_m = timed(solve_mcp, nw, prof, req)

                def e(sol):
                    return sol.energy * 1e3 if sol.feasible else float("nan")

                def place(sol):
                    if not sol.feasible:
                        return "-"
                    h = sol.config.tier_histogram(nw)
                    return f"{h.get('mobile',0)}|{h.get('edge',0)}|{h.get('cloud',0)}"

                rows.append(Row(
                    f"{fig}/{app}/a{alpha}/d{delta_ms}ms", us_f10,
                    kv(opt_mJ=e(opt), fin10_mJ=e(fin10), fin3_mJ=e(fin3),
                       mcp_mJ=e(mcp), fin10_place=place(fin10),
                       opt_place=place(opt), mcp_place=place(mcp),
                       fin10_exit=(fin10.config.final_exit + 1
                                   if fin10.feasible else -1))))
                # competitive-ratio check recorded inline
                if opt.feasible and fin10.feasible:
                    assert fin10.energy <= opt.energy * 1.1 + 1e-15
    return rows


if __name__ == "__main__":
    for r in run():
        print(r.csv())
