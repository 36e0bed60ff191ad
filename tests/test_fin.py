"""FIN solver tests: optimality vs Opt, feasibility, paper-scenario behaviour."""
import numpy as np
import pytest

from repro.core import (AppRequirements, Config, Network, build_extended_graph,
                        build_feasible_graph, evaluate_config, make_network,
                        paper_profile, solve_fin, solve_mcp, solve_opt,
                        synthetic_profile)
from repro.core.scenarios import paper_scenario


@pytest.fixture(scope="module")
def scenario():
    return paper_scenario()


#: (figure, app, accuracy targets, latency targets ms): Fig. 5 sweeps
#: B-AlexNet (h2), Fig. 7 sweeps B-LeNet (h6)
FIG5_7_SWEEPS = [
    ("fig5", "h2", (0.55, 0.80), (2.0, 5.0, 8.0, 12.0)),
    ("fig7", "h6", (0.93, 0.99), (0.05, 0.1, 0.5, 1.0)),
]

#: each app at its loosest accuracy and 8 ms (alpha None), then every
#: (alpha, delta) point of the Fig. 5 and Fig. 7 sweeps
OPT_CASES = (
    [pytest.param(app, None, 8.0, id=app)
     for app in ("h1", "h2", "h3", "h4", "h5", "h6")]
    + [pytest.param(app, alpha, delta_ms,
                    id=f"{fig}-{app}-a{alpha}-d{delta_ms}ms")
       for fig, app, alphas, deltas in FIG5_7_SWEEPS
       for alpha in alphas for delta_ms in deltas])


@pytest.mark.parametrize("app,alpha,delta_ms", OPT_CASES)
def test_fin_matches_opt_on_paper_apps(scenario, app, alpha, delta_ms):
    """Sec. V: 'FIN virtually always matches the optimum' (gamma=10):
    within the 1 + 1/gamma competitive ratio of Opt."""
    prof = paper_profile(app)
    if alpha is None:
        alpha = min(e.accuracy for e in prof.exits)  # always satisfiable
    req = AppRequirements(alpha=alpha, delta=delta_ms * 1e-3)
    fin = solve_fin(scenario, prof, req, gamma=10)
    opt = solve_opt(scenario, prof, req)
    assert opt.feasible
    assert fin.feasible
    assert fin.energy <= opt.energy * (1 + 1.0 / 10) + 1e-15


def test_fin_solution_is_feasible_by_construction(scenario):
    prof = paper_profile("h2")
    for delta in (2e-3, 5e-3, 12e-3):
        for alpha in (0.5, 0.8):
            sol = solve_fin(scenario, prof, AppRequirements(alpha, delta), gamma=10)
            if sol.found:
                assert sol.feasible, sol.eval.violations


def test_fin_infeasible_alpha_returns_none(scenario):
    prof = paper_profile("h2")  # best exit accuracy 0.8595
    sol = solve_fin(scenario, prof, AppRequirements(alpha=0.95, delta=1.0))
    assert not sol.found
    assert "3c" in sol.meta["reason"] or "alpha" in sol.meta["reason"]


def test_fin_tight_latency_forces_split_or_fast_tier(scenario):
    """Fig. 5: small delta forces offloading; large delta keeps mobile-only."""
    prof = paper_profile("h2")
    tight = solve_fin(scenario, prof, AppRequirements(0.80, 2e-3), gamma=10)
    loose = solve_fin(scenario, prof, AppRequirements(0.80, 12e-3), gamma=10)
    assert tight.feasible and loose.feasible
    assert loose.config.placement == [0] * 5       # all on mobile
    assert any(p != 0 for p in tight.config.placement)
    assert loose.energy <= tight.energy            # energy-latency trade-off


def test_fin_energy_monotone_in_delta(scenario):
    """Looser latency targets can only reduce (or keep) the optimal energy."""
    prof = paper_profile("h1")
    req_alpha = 0.54
    prev = np.inf
    for delta in (1.5e-3, 3e-3, 6e-3, 12e-3, 24e-3):
        sol = solve_fin(scenario, prof, AppRequirements(req_alpha, delta), gamma=16)
        if sol.feasible:
            assert sol.energy <= prev * (1 + 1.0 / 16) + 1e-15
            prev = min(prev, sol.energy)


def test_gamma_refines_solution_quality(scenario):
    """Property 2: competitive ratio 1 + 1/gamma for adequate resolution.

    The bound holds for gamma >= 10 (the paper's working point).  At gamma=3
    depth-state collisions of the scaled quantizer can lose the optimal path
    — the paper itself observes gamma=3 'deteriorates significantly' on the
    communication term (Fig. 6); we only require feasibility there.
    """
    prof = paper_profile("h2")
    req = AppRequirements(0.80, 4e-3)
    opt = solve_opt(scenario, prof, req)
    assert opt.feasible
    energies = {}
    for gamma in (3, 10, 40):
        sol = solve_fin(scenario, prof, req, gamma=gamma)
        assert sol.feasible
        energies[gamma] = sol.energy
        if gamma >= 10:
            assert sol.energy <= opt.energy * (1 + 1.0 / gamma) + 1e-15
    assert energies[40] <= energies[10] + 1e-15  # refinement is monotone here


def test_lambda_proximity_restriction(scenario):
    """lam=gamma is exhaustive; small lam is a heuristic that may only prune."""
    prof = paper_profile("h2")
    req = AppRequirements(0.80, 5e-3)
    full = solve_fin(scenario, prof, req, gamma=10, lam=10)
    assert full.feasible
    pruned = solve_fin(scenario, prof, req, gamma=10, lam=3)
    if pruned.feasible:
        assert pruned.energy >= full.energy - 1e-15


def test_feasible_graph_counts(scenario):
    prof = paper_profile("h2")
    req = AppRequirements(0.80, 5e-3)
    ext = build_extended_graph(scenario, prof, req)
    fg = build_feasible_graph(ext, gamma=10)
    assert fg.n_states == scenario.n_nodes * 11
    assert fg.n_vertices == prof.n_blocks * fg.n_states + 1
    assert fg.n_edges > 0
    # gamma replication: more resolution => at least as many edges
    fg2 = build_feasible_graph(ext, gamma=20)
    assert fg2.n_edges >= fg.n_edges


def test_quantize_ceil_guarantees_latency(scenario):
    """ceil quantization: any returned path meets (3b) without tightening."""
    prof = paper_profile("h5")  # 3 blocks — fits in gamma=8 even with ceil
    req = AppRequirements(0.90, 1e-3)
    sol = solve_fin(scenario, prof, req, gamma=8, quantize="ceil", max_tighten=0)
    if sol.found:
        assert sol.eval.latency <= req.delta + 1e-12


def test_fault_tolerance_replacement(scenario):
    """Node failure: re-solve on the reduced network (DESIGN.md Sec. 5)."""
    prof = paper_profile("h2")
    req = AppRequirements(0.80, 2e-3)
    sol = solve_fin(scenario, prof, req, gamma=10)
    assert sol.feasible
    used = {p for p in sol.config.placement if p != 0}
    if used:
        failed = used.pop()
        reduced = scenario.without_node(failed)
        sol2 = solve_fin(reduced, prof, req, gamma=10)
        if sol2.found:
            assert sol2.feasible
            assert sol2.energy >= sol.energy - 1e-15  # fewer options can't win


def test_evaluate_config_violations(scenario):
    prof = paper_profile("h2")
    req = AppRequirements(alpha=0.99, delta=1e-6)
    cfg = Config(placement=[0] * 5, final_exit=2)
    ev = evaluate_config(scenario, prof, req, cfg)
    assert not ev.feasible
    kinds = " ".join(ev.violations)
    assert "(3b)" in kinds and "(3c)" in kinds


def test_energy_decomposition_consistency(scenario):
    prof = paper_profile("h2")
    req = AppRequirements(0.80, 5e-3)
    sol = solve_fin(scenario, prof, req, gamma=10)
    ev = sol.eval
    assert ev.energy == pytest.approx(ev.energy_comp + ev.energy_comm)
    assert ev.energy_comp > 0


def test_steiner_like_instance():
    """Property 1 flavor: a hub-constrained instance — only one node can run
    the block; the solver must route through it or fail."""
    nw = make_network(("mobile", "edge", "cloud"),
                      compute_frac=(1e-9, 1.0, 1e-9))
    prof = synthetic_profile(1, 1, seed=3)
    req = AppRequirements(alpha=0.0, delta=10.0, sigma=1e-9)
    sol = solve_fin(nw, prof, req, gamma=10)
    assert sol.feasible
    assert sol.config.placement == [1]


def test_k_best_dp_fixes_small_gamma_collisions(scenario):
    """Beyond-paper: keeping the k cheapest paths per (node, depth) state
    restores optimality at gamma=3, where the 1-best DP provably loses the
    optimal path to a quantizer state collision (EXPERIMENTS §Perf)."""
    prof = paper_profile("h2")
    req = AppRequirements(0.80, 4e-3)
    opt = solve_opt(scenario, prof, req)
    one = solve_fin(scenario, prof, req, gamma=3, n_best=1)
    four = solve_fin(scenario, prof, req, gamma=3, n_best=4)
    assert opt.feasible and one.feasible and four.feasible
    assert four.energy <= one.energy + 1e-15
    assert four.energy <= opt.energy * (1 + 1.0 / 3) + 1e-15
    # on this instance k-best recovers the exact optimum
    assert four.energy == pytest.approx(opt.energy)
