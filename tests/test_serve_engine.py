"""Split-serving engine tests: continuous batching, gating, FIN integration."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import AppRequirements, paper_profile
from repro.core.contingency import NoFeasiblePlacement
from repro.core.scenarios import churn_trace, paper_scenario
from repro.kernels.ee_gate.ops import ee_gate
from repro.models import transformer as T
from repro.runtime.serve_engine import (SplitServeEngine, gate_heads,
                                        serve_with_churn)


@pytest.fixture(scope="module")
def setup():
    cfg = get("qwen3-4b", reduced=True)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_engine_serves_all_requests(setup):
    cfg, params = setup
    eng = SplitServeEngine(cfg, params, batch_size=4, cache_len=64)
    reqs = [eng.submit([1, 2, 3], max_new_tokens=5) for _ in range(10)]
    stats = eng.run(max_steps=200)
    assert all(r.done for r in reqs)
    assert stats.tokens_out == 10 * 5
    assert all(len(r.tokens) == 5 for r in reqs)


def test_continuous_batching_beats_sequential_steps(setup):
    """10 requests on 4 slots must take far fewer steps than 10 sequential
    prompts (slots are refilled as soon as a sequence finishes)."""
    cfg, params = setup
    eng = SplitServeEngine(cfg, params, batch_size=4, cache_len=128)
    for _ in range(10):
        eng.submit([1, 2, 3], max_new_tokens=4)
    stats = eng.run(max_steps=400)
    sequential_steps = 10 * (3 + 4)
    assert stats.steps < sequential_steps


def test_exit_thresholds_control_depth(setup):
    cfg, params = setup
    # threshold 0: everything exits at the first exit
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=32,
                           thresholds=[0.0, 0.0])
    eng.submit([1, 2], max_new_tokens=4)
    stats = eng.run(max_steps=50)
    assert set(stats.exit_histogram) == {0}
    # threshold > 1: nothing exits early
    eng2 = SplitServeEngine(cfg, params, batch_size=2, cache_len=32,
                            thresholds=[1.1, 1.1])
    eng2.submit([1, 2], max_new_tokens=4)
    stats2 = eng2.run(max_steps=50)
    assert set(stats2.exit_histogram) == {eng2.n_exits - 1}


def _host_gate(heads, thresholds):
    """The engine's former gate: ``ee_gate`` on each head, both results
    read to the host, first-exit-wins in numpy (the oracle)."""
    confs, args = [], []
    for x in heads:
        c, a = ee_gate(x)
        confs.append(np.asarray(c))
        args.append(np.asarray(a))
    k = len(heads) - 1
    exit_idx = np.full(len(args[0]), k)
    for j in reversed(range(k)):
        exit_idx = np.where(confs[j] >= thresholds[j], j, exit_idx)
    return np.choose(exit_idx, args), exit_idx


def _gate_logits(n_heads, rows, V=3000, seed=0):
    """Heads whose rows run from flat to sharply peaked, so that their
    confidences spread over (0, 1]."""
    rng = np.random.default_rng(seed)
    temp = np.geomspace(0.05, 40.0, rows)
    return [jnp.asarray(rng.standard_normal((rows, V))
                        * rng.permutation(temp)[:, None], jnp.float32)
            for _ in range(n_heads)]


@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("n_heads", [1, 2, 3])
@pytest.mark.parametrize("kind", ["zero", "above_one", "mixed"])
def test_device_gate_equals_host_gate(n_heads, rows, kind):
    """``gate_heads`` gives exactly the host gate's token and exit index.
    The mixed thresholds sit on row confidences: one equal to a float32
    confidence, one a Python float that rounds to one, so that some rows
    clear an early exit, some tie it exactly in float32 and the rest fall
    through to the last head."""
    heads = _gate_logits(n_heads, rows, seed=10 * n_heads + rows)
    if kind == "zero":
        thr = [0.0, 0.0]
    elif kind == "above_one":
        thr = [1.1, 1.1]
    else:
        thr = []
        for j, x in enumerate(heads[:2]):
            c = np.sort(np.asarray(ee_gate(x)[0]))
            t = float(c[len(c) // 2])
            if j == 1:      # above the float32 value, within half an ulp
                t += float(np.spacing(np.float32(t))) / 4
                assert t > float(c[len(c) // 2])
            thr.append(t)
    tok, idx = _host_gate(heads, thr)
    got = np.asarray(gate_heads(tuple(heads), jnp.asarray(thr, jnp.float32)))
    assert got.dtype == np.int32 and got.shape == (2, rows)
    np.testing.assert_array_equal(got[0], tok)
    np.testing.assert_array_equal(got[1], idx)
    if kind == "mixed" and rows > 1 and n_heads > 1:
        c0 = np.asarray(ee_gate(heads[0])[0])
        tie = c0 == np.float32(thr[0])
        assert (c0 > thr[0]).any() and tie.any() and (idx[tie] == 0).all()
        assert set(idx.tolist()) == set(range(n_heads))


def test_fin_placement_energy_accounting(setup):
    cfg, params = setup
    nw = paper_scenario()
    prof = paper_profile("h2")
    req = AppRequirements(alpha=0.5, delta=8e-3)
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           thresholds=[0.0, 0.0], network=nw, profile=prof,
                           req=req)
    assert eng.placement is not None
    eng.submit([1, 2], max_new_tokens=6)
    stats = eng.run(max_steps=100)
    assert stats.energy_j > 0
    assert stats.blocks_saved > 0           # exit-0 skips deep blocks
    assert stats.blocks_executed > 0
    # early exits save work: executed < total blocks x tokens
    total = prof.n_blocks * stats.tokens_out
    assert stats.blocks_executed < total


def test_failure_triggers_replacement(setup):
    cfg, params = setup
    nw = paper_scenario()
    prof = paper_profile("h2")
    req = AppRequirements(alpha=0.5, delta=8e-3)
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           network=nw, profile=prof, req=req)
    before = list(eng.placement.placement)
    used = {p for p in before if p != nw.source_node}
    victim = used.pop() if used else 1
    eng.fail_node(victim)
    assert eng.stats.replacements == 1
    eng.submit([1], max_new_tokens=2)
    stats = eng.run(max_steps=50)
    assert stats.tokens_out == 2


def test_fail_node_avoids_dead_node_and_matches_cold_solve(setup):
    """Post-failure placement avoids the dead node, stats keep
    accumulating across the failure, and the warm re-solve equals a cold
    solve on the reduced network (energies bit-equal, placements equal
    modulo the index remap)."""
    import numpy as np

    from repro.core import Network, solve_fin

    cfg, params = setup
    nw = paper_scenario(n_extra_edge=1)
    prof = paper_profile("h2")
    req = AppRequirements(alpha=0.5, delta=8e-3)
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           thresholds=[0.0, 0.0], network=nw, profile=prof,
                           req=req)
    eng.submit([1, 2], max_new_tokens=3)
    pre = eng.run(max_steps=40)
    tokens_before, energy_before = pre.tokens_out, pre.energy_j
    assert tokens_before > 0 and energy_before > 0

    victim = 1 if 1 != eng.plan.network.source_node else 2
    eng.fail_node(victim)
    # placement avoids the dead node; node indexing is unchanged
    assert victim not in eng.placement.placement
    assert eng.network.n_nodes == nw.n_nodes

    # warm == cold on the reduced network
    keep = [i for i in range(nw.n_nodes) if i != victim]
    remap = {new: old for new, old in enumerate(keep)}
    full = eng.plan.network
    red = Network(nodes=[full.nodes[i] for i in keep],
                  bandwidth=full.bandwidth[np.ix_(keep, keep)].copy(),
                  compute=full.compute[keep].copy(), source_node=0)
    cold = solve_fin(red, prof, req)
    assert cold.feasible
    warm = eng.plan.solution
    assert warm.energy == cold.energy
    assert warm.config.placement == [remap[p] for p in cold.config.placement]

    # serving continues and stats accumulate past the failure
    eng.submit([1, 2], max_new_tokens=3)
    post = eng.run(max_steps=40)
    assert post.tokens_out > tokens_before
    assert post.energy_j > energy_before
    assert post.replacements == 1

    # recovery re-solves again (back to the full network's optimum)
    eng.recover_node(victim)
    assert post.replacements == 2
    ref = solve_fin(full, prof, req)
    assert eng.plan.solution.energy == ref.energy


def test_failover_exposes_frontier_and_migration_aware_resplit(setup):
    """Every failover re-split refreshes ``engine.frontier`` (the scenario's
    Pareto rows, argmin == the plan's solve), and with a heavy
    ``migration_weight`` a recovery keeps the current placement instead of
    migrating every block back for a marginal energy win."""
    from repro.core.multiapp import PAPER_MULTIAPP_REQS

    cfg, params = setup
    nw = paper_scenario(n_extra_edge=1)
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]

    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           network=nw, profile=prof, req=req)
    assert eng.frontier is not None and len(eng.frontier) >= 1
    assert eng.frontier.argmin.config.placement == eng.placement.placement

    # channel regime that places off-mobile (the failure-bench setup)
    eng.plan.update_uplink(0.3e9)
    eng._replace()
    assert eng.frontier.argmin.config.placement == eng.placement.placement
    victim = next(p for p in eng.placement.placement
                  if p != nw.source_node)
    eng.fail_node(victim)
    assert victim not in eng.placement.placement
    assert all(victim not in r.config.placement for r in eng.frontier)
    assert eng.frontier.argmin.config.placement == eng.placement.placement
    post_fail = list(eng.placement.placement)
    eng.recover_node(victim)
    argmin_back = list(eng.placement.placement)

    # heavy migration weight: the recovery re-split keeps the incumbent
    eng2 = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                            network=nw, profile=prof, req=req,
                            migration_weight=1.0)
    eng2.plan.update_uplink(0.3e9)
    eng2._replace()
    victim2 = next(p for p in eng2.placement.placement
                   if p != nw.source_node)
    eng2.fail_node(victim2)
    bits_after_fail = eng2.stats.migration_bits
    kept = list(eng2.placement.placement)
    eng2.recover_node(victim2)
    assert eng2.placement.placement == kept       # no migrate-back
    assert eng2.stats.migration_bits == bits_after_fail
    assert argmin_back != post_fail or kept == argmin_back


def test_measured_phi_feeds_placement(setup):
    """measured_phi from the gates is a valid phi vector for core.DNNProfile."""
    cfg, params = setup
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           thresholds=[0.5, 0.5])
    eng.submit(list(range(1, 5)), max_new_tokens=8)
    stats = eng.run(max_steps=100)
    phi = stats.measured_phi
    assert abs(sum(phi.values()) - 1.0) < 1e-9


# ---------------------------------------------------------------------------
# Contingency library: O(1) failover and graceful degradation (PR 7)
# ---------------------------------------------------------------------------

def _placed_engine(setup, **kw):
    """Engine in the off-mobile channel regime (the failover-bench setup),
    with a freshly keyed contingency library."""
    from repro.core.multiapp import PAPER_MULTIAPP_REQS

    cfg, params = setup
    nw = paper_scenario(n_extra_edge=1)
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           network=nw, profile=paper_profile("h1"),
                           req=PAPER_MULTIAPP_REQS["h1"], **kw)
    eng.plan.update_uplink(0.3e9)
    eng._replace()
    if eng.contingency is not None:
        eng.refresh_contingency()
    return eng, nw


def _weak_source_engine(setup, **kw):
    """Engine whose source node cannot serve alone: masking every helper
    makes the placement infeasible (the graceful-degradation regime)."""
    cfg, params = setup
    nw = paper_scenario(n_extra_edge=1)
    nw.compute[nw.source_node] *= 1e-3
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           network=nw, profile=paper_profile("h2"),
                           req=AppRequirements(alpha=0.5, delta=8e-3), **kw)
    return eng, nw


def test_simultaneous_multi_node_failure_is_one_o1_hit(setup):
    """A joint tier outage (``fail_nodes``) is ONE library lookup: zero DP
    relaxations, and placement + migration accounting bit-exact vs the
    warm re-solve of a contingency-free twin."""
    eng, nw = _placed_engine(setup)
    twin, _ = _placed_engine(setup, contingency=False)

    r0 = eng.plan.stats.dp_relaxes
    eng.fail_nodes([1, 2])
    assert eng.plan.stats.dp_relaxes == r0       # solve-free failover
    assert eng.stats.contingency_hits == 1
    assert eng.stats.contingency_misses == 0
    twin.fail_nodes([1, 2])
    assert eng.placement == twin.placement
    assert 1 not in eng.placement.placement
    assert 2 not in eng.placement.placement
    assert eng.plan.solution.energy == twin.plan.solution.energy
    assert eng.stats.replacements == twin.stats.replacements
    assert eng.stats.blocks_migrated == twin.stats.blocks_migrated
    assert eng.stats.migration_bits == twin.stats.migration_bits


def test_failure_during_recovery_chain_stays_covered(setup):
    """A second failure landing before the first recovered, then staggered
    recoveries: every step of the compound chain is covered (single-node
    toggles + the tier joint mask) WITHOUT an intermediate refill, stays
    solve-free, and tracks the warm twin bit-exactly."""
    eng, nw = _placed_engine(setup)
    twin, _ = _placed_engine(setup, contingency=False)

    r0 = eng.plan.stats.dp_relaxes
    for op in ("fail", "fail2", "recover", "recover2"):
        if op == "fail":
            eng.fail_node(1); twin.fail_node(1)
        elif op == "fail2":                  # failure during node 1's outage
            eng.fail_node(2); twin.fail_node(2)
        elif op == "recover":                # recovery while node 2 is down
            eng.recover_node(1); twin.recover_node(1)
        else:
            eng.recover_node(2); twin.recover_node(2)
        assert eng.placement == twin.placement, op
        assert eng.plan.solution.energy == twin.plan.solution.energy, op
        assert eng.stats.blocks_migrated == twin.stats.blocks_migrated, op
        assert eng.stats.migration_bits == twin.stats.migration_bits, op
    # {1} and {2,} toggles, the {1,2} tier mask and the all-clear base
    # mask are all library candidates: the whole chain was O(1)
    assert eng.plan.stats.dp_relaxes == r0
    assert eng.stats.contingency_hits == 4
    assert eng.stats.contingency_misses == 0
    assert eng.stats.replacements == twin.stats.replacements


def test_final_exit_host_failure(setup):
    """Failure of the node hosting the final exit: the library hit moves
    the deepest block (and its exit) bit-exactly like the warm re-solve,
    and serving continues across the failover."""
    eng, nw = _placed_engine(setup)
    twin, _ = _placed_engine(setup, contingency=False)
    host = eng.placement.placement[-1]       # final-exit-hosting node
    assert host != nw.source_node

    r0 = eng.plan.stats.dp_relaxes
    eng.fail_node(host)
    assert eng.plan.stats.dp_relaxes == r0
    assert eng.stats.contingency_hits == 1
    twin.fail_node(host)
    assert eng.placement == twin.placement
    assert host not in eng.placement.placement
    assert eng.placement.final_exit == twin.placement.final_exit
    assert eng.stats.blocks_migrated == twin.stats.blocks_migrated
    assert eng.stats.migration_bits == twin.stats.migration_bits

    eng.submit([1, 2], max_new_tokens=3)
    stats = eng.run(max_steps=40)
    assert stats.tokens_out == 3


def test_on_infeasible_pause_parks_and_recovery_resumes(setup):
    """``on_infeasible="pause"``: an unsurvivable outage parks serving
    (steps are no-ops, run() returns) with the EngineStats recording the
    pause; a recovery restores feasibility and serving resumes."""
    eng, nw = _weak_source_engine(setup, on_infeasible="pause")
    eng.submit([1, 2], max_new_tokens=3)
    eng.fail_nodes([1, 2, 3])                # nothing left to offload to
    assert eng.paused
    assert eng.stats.paused_events == 1
    steps0 = eng.stats.steps
    eng.step()
    assert eng.stats.steps == steps0          # parked: step is a no-op
    eng.run(max_steps=10)
    assert eng.stats.steps == steps0

    eng.recover_node(3)
    assert not eng.paused
    stats = eng.run(max_steps=40)
    assert stats.tokens_out == 3
    assert 3 in eng.placement.placement or \
        eng.placement.placement == [nw.source_node]


def test_on_infeasible_degrade_uses_last_feasible_frontier(setup):
    """``on_infeasible="degrade"``: when the channel collapses below any
    feasible placement, the engine deploys the cheapest row of the LAST
    feasible frontier (best-effort serving) instead of dying; when every
    historical row routes through a dead node it falls back to pausing."""
    from repro.core.scenarios import ChurnEvent

    eng, nw = _weak_source_engine(setup, on_infeasible="degrade")
    row0 = eng.frontier.argmin
    # channel collapse: no placement is feasible at 0.1x uplink
    rep = eng.on_tick([ChurnEvent("uplink", 0, 0.1)])
    assert rep["resplit"] and not rep["held"]
    assert eng.degraded and not eng.paused
    assert eng.stats.degrades == 1
    assert eng.placement == row0.config       # cheapest historical row
    # now the degraded host dies too — every historical row uses it
    eng.fail_node(eng.placement.placement[-1])
    assert eng.paused
    assert eng.stats.paused_events == 1


def test_on_infeasible_raise_carries_masked_set_and_frontier(setup):
    """Default policy: a typed ``NoFeasiblePlacement`` carrying the masked
    node set and the last feasible frontier (not a bare RuntimeError)."""
    eng, nw = _weak_source_engine(setup)
    with pytest.raises(NoFeasiblePlacement) as ei:
        eng.fail_nodes([1, 2, 3])
    assert ei.value.masked_nodes == [1, 2, 3]
    assert ei.value.frontier is not None and len(ei.value.frontier) >= 1
    assert isinstance(ei.value, RuntimeError)   # backward compatible


def test_engine_failover_validation_errors(setup):
    """Satellite audit: explicit errors instead of asserts — RuntimeError
    without a plan, ValueError on bad node indices (both engine- and
    plan-level), and no partial mutation on a bad joint failure."""
    cfg, params = setup
    bare = SplitServeEngine(cfg, params, batch_size=2, cache_len=64)
    with pytest.raises(RuntimeError, match="no placement plan"):
        bare.fail_node(1)
    with pytest.raises(RuntimeError, match="no placement plan"):
        bare.recover_node(1)

    eng, nw = _placed_engine(setup)
    for bad in (-1, nw.n_nodes, 1.5, "1"):
        with pytest.raises(ValueError):
            eng.fail_node(bad)
        with pytest.raises(ValueError):
            eng.recover_node(bad)
    with pytest.raises(ValueError):
        eng.fail_node(nw.source_node)
    # a bad node anywhere in a joint failure mutates nothing
    with pytest.raises(ValueError):
        eng.fail_nodes([1, nw.n_nodes])
    assert not eng.plan._masked.any()
    # plan-level audit (same error contract)
    for bad in (-1, nw.n_nodes, 1.5):
        with pytest.raises(ValueError):
            eng.plan.mask_node(bad)
        with pytest.raises(ValueError):
            eng.plan.unmask_node(bad)
    with pytest.raises(ValueError):
        SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                         on_infeasible="retry")


def test_serve_with_churn_drives_engine_from_trace(setup):
    """End-to-end churn-driven serving: AR(1) fades re-split mid-serving
    behind the hysteresis band, failures/recoveries hit the library, and
    decode keeps producing tokens through it all."""
    eng, nw = _placed_engine(setup)
    eng.submit([1, 2, 3], max_new_tokens=10)
    trace = churn_trace(1, 12, seed=5, p_fail=0.3, p_recover=0.6,
                        fail_nodes=(1,))
    reports = serve_with_churn(eng, trace, steps_per_tick=2)
    assert len(reports) == 12
    n_fail = sum(r["n_fail"] for r in reports)
    n_rec = sum(r["n_recover"] for r in reports)
    hits = sum(r["contingency_hits"] for r in reports)
    misses = sum(r["contingency_misses"] for r in reports)
    assert n_fail > 0 and n_rec > 0
    # every topology event resolved through the library protocol
    assert hits + misses == n_fail + n_rec
    assert hits > 0                      # the refill loop keeps coverage
    assert sum(1 for r in reports if r["held"]) > 0   # hysteresis holds
    assert eng.stats.tokens_out > 0
    assert not eng.paused
