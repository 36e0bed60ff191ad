"""Churn orchestrator: hysteresis, failures, mobility, migration accounting."""
import numpy as np
import pytest

from repro.core import (ChurnEvent, ChurnOrchestrator, Population,
                        churn_trace, population_cohorts, population_plans,
                        solve_fin)


def _same(a, b):
    if a.found != b.found:
        return False
    if not a.found:
        return True
    return (a.config.placement == b.config.placement
            and a.config.final_exit == b.config.final_exit
            and a.energy == b.energy)


def test_churn_trace_structure_and_determinism():
    t1 = churn_trace(6, 10, seed=3, p_fail=0.3, p_recover=0.5,
                     fail_nodes=(1,), p_move=0.3, n_edge=3)
    t2 = churn_trace(6, 10, seed=3, p_fail=0.3, p_recover=0.5,
                     fail_nodes=(1,), p_move=0.3, n_edge=3)
    assert t1 == t2
    assert len(t1) == 10
    kinds = {ev.kind for tick in t1 for ev in tick}
    assert "uplink" in kinds
    for tick in t1:
        ups = [ev for ev in tick if ev.kind == "uplink"]
        assert len(ups) == 6                      # one channel draw per user
        assert all(0.3 <= ev.value <= 1.0 for ev in ups)
    # fail/recover alternate consistently per node
    state = False
    for tick in t1:
        for ev in tick:
            if ev.kind == "fail":
                assert not state
                state = True
            elif ev.kind == "recover":
                assert state
                state = False


def test_hysteresis_holds_on_benign_fades():
    """Small fades that keep the incumbent feasible must not re-place."""
    plans = population_plans(12, n_extra_edge=2)
    orch = ChurnOrchestrator(plans, hysteresis=0.05)
    stats = orch.run(churn_trace(12, 8, seed=1, sigma=0.02))
    assert stats.total("n_dirty") == 12 * 8
    assert stats.total("n_held") > 0
    assert stats.resolve_rate < 0.5
    assert stats.total("n_failed") == 0


def test_failure_of_used_node_forces_resolve_and_migration():
    plans = population_plans(6, n_extra_edge=2)
    orch = ChurnOrchestrator(plans, hysteresis=0.05)
    # drive everyone into the cloud-heavy regime, then fail the cloud
    orch.step([ChurnEvent("uplink", u, 0.3) for u in range(6)])
    used = {n for p in plans if p.solution.feasible
            for n in p.solution.config.placement}
    victim = max(used)
    assert victim != 0
    rep = orch.step([ChurnEvent("fail", None, victim)])
    assert rep.n_resolved > 0
    for p in plans:
        if p.solution.feasible:
            assert victim not in p.solution.config.placement
    assert rep.n_migrations > 0 and rep.blocks_moved > 0
    assert rep.migration_bits > 0
    rep2 = orch.step([ChurnEvent("recover", None, victim)])
    assert victim not in plans[0].masked_nodes


def test_always_resolve_matches_cold_solver_per_tick():
    """AC: per-tick configurations bit-exact vs cold solve_fin."""
    plans = population_plans(8, n_extra_edge=2)
    orch = ChurnOrchestrator(plans, always_resolve=True)
    trace = churn_trace(8, 4, seed=4, q_mean=0.5, sigma=0.15,
                        p_move=0.25, n_edge=3)
    for events in trace:
        orch.step(events)
        for p in plans:
            assert _same(p.solution,
                         solve_fin(p.network, p.profile, p.req))


def test_slice_event_applies_to_all_users():
    """A global slice cut marks everyone dirty and lands on every plan;
    each user either re-solves or provably keeps a feasible incumbent."""
    plans = population_plans(4, n_extra_edge=1)
    orch = ChurnOrchestrator(plans, hysteresis=0.01)
    rep = orch.step([ChurnEvent("slice", None, 0.25)])
    assert rep.n_dirty == 4
    assert rep.n_resolved + rep.n_held + rep.n_failed == 4
    for p in plans:
        assert p.stats.slice_updates == 1
        assert np.allclose(p.network.compute,
                           0.25 * p._compute_base)
        if p.solution.feasible:
            assert p.evaluate(p.solution.config).feasible


def test_run_is_deterministic():
    a = ChurnOrchestrator(population_plans(6), hysteresis=0.1).run(
        churn_trace(6, 6, seed=9, sigma=0.15))
    b = ChurnOrchestrator(population_plans(6), hysteresis=0.1).run(
        churn_trace(6, 6, seed=9, sigma=0.15))
    assert [t.energy for t in a.ticks] == [t.energy for t in b.ticks]
    assert a.total("n_resolved") == b.total("n_resolved")


def test_population_plans_round_robin():
    plans = population_plans(13)
    names = [p.profile.name for p in plans]
    assert names[0] == names[6] and names[1] == names[7]
    assert len(set(names)) == 6


def test_unknown_event_kind_raises():
    plans = population_plans(2)
    orch = ChurnOrchestrator(plans)
    with pytest.raises(ValueError, match="kind"):
        orch.step([ChurnEvent("teleport", 0, 1.0)])


def test_attach_after_same_tick_event_still_refreshes_bandwidth():
    """An attach must reach the batched uplink refresh even when the user
    was already dirtied by an earlier event in the same tick."""
    plans = population_plans(4, n_extra_edge=2)
    orch = ChurnOrchestrator(plans)
    orch.step([ChurnEvent("slice", 0, 0.8), ChurnEvent("attach", 0, 1)])
    expect = orch._uplink_vector(0)
    got = plans[0].network.bandwidth[0].copy()
    got[0] = np.inf
    np.testing.assert_array_equal(got, expect)


def test_uplink_event_requires_user():
    """user=None broadcasts for fail/recover/slice but is invalid for the
    per-user channel events — it must raise, not corrupt every user's
    quality via numpy None-indexing."""
    plans = population_plans(3)
    orch = ChurnOrchestrator(plans)
    before = orch.quality.copy()
    for kind in ("uplink", "attach"):
        with pytest.raises(ValueError, match="per-user"):
            orch.step([ChurnEvent(kind, None, 0.5)])
    np.testing.assert_array_equal(orch.quality, before)


def test_streamed_threshold_gate_matches_exact_ticks(monkeypatch):
    """A streamed ``run_arrays`` trace with outages, whose channel ticks
    gate every incumbent by its channel threshold, makes the decisions,
    energies and migrations of the same trace as event ticks, whose dense
    store sends every incumbent through the exact evaluator."""
    U, T = 240, 12
    rng = np.random.default_rng(21)
    qual = np.empty((T, U))
    q = rng.uniform(0.3, 1.0, U)
    for t in range(T):
        q = np.clip(0.55 + 0.9 * (q - 0.55) + rng.normal(0, 0.15, U),
                    0.05, 1.0)
        qual[t] = q
    att = np.zeros((T, U), dtype=np.int64)
    for t in range(1, T):
        move = rng.random(U) < 0.2
        att[t] = np.where(move, rng.integers(0, 3, U), att[t - 1])
    outages = {4: ("fail", 1), 8: ("recover", 1), 10: ("fail", 4)}
    judged = []
    judge = Population._gate_judge
    monkeypatch.setattr(Population, "_gate_judge", lambda self, *a: (
        judged.append(self.U), judge(self, *a))[1])

    def orch():
        return ChurnOrchestrator(
            population=population_cohorts(U, n_extra_edge=2),
            hysteresis=0.05, detach_frac=0.3)

    streamed, exact = orch(), orch()
    reps_s, reps_e = [], []
    bounds = [0, 4, 8, 10, T]
    for a, b in zip(bounds, bounds[1:]):
        if a in outages:
            kind, node = outages[a]
            for o, reps in ((streamed, reps_s), (exact, reps_e)):
                reps.append(o.step([ChurnEvent(kind, None, node)]))
        reps_s += streamed.run_arrays(qual[a:b], att[a:b])
        for t in range(a, b):
            n_judged = len(judged)
            reps_e.append(exact.step(
                [ChurnEvent("uplink", u, float(qual[t, u]))
                 for u in range(U)]
                + [ChurnEvent("attach", u, int(att[t, u]))
                   for u in range(U)]))
            assert len(judged) == n_judged      # the exact path judged
    # every channel tick of the stream went through the threshold gate
    assert len(judged) == T * len(streamed.pops)
    assert len(reps_s) == len(reps_e) == T + len(outages)
    assert sum(r.n_resolved for r in reps_s) > 0
    for ra, rb in zip(reps_s, reps_e):
        for f in ("n_dirty", "n_resolved", "n_held", "n_failed",
                  "n_migrations", "blocks_moved"):
            assert getattr(ra, f) == getattr(rb, f), (ra.tick, f)
        assert ra.energy == rb.energy, ra.tick
        assert ra.migration_bits == rb.migration_bits, ra.tick
    np.testing.assert_array_equal(streamed._cur_energy, exact._cur_energy)
    np.testing.assert_array_equal(streamed._ref_energy, exact._ref_energy)
    for p1, p2 in zip(streamed.pops, exact.pops):
        np.testing.assert_array_equal(p1._inc_exit, p2._inc_exit)
        np.testing.assert_array_equal(p1._inc_place, p2._inc_place)
