"""Population SoA engine: whole-cohort ticks bit-exact vs per-plan solves.

The defining invariant of the struct-of-arrays layer: after ANY sequence of
cohort deltas (channel draws — scalar or per-target — failures, recoveries,
slice rescales), ``Population.solve()`` returns exactly the configurations
and energies that per-user ``Plan.solve()`` calls produce on the same
mutated scenarios, and a population-mode ``ChurnOrchestrator`` makes
exactly the per-plan orchestrator's decisions tick by tick.
"""
import numpy as np
import pytest

from repro.core import (AppRequirements, ChurnEvent, ChurnOrchestrator, Plan,
                        Population, churn_trace, paper_profile,
                        population_cohorts, population_plans, solve_plans,
                        synthetic_profile, update_uplinks)
from repro.core.multiapp import PAPER_MULTIAPP_REQS
from repro.core.scenarios import paper_scenario

APPS = ("h1", "h2", "h3", "h4", "h5", "h6")


def _same(a, b):
    if a.found != b.found:
        return False
    if not a.found:
        return True
    return (a.config.placement == b.config.placement
            and a.config.final_exit == b.config.final_exit
            and a.energy == b.energy)


@pytest.fixture(scope="module")
def network():
    return paper_scenario(n_extra_edge=2)


def _assert_pop_equals_plans(pop, plans, ctx=""):
    sols = solve_plans(plans)
    psols = pop.solve()
    for u, (a, b) in enumerate(zip(psols, sols)):
        assert _same(a, b), (ctx, u, a, b)


# ---------------------------------------------------------------------------
# delta-sequence bit-exactness vs per-plan Plan.solve()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("app", ["h1", "h4", "h6"])
def test_channel_ticks_bitexact(network, app):
    prof = paper_profile(app)
    req = PAPER_MULTIAPP_REQS[app]
    U = 6
    pop = Population(network, prof, req, U)
    plans = [Plan(network, prof, req) for _ in range(U)]
    _assert_pop_equals_plans(pop, plans, "cold")
    rng = np.random.default_rng(7)
    for t in range(8):
        q = rng.uniform(0.3, 1.0, U) * 1e9
        ch_pop = pop.ingest(q)
        ch_pl = update_uplinks(plans, q)
        assert list(ch_pop) == ch_pl, (app, t)
        _assert_pop_equals_plans(pop, plans, (app, t))


def test_per_target_vectors_and_masks_bitexact(network):
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    U = 5
    pop = Population(network, prof, req, U)
    plans = [Plan(network, prof, req) for _ in range(U)]
    rng = np.random.default_rng(3)
    for t in range(10):
        vec = rng.uniform(0.2, 1.0, (U, network.n_nodes)) * 1e9
        pop.ingest(vec)
        update_uplinks(plans, vec)
        if t == 2:          # cohort-wide failure
            pop.mask_node(4)
            for p in plans:
                p.mask_node(4)
        if t == 5:          # recovery
            pop.unmask_node(4)
            for p in plans:
                p.unmask_node(4)
        if t == 7:          # per-user failure
            pop.mask_node(2, users=[1])
            plans[1].mask_node(2)
        _assert_pop_equals_plans(pop, plans, t)


def test_slice_rescale_bitexact(network):
    prof = paper_profile("h2")
    req = PAPER_MULTIAPP_REQS["h2"]
    U = 4
    pop = Population(network, prof, req, U)
    plans = [Plan(network, prof, req) for _ in range(U)]
    rng = np.random.default_rng(9)
    for t, frac in enumerate((0.5, 0.25, 1.0)):
        q = rng.uniform(0.3, 1.0, U) * 1e9
        pop.ingest(q)
        update_uplinks(plans, q)
        pop.update_slice(frac)
        for p in plans:
            p.update_slice(frac)
        _assert_pop_equals_plans(pop, plans, (t, frac))


def test_lazy_ingest_same_solutions(network):
    """Deferred requantization must not change any solution."""
    prof = paper_profile("h3")
    req = PAPER_MULTIAPP_REQS["h3"]
    U = 5
    eager = Population(network, prof, req, U)
    lazy = Population(network, prof, req, U)
    rng = np.random.default_rng(4)
    for t in range(6):
        q = rng.uniform(0.3, 1.0, U) * 1e9
        eager.ingest(q)
        assert lazy.ingest(q, requant=False) is None
        a = eager.solve()
        b = lazy.solve()
        for u in range(U):
            assert _same(a[u], b[u]), (t, u)


# ---------------------------------------------------------------------------
# cross-user state dedupe
# ---------------------------------------------------------------------------

def test_identical_users_share_one_state_and_solve(network):
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    pop = Population(network, prof, req, 64)
    pop.solve()
    assert pop.n_states == 1
    assert pop.stats.dp_relaxes == 1
    assert pop.stats.unique_solves == 1          # same state AND same bw
    assert pop.stats.solves == 64
    # in-cell fades: same quantized cell -> no new relax, exact post-pass
    pop.ingest(np.full(64, 0.999e9))
    pop.solve()
    assert pop.stats.dp_relaxes <= 2


def test_state_cache_compaction(network):
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    pop = Population(network, prof, req, 8, max_states=4)
    rng = np.random.default_rng(1)
    for _ in range(8):
        pop.ingest(rng.uniform(0.2, 1.0, (8, network.n_nodes)) * 1e9)
        pop.solve()
    assert pop.stats.state_evictions > 0
    # every referenced state survived: solving again is cache-hits only
    relaxes = pop.stats.dp_relaxes
    pop.solve()
    assert pop.stats.dp_relaxes == relaxes


# ---------------------------------------------------------------------------
# ingest validation (satellite: clear errors for malformed bps)
# ---------------------------------------------------------------------------

def test_ingest_shape_validation(network):
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    pop = Population(network, prof, req, 4)
    N = network.n_nodes
    with pytest.raises(ValueError, match="leading dimension"):
        pop.ingest(np.ones(3) * 1e9)             # (U-1,)
    with pytest.raises(ValueError, match=r"\(4, \d+\)"):
        pop.ingest(np.ones((4, N + 1)) * 1e9)    # (U, N+1)
    with pytest.raises(ValueError, match="ndim"):
        pop.ingest(np.ones((4, N, 2)))           # 3-d
    with pytest.raises(ValueError, match="leading dimension"):
        pop.ingest(np.ones((N, N)) * 1e9, users=np.array([0, 1]))


def test_update_uplinks_shape_validation(network):
    plans = [Plan(network, paper_profile("h1"), PAPER_MULTIAPP_REQS["h1"])
             for _ in range(4)]
    N = network.n_nodes
    with pytest.raises(ValueError, match="leading dimension"):
        update_uplinks(plans, np.ones(5) * 1e9)
    with pytest.raises(ValueError, match="node count"):
        update_uplinks(plans, np.ones((4, N + 2)) * 1e9)
    with pytest.raises(ValueError, match="ndim"):
        update_uplinks(plans, np.ones((4, N, 2)))
    # mixed node counts cannot take one (U, N) matrix
    small = paper_scenario()
    mixed = plans[:2] + [Plan(small, paper_profile("h1"),
                              PAPER_MULTIAPP_REQS["h1"])]
    with pytest.raises(ValueError, match="node count"):
        update_uplinks(mixed, np.ones((3, N)) * 1e9)


def test_population_constructor_validation(network):
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    with pytest.raises(ValueError, match="backend"):
        Population(network, prof, req, 2, backend="cuda")
    with pytest.raises(ValueError, match="dense"):
        Population(network, prof, req, 2, backend="dense")
    with pytest.raises(ValueError, match="n_users"):
        Population(network, prof, req, 0)
    with pytest.raises(ValueError, match="source"):
        Population(network, prof, req, 2).mask_node(network.source_node)


# ---------------------------------------------------------------------------
# orchestrator population mode
# ---------------------------------------------------------------------------

def _compare_orchestrators(oa, ob, trace):
    for t, events in enumerate(trace):
        ra, rb = oa.step(events), ob.step(events)
        for f in ("n_events", "n_uplink_updates", "n_quant_changed",
                  "n_dirty", "n_resolved", "n_held", "n_failed",
                  "n_migrations", "blocks_moved"):
            assert getattr(ra, f) == getattr(rb, f), (t, f, ra, rb)
        assert ra.migration_bits == rb.migration_bits, t
        assert ra.energy == rb.energy, t
        np.testing.assert_array_equal(oa._cur_energy, ob._cur_energy)
        np.testing.assert_array_equal(oa._ref_energy, ob._ref_energy)
        for u, p in enumerate(oa.plans):
            pi = ob._pop_of[u]
            loc = ob._local_of[u]
            pop = ob.pops[pi]
            found_a = p.solution is not None and p.solution.feasible
            assert found_a == bool(pop.inc_found[loc]), (t, u)
            if found_a:
                nb = len(p.solution.config.placement)
                assert list(pop._inc_place[loc][:nb]) \
                    == p.solution.config.placement, (t, u)
                assert pop._inc_exit[loc] == p.solution.config.final_exit


def test_orchestrator_population_mode_equivalence():
    U, T = 18, 6
    trace = churn_trace(U, T, seed=5, q_mean=0.5, sigma=0.15, p_fail=0.2,
                        p_recover=0.5, fail_nodes=(4,), p_move=0.15,
                        n_edge=3)
    trace[2].append(ChurnEvent("slice", None, 0.5))
    oa = ChurnOrchestrator(population_plans(U, n_extra_edge=2),
                           hysteresis=0.05)
    ob = ChurnOrchestrator(population=population_cohorts(U, n_extra_edge=2),
                           hysteresis=0.05)
    np.testing.assert_array_equal(oa._ref_energy, ob._ref_energy)
    _compare_orchestrators(oa, ob, trace)


def test_orchestrator_population_always_resolve():
    U, T = 12, 4
    trace = churn_trace(U, T, seed=7, sigma=0.15, p_move=0.25, n_edge=3)
    oa = ChurnOrchestrator(population_plans(U, n_extra_edge=2),
                           always_resolve=True)
    ob = ChurnOrchestrator(population=population_cohorts(U, n_extra_edge=2),
                           always_resolve=True)
    _compare_orchestrators(oa, ob, trace)


def test_step_arrays_equals_event_ticks():
    """The lazy array tick path makes the per-plan path's decisions."""
    U, T = 12, 5
    rng = np.random.default_rng(5)
    q = np.full(U, 0.6)
    oa = ChurnOrchestrator(population_plans(U, n_extra_edge=2),
                           hysteresis=0.05)
    ob = ChurnOrchestrator(population=population_cohorts(U, n_extra_edge=2),
                           hysteresis=0.05)
    for t in range(T):
        q = np.clip(0.65 + 0.95 * (q - 0.65) + rng.normal(0, 0.1, U),
                    0.3, 1.0)
        ra = oa.step([ChurnEvent("uplink", u, float(q[u]))
                      for u in range(U)])
        rb = ob.step_arrays(quality=q)
        for f in ("n_dirty", "n_resolved", "n_held", "n_failed",
                  "n_migrations", "blocks_moved"):
            assert getattr(ra, f) == getattr(rb, f), (t, f)
        assert ra.energy == rb.energy, t
        np.testing.assert_array_equal(oa._cur_energy, ob._cur_energy)


def test_population_mode_rejects_per_user_slice():
    ob = ChurnOrchestrator(population=population_cohorts(4, n_extra_edge=1))
    with pytest.raises(ValueError, match="per-user slice"):
        ob.step([ChurnEvent("slice", 1, 0.5)])


def test_orchestrator_arg_validation():
    with pytest.raises(ValueError, match="exactly one"):
        ChurnOrchestrator()
    plans = population_plans(2)
    pops = population_cohorts(2)
    with pytest.raises(ValueError, match="exactly one"):
        ChurnOrchestrator(plans, population=pops)
    with pytest.raises(ValueError, match="step_arrays requires"):
        ChurnOrchestrator(plans).step_arrays(quality=np.ones(2))


# ---------------------------------------------------------------------------
# f32 / mesh backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_population_f32_backends_agree(network, backend):
    prof = paper_profile("h2")
    req = PAPER_MULTIAPP_REQS["h2"]
    U = 4
    ref = Population(network, prof, req, U)
    pop = Population(network, prof, req, U, backend=backend)
    rng = np.random.default_rng(11)
    for t in range(3):
        q = rng.uniform(0.3, 1.0, U) * 1e9
        ref.ingest(q)
        pop.ingest(q)
        a = ref.solve()
        b = pop.solve()
        for u in range(U):
            assert _same(a[u], b[u]), (backend, t, u)


def test_population_mesh_backend_single_device(network):
    """Mesh backend must work on whatever devices exist (1 on plain CPU);
    ``tests/test_mesh_relaxer.py::test_population_mesh_backend_matches_minplus``
    runs the orchestrator on 4 host devices."""
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    ref = Population(network, prof, req, 3)
    pop = Population(network, prof, req, 3, backend="mesh")
    rng = np.random.default_rng(2)
    for t in range(2):
        q = rng.uniform(0.3, 1.0, 3) * 1e9
        ref.ingest(q)
        pop.ingest(q)
        a = ref.solve()
        b = pop.solve()
        for u in range(3):
            assert _same(a[u], b[u]), (t, u)


# ---------------------------------------------------------------------------
# randomized sweep (hypothesis when available, seeded loop otherwise)
# ---------------------------------------------------------------------------

def _random_population_run(seed: int, quantize: str, gamma: int) -> None:
    """Mixed-cohort churn: random profiles / requirements / topologies per
    cohort, random delta sequences, population vs per-plan bit-exact."""
    rng = np.random.default_rng(seed)
    n_cohorts = int(rng.integers(1, 3))
    cohorts = []
    for c in range(n_cohorts):
        n_blocks = int(rng.integers(2, 6))
        prof = synthetic_profile(n_blocks,
                                 min(n_blocks, int(rng.integers(1, 4))),
                                 seed=seed + c)
        nw = paper_scenario(n_extra_edge=int(rng.integers(0, 3)))
        alpha = float(rng.uniform(0.0, max(e.accuracy for e in prof.exits)))
        req = AppRequirements(alpha=alpha,
                              delta=float(rng.uniform(1e-3, 20e-3)))
        U = int(rng.integers(2, 5))
        pop = Population(nw, prof, req, U, gamma=gamma, quantize=quantize)
        plans = [Plan(nw, prof, req, gamma=gamma, quantize=quantize)
                 for _ in range(U)]
        cohorts.append((nw, pop, plans))
    for t in range(5):
        for nw, pop, plans in cohorts:
            U = len(plans)
            r = rng.random()
            if r < 0.55:
                q = rng.uniform(0.1, 1.2, U) * 1e9
                pop.ingest(q)
                update_uplinks(plans, q)
            elif r < 0.7:
                vec = rng.uniform(0.1, 1.2, (U, nw.n_nodes)) * 1e9
                pop.ingest(vec)
                update_uplinks(plans, vec)
            elif r < 0.85:
                frac = float(rng.uniform(0.3, 1.0))
                pop.update_slice(frac)
                for p in plans:
                    p.update_slice(frac)
            else:
                n = int(rng.integers(1, nw.n_nodes))
                if n in pop.masked_nodes:
                    pop.unmask_node(n)
                    for p in plans:
                        p.unmask_node(n)
                else:
                    pop.mask_node(n)
                    for p in plans:
                        p.mask_node(n)
            _assert_pop_equals_plans(pop, plans, (seed, t))


@pytest.mark.parametrize("quantize", ["floor", "ceil", "round"])
@pytest.mark.parametrize("gamma", [3, 10])
def test_random_population_sequences_bitexact(quantize, gamma):
    for seed in range(2):
        _random_population_run(2000 * gamma + seed, quantize, gamma)


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @given(seed=st.integers(0, 10_000),
           quantize=st.sampled_from(["floor", "ceil", "round"]),
           gamma=st.sampled_from([3, 10, 25]))
    @settings(max_examples=12, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def test_property_population_bitexact(seed, quantize, gamma):
        """Property form (AC): population ticks bit-exact vs per-plan
        Plan.solve across mixed cohorts, masked nodes and quantizers."""
        _random_population_run(seed, quantize, gamma)
except ImportError:          # pragma: no cover - hypothesis optional
    pass


def test_population_mesh_arg_requires_mesh_backend(network):
    from repro.sharding.population import population_mesh
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    with pytest.raises(ValueError, match="mesh= only applies"):
        Population(network, prof, req, 2, mesh=population_mesh(1))
    pop = Population(network, prof, req, 2, backend="mesh",
                     mesh=population_mesh(1))
    pop.solve()
    assert pop._mesh_relaxer.n_devices == 1


# ---------------------------------------------------------------------------
# threshold gate: evaluate_incumbents(None) over the lazy store is
# bit-identical to re-evaluating every incumbent
# ---------------------------------------------------------------------------

def _cfg(k, place):
    from repro.core.problem import Config
    return Config(placement=list(place), final_exit=k)


H1_LOCAL_CLOUD = _cfg(2, [0, 0, 0, 4, 4])   # one source link (to node 4)
H1_CLOUD = _cfg(2, [4, 4, 4, 4, 4])
H1_TWO_LINKS = _cfg(2, [1, 0, 2, 2, 2])     # source links to nodes 1 and 2
H1_EDGE1 = _cfg(2, [0, 0, 1, 1, 1])
H1_EDGE1_CLOUD = _cfg(2, [0, 0, 1, 4, 4])   # and the backhaul 1 -> 4
H2_LOCAL = _cfg(1, [0, 0, 0])               # feasible, no source link
H2_LOCAL_DEEP = _cfg(2, [0, 0, 0, 0, 0])    # infeasible, no source link
H2_CLOUD = _cfg(1, [4, 4, 4])
H2_SPLIT = _cfg(1, [0, 0, 4])


def _attach_factors(U, N, rng, detach_frac=0.3, edge=(1, 2, 3)):
    """``ChurnOrchestrator._fac_rows``' pattern: 1.0 on the attached edge
    node and the non-edge targets, ``detach_frac`` on the other edges."""
    fac = np.ones((U, N))
    att = np.asarray(edge)[rng.integers(0, len(edge), U)]
    for n in edge:
        fac[att != n, n] = detach_frac
    return fac


def _gate_population(network, app, U, cfgs, shares, rng):
    pop = Population(network, paper_profile(app), PAPER_MULTIAPP_REQS[app],
                     U)
    which = rng.choice(len(cfgs), size=U, p=shares)
    pop.set_incumbents(np.arange(U), [cfgs[w] for w in which],
                       [0.0] * U)
    return pop, which


def _threshold_scales(pop, which, cfgs, fac, rng):
    """Log-uniform scales around each row's threshold, and in every
    (configuration, factor row) group one row at the threshold and one at
    each neighbouring float64."""
    U = pop.U
    t = np.empty(U)
    for u in range(U):
        c = cfgs[which[u]]
        cols = pop._gate_cols(c.placement)
        t[u] = pop._gate_entry(c.final_exit, c.placement, cols, fac[u])[1]
    base = np.where(np.isfinite(t) & (t > 0), t, 1e9)
    sc = base * 2.0 ** rng.uniform(-2, 2, U)
    seen = {}
    for u in range(U):
        seen.setdefault((which[u], fac[u].tobytes()), []).append(u)
    edges = []
    for rows in seen.values():
        tu = t[rows[0]]
        if len(rows) >= 3 and np.isfinite(tu) and tu > 0:
            sc[rows[:3]] = (tu, np.nextafter(tu, -np.inf),
                            np.nextafter(tu, np.inf))
            edges.append(rows[:3])
    return sc, edges


def _assert_gate_bitexact(pop, sc, fac):
    pop.ingest_factors(sc, fac, requant=False)
    assert pop._bw_lazy is not None
    plan = pop._gate_plan()
    assert plan is not None and not plan[4], "rows left to the exact path"
    got = pop.evaluate_incumbents(None)
    want = pop._incumbents_exact(None)
    rows = pop.evaluate_incumbents(np.arange(pop.U))
    for name, a, b, c in zip(("no_inc", "feas", "energy"), got, want, rows):
        assert a.dtype == b.dtype and np.array_equal(a, b), name
        assert np.array_equal(a, c), name
    return got


def _gate_case(case, network, rng):
    N = network.n_nodes
    if case == "scales":
        cfgs = [H1_LOCAL_CLOUD, H1_CLOUD]
        pop, which = _gate_population(network, "h1", 2000, cfgs,
                                      [0.7, 0.3], rng)
        return pop, which, cfgs, np.ones((2000, N))
    if case == "all_local":
        cfgs = [H2_LOCAL, H2_LOCAL_DEEP]
        pop, which = _gate_population(network, "h2", 2000, cfgs,
                                      [0.9, 0.1], rng)
        return pop, which, cfgs, np.ones((2000, N))
    if case == "uniform_all_local":
        cfgs = [H2_LOCAL]
        pop, which = _gate_population(network, "h2", 2000, cfgs, [1.0], rng)
        pop._inc_single = pop._recompute_inc_single()
        assert pop._inc_single is not None
        return pop, which, cfgs, np.ones((2000, N))
    if case == "two_source_links":
        cfgs = [H1_TWO_LINKS, H1_LOCAL_CLOUD]
        pop, which = _gate_population(network, "h1", 2000, cfgs,
                                      [0.5, 0.5], rng)
        assert len(pop._gate_cols(H1_TWO_LINKS.placement)) == 2
        return pop, which, cfgs, np.ones((2000, N))
    if case == "attachment_rows":
        # the modal incumbent reads the edge link whose factor differs
        # between attached and detached users
        cfgs = [H1_EDGE1, H1_LOCAL_CLOUD, H1_TWO_LINKS]
        pop, which = _gate_population(network, "h1", 2000, cfgs,
                                      [0.8, 0.1, 0.1], rng)
        return pop, which, cfgs, _attach_factors(2000, N, rng)
    if case == "masked_node":
        cfgs = [H1_LOCAL_CLOUD, H1_CLOUD, H1_EDGE1]
        pop, which = _gate_population(network, "h1", 2000, cfgs,
                                      [0.6, 0.2, 0.2], rng)
        pop.mask_node(4, users=np.nonzero(rng.random(2000) < 0.3)[0])
        pop.mask_node(1, users=np.nonzero(rng.random(2000) < 0.1)[0])
        return pop, which, cfgs, np.ones((2000, N))
    if case == "three_configs":
        cfgs = [H2_LOCAL, H2_CLOUD, H2_SPLIT]
        pop, which = _gate_population(network, "h2", 5000, cfgs,
                                      [0.6, 0.25, 0.15], rng)
        assert np.bincount(which).max() * 8 < 7 * 5000
        return pop, which, cfgs, np.ones((5000, N))
    raise AssertionError(case)


@pytest.mark.parametrize("case", ["scales", "all_local", "uniform_all_local",
                                  "two_source_links", "attachment_rows",
                                  "masked_node", "three_configs"])
def test_threshold_gate_bitexact(network, case):
    rng = np.random.default_rng(sum(map(ord, case)))
    pop, which, cfgs, fac = _gate_case(case, network, rng)
    sc, edges = _threshold_scales(pop, which, cfgs, fac, rng)
    no_inc, feas, _e = _assert_gate_bitexact(pop, sc, fac)
    assert not no_inc.any()
    # each threshold is tight: feasible at it and above, not one below
    dead = np.array([pop._masked[u, cfgs[which[u]].placement].any()
                     for u in range(pop.U)])
    for at, below, above in edges:
        if dead[[at, below, above]].any():
            continue
        assert (feas[at], feas[below], feas[above]) == (True, False, True)
    if case not in ("all_local", "uniform_all_local"):
        assert edges and feas.any() and not feas.all()
    if case in ("all_local", "uniform_all_local"):
        # no source link: one constant per configuration
        assert np.array_equal(feas, np.asarray(cfgs)[which] == H2_LOCAL)


@pytest.mark.parametrize("delta", ["update_slice", "update_backhaul"])
def test_threshold_gate_cache_follows_the_proto(network, delta):
    """A compute-slice or backhaul repricing moves the thresholds: the
    memo is dropped, and rows at the old thresholds are judged afresh."""
    rng = np.random.default_rng(11)
    cfgs = [H1_LOCAL_CLOUD, H1_EDGE1_CLOUD]
    pop, which = _gate_population(network, "h1", 2000, cfgs, [0.5, 0.5],
                                  rng)
    fac = np.ones((2000, network.n_nodes))
    sc, _ = _threshold_scales(pop, which, cfgs, fac, rng)
    _n, before, _e = _assert_gate_bitexact(pop, sc, fac)
    assert pop._gate_cache
    if delta == "update_slice":
        pop.update_slice(0.5)
    else:
        pop.update_backhaul(0.02)
    assert not pop._gate_cache
    _n, after, _e = _assert_gate_bitexact(pop, sc, fac)
    assert not np.array_equal(before, after)
