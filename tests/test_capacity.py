"""Shared node/link capacity: congestion pricing, admission, oracles.

The contracts under test (core/capacity.py):

* ``accumulate_loads`` over the SoA incumbent arrays is IEEE-identical to
  a scalar replay of ``problem.config_node_loads`` / ``config_link_loads``
  through the documented canonical grouped reduction — including failed
  users, masked nodes and ``check_aggregate_load`` cohorts;
* the (3d+) aggregate-load arithmetic has ONE home: ``problem.
  evaluate_config`` and ``frontier.eval_config_users`` agree bit-for-bit
  on the load == capacity boundary (the historical duplicated-logic
  footgun);
* a converged congestion fixed point never violates a capacity among
  admitted users (brute-force joint-load oracle), and every user left
  unplaced has NO Pareto-frontier row fitting the final residual
  capacity at the final prices;
* infinite capacities are bit-exact vs the uncoupled population tick —
  the controller is a pure read-only probe;
* identical seeds give identical price trajectories, admissions and
  incumbents, for ``vector_postpass`` True/False (f64, bit-exact) and
  for the f32 ``pallas`` engine (self-deterministic, energies within
  ``core/tolerances.py`` of minplus);
* ``update_backhaul`` (the typed link-reprice delta) is bit-exact vs a
  fresh build on the rescaled network, for Plan and Population.

Randomized sweeps run under hypothesis when available and as a seeded
loop otherwise (the CI image does not ship hypothesis).
"""
import numpy as np
import pytest

from repro.core import (ChurnEvent, ChurnOrchestrator, CongestionController,
                        Plan, Population, SharedCapacity, accumulate_loads,
                        app_price_weights, churn_trace, config_load_rows,
                        evaluate_config, paper_profile, population_cohorts,
                        population_plans, synthetic_profile)
from repro.core.capacity import CongestionReport
from repro.core.frontier import eval_config_users
from repro.core.multiapp import PAPER_MULTIAPP_REQS
from repro.core.problem import (AppRequirements, Config, config_link_loads,
                                config_node_loads)
from repro.core.scenarios import paper_scenario
from repro.core.tolerances import dist_tol


@pytest.fixture(scope="module")
def network():
    return paper_scenario(n_extra_edge=1)


def _pop(network, app="h1", U=8, **kw):
    p = Population(network, paper_profile(app), PAPER_MULTIAPP_REQS[app],
                   U, **kw)
    return p


def _ingest_random(pop, seed, lo=0.3, hi=1.2):
    rng = np.random.default_rng(seed)
    pop.ingest(rng.uniform(lo, hi, pop.U) * 1e9)
    pop.solve(build_solutions=False)
    return pop


def _scalar_replay_loads(pops):
    """Independent scalar replay of the canonical grouped reduction:
    per cohort, group incumbents by the raw (exit | placement) int32 row
    bytes, order groups by those bytes ascending (``np.unique`` void-view
    order), contribute ``count * row`` with rows built from the scalar
    ``problem`` helpers.  Shares no code with ``accumulate_loads`` beyond
    the single-config helpers it is specified against."""
    N = pops[0].N
    node = np.zeros(N)
    link = np.zeros((N, N))
    for p in pops:
        groups = {}
        for u in range(p.U):
            if not p.inc_found[u]:
                continue
            row = np.empty(1 + p.L, dtype=np.int32)
            row[0] = p._inc_exit[u]
            row[1:] = p._inc_place[u]
            groups.setdefault(row.tobytes(), []).append(u)
        for key in sorted(groups):
            members = groups[key]
            u0 = members[0]
            k = int(p._inc_exit[u0])
            nb = p.profile.exits[k].block + 1
            cfg = Config(placement=[int(x) for x in p._inc_place[u0][:nb]],
                         final_exit=k)
            nrow = np.array(config_node_loads(p.profile, cfg, p.req.sigma,
                                              N))
            lrow = np.zeros((N, N))
            for a, b, x in config_link_loads(p.profile, cfg, p.src,
                                             p.req.sigma):
                lrow[a, b] += x
            node += float(len(members)) * nrow
            link += float(len(members)) * lrow
    return node, link


def _assert_caps_hold(ctrl, tol=0.0):
    """Oracle: brute-force per-user joint loads of the admitted set never
    exceed a capacity (tiny relative slack only for the per-user -- i.e.
    non-grouped -- summation order)."""
    N = ctrl.pops[0].N
    node = np.zeros(N)
    link = np.zeros((N, N))
    for p in ctrl.pops:
        for u in range(p.U):
            if not p.inc_found[u]:
                continue
            k = int(p._inc_exit[u])
            nb = p.profile.exits[k].block + 1
            cfg = Config(placement=[int(x) for x in p._inc_place[u][:nb]],
                         final_exit=k)
            nr, lr = config_load_rows(p.profile, cfg, p.req.sigma, N, p.src)
            node += nr
            link += lr
    assert (node <= ctrl.node_cap * (1.0 + tol)).all(), \
        (node, ctrl.node_cap)
    assert (link <= ctrl.link_cap * (1.0 + tol)).all()
    # and the canonical grouped reduction holds EXACTLY (what the
    # controller itself enforces)
    nl, ll = accumulate_loads(ctrl.pops)
    assert (nl <= ctrl.node_cap).all()
    assert (ll <= ctrl.link_cap).all()


def _no_fitting_row(ctrl, k_per_exit=4):
    """Admission contract: every unplaced user has no frontier row that
    fits the final residual capacity at the final prices.  Each _fits
    rejection is cross-checked against an independent canonical install
    (guards the incremental screen against false rejections)."""
    for pi, p in enumerate(ctrl.pops):
        for lu in np.nonzero(~p.inc_found)[0]:
            lu = int(lu)
            fr = p.frontier(lu, k_per_exit=k_per_exit)
            for row in fr.rows:
                assert not ctrl._fits(pi, lu, row.config, row.energy), \
                    (pi, lu, row.config)
                # the canonical grouped reduction must agree that this
                # install genuinely violates a capacity
                save = (p._inc_place[lu].copy(), int(p._inc_exit[lu]),
                        float(p._inc_energy[lu]), bool(p._solved[lu]),
                        p._solutions[lu])
                p.set_incumbents(np.array([lu]), [row.config], [row.energy])
                nl, ll = accumulate_loads(ctrl.pops)
                assert (nl > ctrl.node_cap).any() \
                    or (ll > ctrl.link_cap).any(), (pi, lu, row.config)
                p._inc_place[lu] = save[0]
                p._inc_exit[lu] = save[1]
                p._inc_energy[lu] = save[2]
                p._solved[lu] = save[3]
                p._solutions[lu] = save[4]


# ---------------------------------------------------------------------------
# satellite: one home for the (3d+) arithmetic — both call sites agree
# ---------------------------------------------------------------------------

def test_aggregate_load_call_sites_agree_on_boundary(network):
    """problem.evaluate_config and frontier.eval_config_users must make
    the same feasibility call when the aggregate load lands EXACTLY on
    the capacity (the old duplicated logic could disagree in the last
    ulp); marginally above must flip both."""
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    N = network.n_nodes
    src = network.source_node
    cfg = Config(placement=[1] * prof.n_blocks,
                 final_exit=len(prof.exits) - 1)
    load = config_node_loads(prof, cfg, req.sigma, N)
    bwv = np.full(N, 1e9)
    bwv[src] = np.inf

    from repro.core import Network
    for scale, expect_viol in ((1.0, False), (1.0 - 1e-12, True)):
        comp = network.compute.copy()
        for n in range(N):
            if load[n] > 0:
                comp[n] = load[n] * scale
        nw = Network(nodes=network.nodes, bandwidth=network.bandwidth,
                     compute=comp, source_node=src)
        # problem call site
        ev = evaluate_config(nw, prof, req, cfg, check_aggregate_load=True)
        has = any("(3d+)" in v for v in ev.violations)
        assert has == expect_viol, (scale, ev.violations)
        # frontier call site: the aggregate check flips viol for all users
        _e, _ec, _em, _lat, viol_off = eval_config_users(
            prof, req, network.nodes, network.bandwidth, comp, src, cfg,
            bwv[None, :], check_aggregate_load=False)
        _e, _ec, _em, _lat, viol_on = eval_config_users(
            prof, req, network.nodes, network.bandwidth, comp, src, cfg,
            bwv[None, :], check_aggregate_load=True)
        if expect_viol:
            assert viol_on.all()
        else:
            assert (viol_on == viol_off).all()


def test_config_link_loads_terms(network):
    """Link rows carry exactly the (3e) terms: input transfer src->first
    host when offloaded, survival-weighted cut bits on every placement
    cut — nothing on co-located blocks."""
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    src = network.source_node
    k = len(prof.exits) - 1
    nb = prof.exits[k].block + 1
    place = [src] * nb
    place[-1] = 1                                    # one cut at the end
    cfg = Config(placement=place, final_exit=k)
    terms = config_link_loads(prof, cfg, src, req.sigma)
    assert terms == [(src, 1, req.sigma * prof.survival_after_block(nb - 2, k)
                      * float(prof.cut_bits[nb - 2]))]
    # fully local: no link load at all
    assert config_link_loads(prof, Config(placement=[src] * nb,
                                          final_exit=k), src, req.sigma) \
        == []


# ---------------------------------------------------------------------------
# satellite: accumulate_loads vs scalar replay (IEEE-identical)
# ---------------------------------------------------------------------------

def test_accumulate_loads_matches_scalar_replay(network):
    pops = [_ingest_random(_pop(network, "h1", U=7), 3),
            _ingest_random(_pop(network, "h5", U=5), 4)]
    nl, ll = accumulate_loads(pops)
    nl2, ll2 = _scalar_replay_loads(pops)
    assert np.array_equal(nl, nl2)                  # bit-exact, not close
    assert np.array_equal(ll, ll2)
    assert nl[network.source_node] > 0              # local blocks do load
    assert (nl >= 0).all() and (ll >= 0).all()


def test_accumulate_loads_masked_and_failed(network):
    """Masked nodes re-route incumbents; users with no feasible placement
    contribute nothing."""
    pop = _ingest_random(_pop(network, "h1", U=6), 5)
    pop.mask_node(1, users=[0, 1, 2])
    pop.solve(build_solutions=False)
    assert pop.inc_found.any()
    nl, ll = accumulate_loads([pop])
    nl2, ll2 = _scalar_replay_loads([pop])
    assert np.array_equal(nl, nl2) and np.array_equal(ll, ll2)
    # clear some incumbents entirely: they must vanish from the loads
    pop.set_incumbents(np.array([0, 3]), [None, None], [np.inf, np.inf])
    nl3, _ll3 = accumulate_loads([pop])
    nl4, _ll4 = _scalar_replay_loads([pop])
    assert np.array_equal(nl3, nl4)


def test_accumulate_loads_check_aggregate_mode(network):
    """The stricter check_aggregate_load cohorts use the same accumulator
    (the per-config rows do not depend on the flag)."""
    a = _ingest_random(_pop(network, "h1", U=5), 6)
    b = _ingest_random(_pop(network, "h1", U=5, check_aggregate_load=True),
                       6)
    nla, lla = accumulate_loads([a])
    nlb, llb = accumulate_loads([b])
    ra, _ = _scalar_replay_loads([a])
    rb, _ = _scalar_replay_loads([b])
    assert np.array_equal(nla, ra) and np.array_equal(nlb, rb)
    assert np.array_equal(lla, llb)


def test_accumulate_loads_grouping_is_count_times_row(network):
    """Identical configs aggregate as ONE multiply, not repeated adds —
    the determinism contract the oracle replay depends on."""
    pop = _pop(network, "h1", U=5)
    bw = np.full((5, network.n_nodes), 8e8)
    bw[:, network.source_node] = np.inf
    pop.ingest(bw)
    pop.solve(build_solutions=False)
    assert pop.inc_found.all()
    # same channel => same config for every user
    rows = {tuple(pop._inc_place[u]) for u in range(5)}
    assert len(rows) == 1
    nl, _ = accumulate_loads([pop])
    k = int(pop._inc_exit[0])
    nb = pop.profile.exits[k].block + 1
    cfg = Config(placement=[int(x) for x in pop._inc_place[0][:nb]],
                 final_exit=k)
    nrow, _ = config_load_rows(pop.profile, cfg, pop.req.sigma, pop.N,
                               pop.src)
    assert np.array_equal(nl, 5.0 * nrow)


# ---------------------------------------------------------------------------
# SharedCapacity / controller validation + fairness weights
# ---------------------------------------------------------------------------

def test_shared_capacity_validation():
    with pytest.raises(ValueError, match="node_cap"):
        SharedCapacity(node_cap=np.ones((2, 2)), link_cap=np.ones((2, 2)))
    with pytest.raises(ValueError, match="link_cap"):
        SharedCapacity(node_cap=np.ones(3), link_cap=np.ones((2, 2)))
    with pytest.raises(ValueError, match="positive"):
        SharedCapacity(node_cap=np.zeros(2), link_cap=np.ones((2, 2)))
    with pytest.raises(ValueError, match="price_step"):
        SharedCapacity(node_cap=np.ones(2), link_cap=np.ones((2, 2)),
                       price_step=1.0)
    with pytest.raises(ValueError, match="max_iters"):
        SharedCapacity(node_cap=np.ones(2), link_cap=np.ones((2, 2)),
                       max_iters=0)
    sc = SharedCapacity.infinite(4, price_step=2.0, price_cap=1024.0)
    assert sc.k_max == 10
    assert SharedCapacity.infinite(2, price_step=4.0, price_cap=4.0).k_max \
        == 1


def test_controller_validation(network):
    pop = _pop(network, "h1", U=2)
    sc = SharedCapacity.infinite(network.n_nodes)
    with pytest.raises(ValueError, match="at least one"):
        CongestionController(sc, [])
    with pytest.raises(ValueError, match="price_weights"):
        CongestionController(sc, [pop], weights=[1.0, 2.0])
    with pytest.raises(ValueError, match=">= 0"):
        CongestionController(sc, [pop], weights=[-1.0])
    with pytest.raises(ValueError, match="nodes"):
        CongestionController(SharedCapacity.infinite(network.n_nodes + 1),
                             [pop])


def test_app_price_weights():
    assert app_price_weights(["h1", "h5"]) == [1.0, 1.0]
    w = app_price_weights(["h1", "h5"], mode="latency")
    assert w[0] == 1.0 and 0 < w[1] < 1.0   # h5's tight deadline sheltered
    assert app_price_weights(mode="uniform") == [1.0] * 6
    with pytest.raises(ValueError, match="unknown apps"):
        app_price_weights(["h1", "nope"])
    with pytest.raises(ValueError, match="unknown mode"):
        app_price_weights(["h1"], mode="x")


def test_orchestrator_kwarg_validation(network):
    plans = population_plans(2, n_extra_edge=1)
    sc = SharedCapacity.infinite(network.n_nodes)
    with pytest.raises(ValueError, match="population"):
        ChurnOrchestrator(plans, shared_capacity=sc)
    pops = population_cohorts(2, n_extra_edge=1)
    with pytest.raises(ValueError, match="price_weights"):
        ChurnOrchestrator(population=pops, price_weights=[1.0])


# ---------------------------------------------------------------------------
# tentpole: infinite caps == uncoupled, bit-exact
# ---------------------------------------------------------------------------

APPS2 = {k: PAPER_MULTIAPP_REQS[k] for k in ("h1", "h5")}


def _cohort_orch(n_users, shared=None, weights=None, **pop_kw):
    pops = population_cohorts(n_users, apps=APPS2, n_extra_edge=1,
                              backend=pop_kw.pop("backend", "minplus"),
                              **pop_kw)
    kw = {}
    if shared is not None:
        kw = dict(shared_capacity=shared, price_weights=weights)
    return ChurnOrchestrator(population=pops, **kw)


def test_infinite_caps_bitexact_vs_uncoupled():
    U, T = 16, 5
    o1 = _cohort_orch(U)
    o2 = _cohort_orch(U, shared=SharedCapacity.infinite(o1.pops[0].N))
    s1 = o1.run(churn_trace(U, n_ticks=T, seed=13))
    s2 = o2.run(churn_trace(U, n_ticks=T, seed=13))
    for t1, t2 in zip(s1.ticks, s2.ticks):
        assert t1.energy == t2.energy
        assert (t1.n_resolved, t1.n_held, t1.n_migrations,
                t1.migration_bits) == \
               (t2.n_resolved, t2.n_held, t2.n_migrations,
                t2.migration_bits)
        # the congestion pass ran, observed convergence, touched nothing
        assert t2.congestion_iters == 1 and t2.congestion_converged
        assert t2.n_repriced == t2.n_evicted == t2.n_unplaced == 0
    for p1, p2 in zip(o1.pops, o2.pops):
        assert np.array_equal(p1._inc_place, p2._inc_place)
        assert np.array_equal(p1._inc_exit, p2._inc_exit)
        assert np.array_equal(p1._inc_energy, p2._inc_energy)
    assert o2.congestion.node_price.max() == 1.0
    assert not o2.congestion._active


# ---------------------------------------------------------------------------
# tentpole: over-subscription converges with zero violations (oracle)
# ---------------------------------------------------------------------------

def test_pricing_resolves_oversubscription(network):
    """Caps sized so repricing alone can steer the population feasible:
    converged fixed point, zero violations, nobody evicted."""
    pop = _ingest_random(_pop(network, "h1", U=12), 0, lo=1.0, hi=1.0)
    nl, _ = accumulate_loads([pop])
    src = network.source_node
    busy = int(np.argmax(np.where(np.arange(pop.N) == src, -1.0, nl)))
    assert nl[busy] > 0
    node_cap = np.full(pop.N, np.inf)
    node_cap[busy] = nl[busy] * 0.4
    ctrl = CongestionController(
        SharedCapacity(node_cap=node_cap,
                       link_cap=np.full((pop.N, pop.N), np.inf)), [pop])
    rep = ctrl.run_tick()
    assert rep.converged and not rep.capped
    assert rep.n_repriced >= 1 and rep.n_evicted == 0
    assert rep.unplaced_ids == []
    assert ctrl.node_price[busy] > 1.0
    _assert_caps_hold(ctrl, tol=1e-12)
    # warm prices: the next tick is an immediate no-op
    inc = pop._inc_place.copy()
    rep2 = ctrl.run_tick()
    assert rep2.converged and rep2.iterations == 1 and rep2.n_repriced == 0
    assert np.array_equal(inc, pop._inc_place)


def test_admission_when_prices_cap(network):
    """Local execution infeasible + tiny caps + low price_cap: pricing
    cannot fix it, admission control must evict to feasibility — and
    every rejected user provably has no fitting frontier row left."""
    nw = paper_scenario(n_extra_edge=1)
    nw.compute[nw.source_node] *= 1e-3      # local-only infeasible
    pop = Population(nw, paper_profile("h1"), PAPER_MULTIAPP_REQS["h1"], 12)
    bw = np.full((12, nw.n_nodes), 1e9)
    bw[:, nw.source_node] = np.inf
    pop.ingest(bw)
    pop.solve(build_solutions=False)
    assert pop.inc_found.all()
    nl, _ = accumulate_loads([pop])
    node_cap = np.full(pop.N, np.inf)
    for n in range(pop.N):
        if n != nw.source_node and nl[n] > 0:
            node_cap[n] = nl[n] * 3.0 / 12 * 1.01   # ~3 users fit
    ctrl = CongestionController(
        SharedCapacity(node_cap=node_cap,
                       link_cap=np.full((pop.N, pop.N), np.inf),
                       price_cap=4.0, max_iters=6), [pop])
    rep = ctrl.run_tick()
    assert rep.capped and not rep.converged
    assert rep.n_rejected > 0
    assert 0 < int(pop.inc_found.sum()) < 12
    assert rep.unplaced_ids == sorted(
        int(g) for g in pop.user_ids[~pop.inc_found])
    _assert_caps_hold(ctrl, tol=1e-12)
    _no_fitting_row(ctrl)
    # rejected users carry no energy and no load
    assert not np.isfinite(pop._inc_energy[~pop.inc_found]).any()


def test_congested_churn_end_to_end():
    """Orchestrator integration: coupled churn stays violation-free every
    tick, reports carry the congestion accounting, and the energy ledger
    resyncs after evictions."""
    U, T = 16, 4
    probe = _cohort_orch(U)
    nl, _ = accumulate_loads(probe.pops)
    N = probe.pops[0].N
    src = probe.pops[0].src
    busy = int(np.argmax(np.where(np.arange(N) == src, -1.0, nl)))
    node_cap = np.full(N, np.inf)
    node_cap[busy] = max(nl[busy] * 0.5, 1.0)
    sc = SharedCapacity(node_cap=node_cap,
                        link_cap=np.full((N, N), np.inf))
    o = _cohort_orch(U, shared=sc,
                     weights=app_price_weights(list(APPS2),
                                               mode="latency"))
    stats = o.run(churn_trace(U, n_ticks=T, seed=13))
    assert stats.ticks[0].n_repriced >= 1
    for t in stats.ticks:
        assert t.congestion_converged
        assert t.congestion_iters >= 1
    _assert_caps_hold(o.congestion, tol=1e-12)
    # ledger == pop incumbents after the congestion pass
    for p in o.pops:
        gl = p.user_ids
        e = np.where(p.inc_found, p._inc_energy, np.inf)
        assert np.array_equal(o._cur_energy[gl], e)


def test_link_capacity_pricing(network):
    """A choked shared backhaul link reroutes or localizes traffic via
    update_backhaul repricing.  One-hop offloads ride private source
    links, so the edge->cloud traffic is installed explicitly: every
    incumbent splits across edge node 1 and the cloud."""
    pop = _ingest_random(_pop(network, "h1", U=10), 1, lo=1.0, hi=1.0)
    src = network.source_node
    cloud = int(np.argmax(network.compute))
    assert cloud not in (src, 1)
    k = len(pop.profile.exits) - 1
    nb = pop.profile.exits[k].block + 1
    cfg = Config(placement=[1] * (nb // 2) + [cloud] * (nb - nb // 2),
                 final_exit=k)
    ev = evaluate_config(network, pop.profile, pop.req, cfg)
    assert ev.feasible
    pop.set_incumbents(np.arange(pop.U), [cfg] * pop.U,
                       [ev.energy] * pop.U)
    _nl, ll = accumulate_loads([pop])
    assert ll[1, cloud] > 0                         # shared backhaul loaded
    link_cap = np.full((pop.N, pop.N), np.inf)
    link_cap[1, cloud] = ll[1, cloud] * 0.5
    ctrl = CongestionController(
        SharedCapacity(node_cap=np.full(pop.N, np.inf),
                       link_cap=link_cap), [pop])
    rep = ctrl.run_tick()
    assert rep.converged
    assert rep.touched
    assert ctrl.link_price[1, cloud] > 1.0
    assert pop._proto.stats.backhaul_updates > 0    # typed delta path
    _assert_caps_hold(ctrl, tol=1e-12)


def test_zero_weight_cohort_never_repriced(network):
    """w == 0 shelters a cohort from repricing (its tensors never move)
    while its load still counts and admission may still touch it."""
    a = _ingest_random(_pop(network, "h1", U=6), 0, lo=1.0, hi=1.0)
    b = _ingest_random(_pop(network, "h1", U=6,
                            user_ids=np.arange(6, 12)), 0, lo=1.0, hi=1.0)
    nl, _ = accumulate_loads([a, b])
    src = network.source_node
    busy = int(np.argmax(np.where(np.arange(a.N) == src, -1.0, nl)))
    node_cap = np.full(a.N, np.inf)
    node_cap[busy] = nl[busy] * 0.4
    ctrl = CongestionController(
        SharedCapacity(node_cap=node_cap,
                       link_cap=np.full((a.N, a.N), np.inf)),
        [a, b], weights=[0.0, 1.0])
    slice_updates_before = a._proto.stats.slice_updates
    ctrl.run_tick()
    assert a._proto.stats.slice_updates == slice_updates_before
    assert b._proto.stats.slice_updates > 0 or ctrl.node_price.max() == 1.0
    _assert_caps_hold(ctrl, tol=1e-12)


# ---------------------------------------------------------------------------
# regressions: slice renegotiation composes with prices; report flags;
# moved-user tracking; hysteresis baseline scoping
# ---------------------------------------------------------------------------

def _congested_ctrl(network, U=12, cap_frac=0.4, **sc_kw):
    """One h1 cohort plus a controller whose busiest non-source node is
    capped at ``cap_frac`` of its uncoupled load."""
    pop = _ingest_random(_pop(network, "h1", U=U), 0, lo=1.0, hi=1.0)
    nl, _ = accumulate_loads([pop])
    src = network.source_node
    busy = int(np.argmax(np.where(np.arange(pop.N) == src, -1.0, nl)))
    assert nl[busy] > 0
    node_cap = np.full(pop.N, np.inf)
    node_cap[busy] = nl[busy] * cap_frac
    ctrl = CongestionController(
        SharedCapacity(node_cap=node_cap,
                       link_cap=np.full((pop.N, pop.N), np.inf), **sc_kw),
        [pop])
    return pop, ctrl, busy


def test_renegotiate_slice_composes_with_prices(network):
    """A slice re-negotiation under active congestion prices COMPOSES
    (base * step**(-k*w)) instead of clobbering the applied price factor
    — and a later reprice keeps the renegotiated base instead of
    discarding it (the absolute-write footgun of Plan.update_slice)."""
    pop, ctrl, busy = _congested_ctrl(network)
    rep = ctrl.run_tick()
    assert rep.converged and ctrl.node_k[busy] > 0
    price_frac = ctrl.step ** (-ctrl.node_k.astype(np.float64) * 1.0)
    assert np.array_equal(pop._proto._slice_frac, 1.0 * price_frac)
    ctrl.renegotiate_slice(0.9)
    assert np.array_equal(pop._proto._slice_frac,
                          np.full(pop.N, 0.9) * price_frac)
    # prices survive the renegotiation: the applied key is in sync, so
    # the next run_tick does not see phantom-unapplied exponents
    assert ctrl._applied_node[0] == ctrl.node_k.tobytes()
    # a further reprice composes on top of the NEW base
    ctrl.node_k[busy] += 1
    ctrl._apply_prices()
    price_frac2 = ctrl.step ** (-ctrl.node_k.astype(np.float64) * 1.0)
    assert np.array_equal(pop._proto._slice_frac,
                          np.full(pop.N, 0.9) * price_frac2)
    with pytest.raises(ValueError, match="finite"):
        ctrl.renegotiate_slice(0.0)


def test_slice_event_composes_end_to_end():
    """Orchestrator form of the same regression: a population-mode slice
    churn event on a congested coupled run lands as base * price on every
    cohort (weights respected), not as a price-clobbering absolute
    write."""
    U = 16
    probe = _cohort_orch(U)
    nl, _ = accumulate_loads(probe.pops)
    N = probe.pops[0].N
    src = probe.pops[0].src
    busy = int(np.argmax(np.where(np.arange(N) == src, -1.0, nl)))
    node_cap = np.full(N, np.inf)
    node_cap[busy] = nl[busy] * 0.4
    o = _cohort_orch(U, shared=SharedCapacity(
        node_cap=node_cap, link_cap=np.full((N, N), np.inf)))
    o.step([])                                   # prices the busy node
    assert o.congestion.node_k[busy] > 0
    o.step([ChurnEvent(kind="slice", user=None, value=0.9)])
    for pi, p in enumerate(o.pops):
        w = o.congestion.weights[pi]
        expect = 0.9 * o.congestion.step \
            ** (-o.congestion.node_k.astype(np.float64) * w)
        assert np.array_equal(p._proto._slice_frac, expect)
    _assert_caps_hold(o.congestion, tol=1e-12)


def test_slice_event_unpriced_coupled_bitexact_vs_uncoupled():
    """With no prices applied (all exponents zero) the composed slice
    factor is bit-exactly the base: a coupled-but-idle orchestrator and
    an uncoupled one make identical decisions through a slice event."""
    U = 12
    o1 = _cohort_orch(U)
    o2 = _cohort_orch(U, shared=SharedCapacity.infinite(o1.pops[0].N))
    ev = [ChurnEvent(kind="slice", user=None, value=0.8)]
    t1, t2 = o1.step(ev), o2.step(ev)
    assert t1.energy == t2.energy
    assert not o2.congestion._active
    for p1, p2 in zip(o1.pops, o2.pops):
        assert np.array_equal(p1._proto._slice_frac, p2._proto._slice_frac)
        assert np.array_equal(p1._inc_place, p2._inc_place)
        assert np.array_equal(p1._inc_energy, p2._inc_energy)


def test_converged_flag_when_iteration_cap_exhausts(network):
    """If the LAST allowed iteration's reprice clears the overload, the
    report must say converged — the loop exhausting right after the
    final bump is not a failure to converge."""
    _pop1, ctrl1, _busy = _congested_ctrl(network, max_iters=16)
    rep1 = ctrl1.run_tick()
    assert rep1.converged
    k = rep1.iterations
    assert k >= 2                 # converged detected on iteration k
    # identical fresh scenario, capped one iteration short of the natural
    # convergence check: same deterministic bump trajectory, but the loop
    # exhausts right after the reprice that cleared the overload
    pop2, ctrl2, busy2 = _congested_ctrl(network, max_iters=k - 1)
    rep2 = ctrl2.run_tick()
    assert rep2.iterations == k - 1
    assert rep2.converged and not rep2.capped
    assert np.array_equal(ctrl2.node_k, ctrl1.node_k)
    assert rep2.unplaced_ids == []
    _assert_caps_hold(ctrl2, tol=1e-12)


def test_moved_gids_are_exactly_the_changed_incumbents(network):
    """CongestionReport.moved_gids == the users whose incumbent (found
    flag, config or energy) differs from the pre-pass state — the set the
    orchestrator re-arms its hysteresis baseline for."""
    nw = paper_scenario(n_extra_edge=1)
    nw.compute[nw.source_node] *= 1e-3
    pop = Population(nw, paper_profile("h1"), PAPER_MULTIAPP_REQS["h1"], 12)
    bw = np.full((12, nw.n_nodes), 1e9)
    bw[:, nw.source_node] = np.inf
    pop.ingest(bw)
    pop.solve(build_solutions=False)
    nl, _ = accumulate_loads([pop])
    node_cap = np.full(pop.N, np.inf)
    for n in range(pop.N):
        if n != nw.source_node and nl[n] > 0:
            node_cap[n] = nl[n] * 3.0 / 12 * 1.01
    ctrl = CongestionController(
        SharedCapacity(node_cap=node_cap,
                       link_cap=np.full((pop.N, pop.N), np.inf),
                       price_cap=4.0, max_iters=6), [pop])
    before = [(p.inc_found.copy(), p._inc_exit.copy(), p._inc_place.copy(),
               p._inc_energy.copy()) for p in ctrl.pops]
    rep = ctrl.run_tick()
    assert rep.touched
    changed = []
    for (f0, e0, pl0, en0), p in zip(before, ctrl.pops):
        for lu in range(p.U):
            if f0[lu] != p.inc_found[lu] or (p.inc_found[lu] and (
                    e0[lu] != p._inc_exit[lu]
                    or (pl0[lu] != p._inc_place[lu]).any()
                    or en0[lu] != p._inc_energy[lu])):
                changed.append(int(p.user_ids[lu]))
    assert rep.moved_gids == sorted(changed)
    assert rep.moved_gids
    # every rejected user changed by definition
    assert set(rep.unplaced_ids) <= set(rep.moved_gids)


def test_congestion_ref_reset_scoped_to_moved_users():
    """The orchestrator's hysteresis baseline (_ref_energy) is re-armed
    ONLY for users the congestion pass actually moved: a sheltered
    (w = 0) cohort's untouched user keeps its baseline through a tick
    that reprices the other cohort, while _cur_energy resyncs for all."""
    U = 16
    probe = _cohort_orch(U)
    nl, _ = accumulate_loads(probe.pops)
    N = probe.pops[0].N
    src = probe.pops[0].src
    busy = int(np.argmax(np.where(np.arange(N) == src, -1.0, nl)))
    # start uncontended, then tighten the live cap between ticks
    o = _cohort_orch(U, shared=SharedCapacity.infinite(N),
                     weights=[0.0, 1.0])
    o.step([])
    a = o.pops[0]                                # the sheltered cohort
    sheltered = a.user_ids
    assert np.isfinite(o._ref_energy[sheltered]).all()
    sentinel = o._ref_energy[sheltered] * (1.0 + 1e-6)
    o._ref_energy[sheltered] = sentinel
    a_inc = (a.inc_found.copy(), a._inc_exit.copy(), a._inc_place.copy(),
             a._inc_energy.copy())
    o.congestion.node_cap[busy] = nl[busy] * 0.5
    rep = o.step([])
    assert rep.n_repriced >= 1                   # cohort b was repriced
    untouched = (a_inc[0] == a.inc_found) \
        & (a_inc[1] == a._inc_exit) \
        & (a_inc[2] == a._inc_place).all(axis=1) \
        & (a_inc[3] == a._inc_energy)
    assert untouched.any()
    # untouched users keep their baseline (the old eager reset clobbered
    # it) while the spent-energy ledger resyncs to the incumbents
    assert np.array_equal(o._ref_energy[sheltered[untouched]],
                          sentinel[untouched])
    assert np.array_equal(o._cur_energy[sheltered[untouched]],
                          a._inc_energy[untouched])
    # moved sheltered users (if any) had their baseline re-armed
    moved = ~untouched
    if moved.any():
        assert not np.isin(o._ref_energy[sheltered[moved]],
                           sentinel[moved]).any()


# ---------------------------------------------------------------------------
# tentpole: determinism across vector_postpass and backends
# ---------------------------------------------------------------------------

def _congested_run(backend, vector_postpass, U=8, T=3):
    pops = population_cohorts(U, apps=APPS2, n_extra_edge=1,
                              backend=backend,
                              vector_postpass=vector_postpass)
    N = pops[0].N
    src = pops[0].src
    node_cap = np.full(N, np.inf)
    # fixed caps (not probe-calibrated) so every config sees the same
    # scenario: small enough to trip congestion for these apps
    for n in range(N):
        if n != src:
            node_cap[n] = 2e9
    sc = SharedCapacity(node_cap=node_cap,
                        link_cap=np.full((N, N), np.inf))
    o = ChurnOrchestrator(population=pops, shared_capacity=sc)
    traj = []
    for events in churn_trace(U, n_ticks=T, seed=21):
        o.step(events)
        traj.append((o.congestion.node_k.tobytes(),
                     o.congestion.link_k.tobytes(),
                     tuple(int(p.inc_found.sum()) for p in o.pops)))
    incs = [(p._inc_place.copy(), p._inc_exit.copy(),
             p._inc_energy.copy()) for p in o.pops]
    return traj, incs


@pytest.mark.parametrize("backend,vp", [("minplus", True),
                                        ("minplus", False),
                                        ("pallas", True)])
def test_determinism_same_seed_same_trajectory(backend, vp):
    """Two runs from identical seeds: identical price trajectories,
    admissions and incumbents (f64 and f32 engines alike are
    self-deterministic)."""
    t1, i1 = _congested_run(backend, vp, U=6, T=2)
    t2, i2 = _congested_run(backend, vp, U=6, T=2)
    assert t1 == t2
    for (p1, e1, g1), (p2, e2, g2) in zip(i1, i2):
        assert np.array_equal(p1, p2)
        assert np.array_equal(e1, e2)
        assert np.array_equal(g1, g2)


def test_determinism_vector_postpass_bitexact():
    """vector_postpass True/False is a pure implementation switch on the
    f64 backend: identical price trajectories and bit-identical
    incumbents through congested churn."""
    t1, i1 = _congested_run("minplus", True)
    t2, i2 = _congested_run("minplus", False)
    assert t1 == t2
    for (p1, e1, g1), (p2, e2, g2) in zip(i1, i2):
        assert np.array_equal(p1, p2)
        assert np.array_equal(e1, e2)
        assert np.array_equal(g1, g2)


def test_f32_backend_energies_within_tolerance():
    """pallas (f32) congested churn lands on the same admissions as
    minplus with energies inside the engine's documented distance
    tolerance."""
    from repro.core.fin import DP_BACKENDS
    t64, i64 = _congested_run("minplus", True, U=6, T=2)
    t32, i32 = _congested_run("pallas", True, U=6, T=2)
    tol = dist_tol(DP_BACKENDS["pallas"])
    assert [a[2] for a in t64] == [a[2] for a in t32]   # same admissions
    for (p1, e1, g1), (p2, e2, g2) in zip(i64, i32):
        assert np.array_equal(p1, p2)                    # same placements
        both = np.isfinite(g1) & np.isfinite(g2)
        assert np.isfinite(g1).tolist() == np.isfinite(g2).tolist()
        if both.any():
            assert np.allclose(g1[both], g2[both], rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# update_backhaul: the typed link-reprice delta, warm == fresh
# ---------------------------------------------------------------------------

def test_plan_update_backhaul_matches_fresh(network):
    prof = paper_profile("h1")
    req = PAPER_MULTIAPP_REQS["h1"]
    rng = np.random.default_rng(9)
    plan = Plan(network, prof, req)
    plan.solve()
    N = network.n_nodes
    src = network.source_node
    for _ in range(3):
        scale = rng.uniform(0.25, 1.0, (N, N))
        plan.update_backhaul(scale)
        bw = network.bandwidth.copy()
        off = np.ones((N, N), dtype=bool)
        off[src, :] = False
        off[:, src] = False
        np.fill_diagonal(off, False)
        bw[off] = network.bandwidth[off] * scale[off]
        from repro.core import Network
        nw2 = Network(nodes=network.nodes, bandwidth=bw,
                      compute=network.compute, source_node=src)
        fresh = Plan(nw2, prof, req)
        a, b = plan.solve(), fresh.solve()
        assert a.found == b.found
        if a.found:
            assert a.config.placement == b.config.placement
            assert a.config.final_exit == b.config.final_exit
            assert a.energy == b.energy
    # scaling back to 1.0 restores the pristine plan exactly
    plan.update_backhaul(1.0)
    pristine = Plan(network, prof, req)
    a, b = plan.solve(), pristine.solve()
    assert a.energy == b.energy and a.config.placement == \
        b.config.placement
    assert plan.stats.backhaul_updates == 4


def test_plan_update_backhaul_validation(network):
    plan = Plan(network, paper_profile("h1"), PAPER_MULTIAPP_REQS["h1"])
    with pytest.raises(ValueError, match="finite"):
        plan.update_backhaul(0.0)
    with pytest.raises(ValueError, match="finite"):
        plan.update_backhaul(np.inf)


def test_population_update_backhaul_matches_plans(network):
    """Cohort-wide update_backhaul == per-plan update_backhaul, and the
    memoized exact energies survive (bandwidth-free Eq. 2)."""
    prof = paper_profile("h2")
    req = PAPER_MULTIAPP_REQS["h2"]
    U = 5
    pop = Population(network, prof, req, U)
    plans = [Plan(network, prof, req) for _ in range(U)]
    rng = np.random.default_rng(17)
    q = rng.uniform(0.3, 1.0, U) * 1e9
    pop.ingest(q)
    for u, p in enumerate(plans):
        p.update_uplink(q[u])
    for scale in (0.5, np.full((network.n_nodes,) * 2, 0.25), 1.0):
        pop.update_backhaul(scale)
        for p in plans:
            p.update_backhaul(scale)
        a = pop.solve()
        b = [p.solve() for p in plans]
        for u in range(U):
            assert a[u].found == b[u].found
            if a[u].found:
                assert a[u].energy == b[u].energy
                assert a[u].config.placement == b[u].config.placement


# ---------------------------------------------------------------------------
# randomized sweep (hypothesis when available, seeded loop otherwise)
# ---------------------------------------------------------------------------

def _random_capacity_run(seed: int) -> None:
    """Random small population (<= 8 users, <= 4 nodes), random caps and
    price grid: the converged/evicted end state never violates a capacity
    (brute-force oracle), unplaced users are justified, and infinite caps
    leave the population untouched."""
    rng = np.random.default_rng(seed)
    nw = paper_scenario(n_extra_edge=int(rng.integers(0, 2)))
    n_blocks = int(rng.integers(2, 5))
    prof = synthetic_profile(n_blocks, min(n_blocks,
                                           int(rng.integers(1, 3))),
                             seed=seed)
    alpha = float(rng.uniform(0.0, max(e.accuracy for e in prof.exits)))
    req = AppRequirements(alpha=alpha,
                          delta=float(rng.uniform(1e-3, 20e-3)))
    U = int(rng.integers(2, 9))
    pop = Population(nw, prof, req, U)
    pop.ingest(rng.uniform(0.2, 1.2, U) * 1e9)
    pop.solve(build_solutions=False)
    if not pop.inc_found.any():
        return
    nl, ll = accumulate_loads([pop])
    assert np.array_equal(np.stack([nl]), np.stack(
        [_scalar_replay_loads([pop])[0]]))

    # infinite caps: read-only
    inc = (pop._inc_place.copy(), pop._inc_exit.copy(),
           pop._inc_energy.copy())
    rep0 = CongestionController(SharedCapacity.infinite(pop.N), [pop]) \
        .run_tick()
    assert rep0.converged and not rep0.touched
    assert rep0.moved_gids == []
    assert np.array_equal(inc[0], pop._inc_place)
    assert np.array_equal(inc[2], pop._inc_energy)

    # random finite caps somewhere below the uncoupled loads
    node_cap = np.full(pop.N, np.inf)
    link_cap = np.full((pop.N, pop.N), np.inf)
    src = nw.source_node
    for n in range(pop.N):
        if n != src and nl[n] > 0 and rng.random() < 0.7:
            node_cap[n] = nl[n] * float(rng.uniform(0.2, 1.5))
    lo = ll.copy()
    lo[src, :] = 0.0
    lo[:, src] = 0.0
    for i, j in zip(*np.nonzero(lo > 0)):
        if rng.random() < 0.5:
            link_cap[i, j] = ll[i, j] * float(rng.uniform(0.2, 1.5))
    if not (np.isfinite(node_cap).any() or np.isfinite(link_cap).any()):
        return
    ctrl = CongestionController(
        SharedCapacity(node_cap=node_cap, link_cap=link_cap,
                       price_step=float(rng.uniform(1.5, 4.0)),
                       price_cap=float(rng.choice([4.0, 64.0, 4096.0])),
                       max_iters=int(rng.integers(2, 10))),
        [pop], frontier_k=int(rng.integers(1, 5)))
    rep = ctrl.run_tick()
    assert rep.iterations <= ctrl.capacity.max_iters
    _assert_caps_hold(ctrl, tol=1e-12)
    _no_fitting_row(ctrl, k_per_exit=ctrl.frontier_k)
    # rejected set is consistent with the report: a pass that never left
    # the read-only regime (no bump, no warm prices) reports nobody, even
    # where the uncoupled solve left users without a placement
    unplaced = sorted(int(g) for g in pop.user_ids[~pop.inc_found])
    assert rep.unplaced_ids == (unplaced if ctrl._active else [])


@pytest.mark.parametrize("seed", range(6))
def test_random_capacity_fixed_points(seed):
    _random_capacity_run(3000 + seed)


try:
    from hypothesis import HealthCheck, example, given, settings
    from hypothesis import strategies as st

    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    # finite caps that never bind, with users the uncoupled solve left
    # unplaced: the pass stays read-only and reports nobody unplaced
    @example(seed=9437)
    def test_property_capacity_fixed_points(seed):
        """Property form (AC): random small populations — the congestion
        fixed point never leaves a capacity violated among admitted
        users, rejections are justified, infinite caps are read-only."""
        _random_capacity_run(seed)
except ImportError:          # pragma: no cover - hypothesis optional
    pass
