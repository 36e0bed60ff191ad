"""Vectorized min-plus FIN backends vs the legacy Python DP oracle.

The vectorized solver must be *indistinguishable* from the legacy
``backend="python"`` triple-loop DP: same selected configuration, same final
exit, same (exactly evaluated) energy — on every paper app and across
gamma / delta / quantizer settings.  ``solve_many`` must in turn equal a
loop of per-scenario ``solve_fin`` calls.
"""
import numpy as np
import pytest

from repro.core import (AppRequirements, build_extended_graph,
                        build_extended_graphs, build_feasible_graph,
                        build_feasible_graphs, paper_profile, solve_fin,
                        solve_many, synthetic_profile, user_network,
                        user_networks)
from repro.core.bellman_ford import (batched_banded_relax_argmin,
                                     batched_banded_relax_min,
                                     batched_banded_relax_minarg,
                                     batched_layered_relax_argmin,
                                     batched_layered_relax_kbest,
                                     batched_layered_relax_min,
                                     layered_relax, layered_relax_argmin)
from repro.core.scenarios import paper_scenario, sweep_scenarios
from repro.core.tolerances import RELAX_RTOL_F32

APPS = ("h1", "h2", "h3", "h4", "h5", "h6")


@pytest.fixture(scope="module")
def scenario():
    return paper_scenario()


def _same(a, b):
    if a.found != b.found:
        return False
    if not a.found:
        return True
    return (a.config.placement == b.config.placement
            and a.config.final_exit == b.config.final_exit
            and a.energy == b.energy)


@pytest.mark.parametrize("backend", ["minplus", "dense", "jnp"])
@pytest.mark.parametrize("app", APPS)
def test_vectorized_backend_matches_python_oracle(scenario, app, backend):
    prof = paper_profile(app)
    alpha = min(e.accuracy for e in prof.exits)
    for delta in (2e-3, 5e-3, 12e-3):
        req = AppRequirements(alpha=alpha, delta=delta)
        oracle = solve_fin(scenario, prof, req, gamma=10, backend="python")
        vec = solve_fin(scenario, prof, req, gamma=10, backend=backend)
        assert _same(oracle, vec), (app, delta, backend)


@pytest.mark.parametrize("gamma", [3, 10, 25])
@pytest.mark.parametrize("quantize", ["floor", "ceil"])
def test_backend_equivalence_across_gamma_and_quantizer(scenario, gamma,
                                                        quantize):
    prof = paper_profile("h2")
    for delta in (2e-3, 4e-3, 8e-3):
        req = AppRequirements(alpha=0.80, delta=delta)
        oracle = solve_fin(scenario, prof, req, gamma=gamma,
                           quantize=quantize, backend="python")
        vec = solve_fin(scenario, prof, req, gamma=gamma,
                        quantize=quantize, backend="minplus")
        assert _same(oracle, vec), (gamma, quantize, delta)


def test_kbest_vectorized_matches_python(scenario):
    """n_best>1 (the beyond-paper collision fix) stays oracle-exact."""
    prof = paper_profile("h2")
    req = AppRequirements(0.80, 4e-3)
    for k in (2, 4):
        oracle = solve_fin(scenario, prof, req, gamma=3, n_best=k,
                           backend="python")
        vec = solve_fin(scenario, prof, req, gamma=3, n_best=k,
                        backend="minplus")
        assert _same(oracle, vec), k


def test_pallas_backend_matches_python(scenario):
    """Interpret-mode kernel path (small instance: interpret is slow)."""
    prof = paper_profile("h6")
    req = AppRequirements(alpha=0.93, delta=0.5e-3)
    oracle = solve_fin(scenario, prof, req, gamma=5, backend="python")
    vec = solve_fin(scenario, prof, req, gamma=5, backend="pallas")
    assert _same(oracle, vec)


def test_unknown_backend_raises(scenario):
    prof = paper_profile("h6")
    with pytest.raises(ValueError, match="backend"):
        solve_fin(scenario, prof, AppRequirements(0.5, 5e-3),
                  backend="cuda")


def test_solve_many_equals_per_scenario_solve(scenario):
    """Batched sweep over apps x deltas x uplinks == loop of solve()."""
    ps, ns, rs = sweep_scenarios(deltas_ms=(2.0, 5.0, 12.0),
                                 uplinks_bps=(1e9, 0.5e9))
    assert len(ps) >= 20
    batched = solve_many(ps, ns, rs, gamma=10)
    looped = [solve_fin(nw, pf, rq, gamma=10)
              for pf, nw, rq in zip(ps, ns, rs)]
    oracle = [solve_fin(nw, pf, rq, gamma=10, backend="python")
              for pf, nw, rq in zip(ps, ns, rs)]
    for b, l, o in zip(batched, looped, oracle):
        assert _same(b, l)
        assert _same(b, o)


def test_solve_many_mixed_sizes_and_broadcast(scenario):
    """Different block counts in one batch (relaxed as separate same-shape
    groups) and broadcasting of single network / requirement arguments."""
    profs = [paper_profile("h2"), paper_profile("h6"),
             synthetic_profile(4, 2, seed=0)]
    req = AppRequirements(alpha=0.0, delta=8e-3)
    batched = solve_many(profs, scenario, req)
    for prof, sol in zip(profs, batched):
        ref = solve_fin(scenario, prof, req)
        assert _same(ref, sol), prof.name


def test_solve_many_infeasible_alpha_slot(scenario):
    """An unsatisfiable-alpha scenario inside the batch stays a clean miss
    without disturbing its neighbours."""
    prof = paper_profile("h2")          # best exit accuracy < 0.95
    reqs = [AppRequirements(0.80, 5e-3), AppRequirements(0.95, 5e-3)]
    sols = solve_many(prof, scenario, reqs)
    assert sols[0].feasible
    assert not sols[1].found
    assert "alpha" in sols[1].meta["reason"]
    assert _same(sols[0], solve_fin(scenario, prof, reqs[0]))


def test_solve_many_backend_jnp(scenario):
    ps, ns, rs = sweep_scenarios(apps=("h2", "h6"), deltas_ms=(2.0, 8.0))
    batched = solve_many(ps, ns, rs, backend="jnp")
    for pf, nw, rq, sol in zip(ps, ns, rs, batched):
        assert _same(solve_fin(nw, pf, rq, backend="python"), sol)


# ---------------------------------------------------------------------------
# banded representation
# ---------------------------------------------------------------------------

def _paper_fgs(scenario, gamma=10, lam=None):
    prof = paper_profile("h2")
    ext = build_extended_graph(scenario, prof,
                               AppRequirements(alpha=0.8, delta=5e-3))
    return build_feasible_graph(ext, gamma, lam=lam)


@pytest.mark.parametrize("lam", [None, 4])
def test_banded_relax_bitexact_vs_dense(scenario, lam):
    """Banded distances equal the dense flattened-state relaxation bit for
    bit (same float64 adds over the same candidate sets)."""
    fg = _paper_fgs(scenario, lam=lam)
    E, st = fg.banded_tensors()
    hb = batched_banded_relax_min(fg.init_grid()[None], E[None], st[None],
                                  fg.depth_window_lo)
    hd = batched_layered_relax_min(fg.init_vector()[None],
                                   fg.layer_matrices()[None])
    np.testing.assert_array_equal(hb[0].reshape(hb.shape[1], -1), hd[0])


def test_banded_lazy_parent_matches_dense(scenario):
    """_BandedDP's O(N) lazy parent scan reproduces _FlatDP's O(S) flat
    column argmin (same first-occurrence tie order) on every finite state."""
    from repro.core.fin import _BandedDP, _FlatDP

    fg = _paper_fgs(scenario)
    N, G = fg.ext.n_nodes, fg.gamma
    E, st = fg.banded_tensors()
    hb = batched_banded_relax_min(fg.init_grid()[None], E[None], st[None],
                                  fg.depth_window_lo)
    Ws = fg.layer_matrices()
    hd = batched_layered_relax_min(fg.init_vector()[None], Ws[None])
    banded = _BandedDP(hb[0], E, st, fg.depth_window_lo)
    flat = _FlatDP(hd[0], Ws, N, G)
    L = hb.shape[1]
    for i in range(1, L):
        for n in range(N):
            for g in range(G + 1):
                if np.isfinite(hb[0, i, n, g]):
                    assert banded.parent(i, n, g, 0) == flat.parent(i, n, g, 0)


def test_banded_argmin_backends_match_numpy(scenario):
    """jnp / pallas banded argmin parents agree with the exact numpy
    distances (f32 tolerance) and reconstruct them through the band."""
    fg = _paper_fgs(scenario)
    E, st = fg.banded_tensors()
    init = fg.init_grid()
    hb = batched_banded_relax_min(init[None], E[None], st[None],
                                  fg.depth_window_lo)
    for backend in ("jnp", "pallas"):
        h, par = batched_banded_relax_argmin(init[None], E[None], st[None],
                                             fg.depth_window_lo,
                                             backend=backend)
        m = np.isfinite(hb[0])
        assert (np.isfinite(h[0]) == m).all()
        np.testing.assert_allclose(h[0][m], hb[0][m], rtol=RELAX_RTOL_F32)
        L = h.shape[1]
        for i in range(1, L):
            for n in range(fg.ext.n_nodes):
                for g in range(fg.gamma + 1):
                    p = par[0, i - 1, n, g]
                    if np.isfinite(h[0, i, n, g]):
                        gs = g - int(st[i - 1, p, n])
                        assert p >= 0 and gs >= 0
                        np.testing.assert_allclose(
                            h[0, i, n, g],
                            h[0, i - 1, p, gs] + E[i - 1, p, n],
                            rtol=RELAX_RTOL_F32)
                    else:
                        assert p == -1


def test_solve_many_backend_dense_equals_banded(scenario):
    ps, ns, rs = sweep_scenarios(apps=("h2", "h6"), deltas_ms=(2.0, 8.0))
    banded = solve_many(ps, ns, rs, backend="minplus")
    dense = solve_many(ps, ns, rs, backend="dense")
    for b, d in zip(banded, dense):
        assert _same(b, d)


# ---------------------------------------------------------------------------
# batched graph construction
# ---------------------------------------------------------------------------

def test_batched_extended_graphs_match_per_scenario():
    ps, ns, rs = sweep_scenarios(deltas_ms=(2.0, 5.0),
                                 uplinks_bps=(1e9, 0.5e9))
    exts = build_extended_graphs(ns, ps, rs)
    # duplicates (same network/profile/sigma) share one object
    assert len({id(e) for e in exts}) < len(exts)
    for pf, nw, rq, eb in zip(ps, ns, rs, exts):
        ea = build_extended_graph(nw, pf, rq)
        for f in ("C", "T", "E", "TT", "mask", "init_T", "init_E",
                  "init_mask"):
            np.testing.assert_array_equal(getattr(ea, f), getattr(eb, f)), f


def test_batched_feasible_graphs_match_per_scenario():
    ps, ns, rs = sweep_scenarios(apps=("h2", "h6"), deltas_ms=(2.0, 8.0))
    exts = build_extended_graphs(ns, ps, rs)
    for quantize in ("floor", "ceil"):
        fgs = build_feasible_graphs(exts, 10, quantize=quantize)
        for ext, fgb in zip(exts, fgs):
            fga = build_feasible_graph(ext, 10, quantize=quantize)
            np.testing.assert_array_equal(fga.steep, fgb.steep)
            np.testing.assert_array_equal(fga.init_depth, fgb.init_depth)
    # per-scenario delta_eff override (the tighten loop's path)
    fgs = build_feasible_graphs(exts[:2], 10, delta_effs=[1e-3, 3e-3])
    for fg, d in zip(fgs, (1e-3, 3e-3)):
        ref = build_feasible_graph(fg.ext, 10, delta_eff=d)
        np.testing.assert_array_equal(ref.steep, fg.steep)


def test_user_networks_batched_matches_single():
    rng = np.random.default_rng(0)
    qs = rng.uniform(0.3, 1.0, 5)
    batched = user_networks(qs, 0.005)
    for q, nb in zip(qs, batched):
        na = user_network(np.random.default_rng(1), 0.005,
                          uplink_quality=float(q))
        np.testing.assert_array_equal(na.bandwidth, nb.bandwidth)
        np.testing.assert_array_equal(na.compute, nb.compute)
    # identical qualities share one Network object (identity-keyed caches)
    twins = user_networks(np.array([0.5, 0.7, 0.5]), 0.005)
    assert twins[0] is twins[2] and twins[0] is not twins[1]


# ---------------------------------------------------------------------------
# relaxation-primitive level
# ---------------------------------------------------------------------------

def test_batched_relax_argmin_matches_single():
    rng = np.random.default_rng(0)
    B, L, S = 5, 4, 24
    Ws = rng.uniform(0.1, 5.0, (B, L, S, S))
    Ws[rng.uniform(size=Ws.shape) < 0.5] = np.inf
    init = rng.uniform(0, 3, (B, S))
    init[rng.uniform(size=init.shape) < 0.4] = np.inf
    hist, par = batched_layered_relax_argmin(init, Ws, backend="numpy")
    hist_j, par_j = batched_layered_relax_argmin(init, Ws, backend="jnp")
    for b in range(B):
        d = layered_relax(init[b], Ws[b], backend="numpy")
        np.testing.assert_array_equal(hist[b], d)
        m = np.isfinite(d)
        np.testing.assert_allclose(hist_j[b][m], d[m], rtol=RELAX_RTOL_F32)
        np.testing.assert_array_equal(par_j[b], par[b])
        # parents reconstruct the distances exactly
        for l in range(1, L + 1):
            for t in range(S):
                p = par[b, l - 1, t]
                if p >= 0:
                    assert hist[b, l, t] == hist[b, l - 1, p] + Ws[b, l - 1, p, t]
                else:
                    assert not np.isfinite(hist[b, l, t])


def test_kbest_rank1_equals_argmin_relax():
    rng = np.random.default_rng(3)
    B, L, S = 3, 3, 16
    Ws = rng.uniform(0.1, 5.0, (B, L, S, S))
    Ws[rng.uniform(size=Ws.shape) < 0.5] = np.inf
    init = rng.uniform(0, 3, (B, S))
    hist1, _ = batched_layered_relax_argmin(init, Ws, backend="numpy")
    histk, ps, pk = batched_layered_relax_kbest(init, Ws, K=3)
    np.testing.assert_array_equal(histk[..., 0], hist1)
    # ranks are sorted per state (inf <= inf for the unused slots)
    assert (histk[..., :-1] <= histk[..., 1:]).all()


def test_layered_relax_argmin_single_wrapper():
    rng = np.random.default_rng(5)
    S, L = 12, 3
    Ws = rng.uniform(0.1, 5.0, (L, S, S))
    init = rng.uniform(0, 3, S)
    hist, par = layered_relax_argmin(init, Ws, backend="numpy")
    assert hist.shape == (L + 1, S) and par.shape == (L, S)
    np.testing.assert_array_equal(hist, layered_relax(init, Ws, "numpy"))


def test_banded_minarg_matches_min_and_lazy_parents(scenario):
    """The argmin-storing float64 banded engine (the Plan IR's warm DP):
    distances bit-equal to the min-only engine, parents identical to the
    lazy ``banded_parent_np`` recovery on every reachable state."""
    from repro.core.bellman_ford import banded_parent_np

    fg = _paper_fgs(scenario)
    E, st = fg.banded_tensors()
    init = fg.init_grid()
    lo = fg.depth_window_lo
    hist_min = batched_banded_relax_min(init[None], E[None], st[None], lo)
    hist, par = batched_banded_relax_minarg(init[None], E[None], st[None], lo)
    np.testing.assert_array_equal(hist, hist_min)
    L = hist.shape[1]
    for i in range(1, L):
        for n in range(fg.ext.n_nodes):
            for g in range(fg.gamma + 1):
                if np.isfinite(hist[0, i, n, g]):
                    pn, pg = banded_parent_np(hist[0, i - 1], E[i - 1],
                                              st[i - 1], n, g, lo)
                    assert par[0, i - 1, n, g] == pn
                    assert g - int(st[i - 1, pn, n]) == pg
                else:
                    assert par[0, i - 1, n, g] == -1


# ---------------------------------------------------------------------------
# REPRO_RELAX_CHUNK_BYTES parsing
# ---------------------------------------------------------------------------

def test_relax_chunk_bytes_env_validation(monkeypatch):
    """A set-but-invalid chunk budget must raise a clear error instead of
    silently falling back (and later failing inexplicably deep inside the
    chunked relaxation); unset/empty means the default."""
    from repro.core.fin import _RELAX_CHUNK_BYTES_DEFAULT, _relax_chunk_bytes

    monkeypatch.delenv("REPRO_RELAX_CHUNK_BYTES", raising=False)
    assert _relax_chunk_bytes() == _RELAX_CHUNK_BYTES_DEFAULT
    monkeypatch.setenv("REPRO_RELAX_CHUNK_BYTES", "")
    assert _relax_chunk_bytes() == _RELAX_CHUNK_BYTES_DEFAULT
    monkeypatch.setenv("REPRO_RELAX_CHUNK_BYTES", "65536")
    assert _relax_chunk_bytes() == 65536
    for bad in ("abc", "4MB", "1.5e6"):        # non-integer
        monkeypatch.setenv("REPRO_RELAX_CHUNK_BYTES", bad)
        with pytest.raises(ValueError, match="REPRO_RELAX_CHUNK_BYTES"):
            _relax_chunk_bytes()
    for bad in ("0", "-4194304"):              # non-positive
        monkeypatch.setenv("REPRO_RELAX_CHUNK_BYTES", bad)
        with pytest.raises(ValueError, match="positive"):
            _relax_chunk_bytes()


def test_relax_chunk_bytes_invalid_surfaces_from_solver(monkeypatch, scenario):
    """The error must surface at the solver entry, not as a deep crash."""
    monkeypatch.setenv("REPRO_RELAX_CHUNK_BYTES", "bogus")
    prof = paper_profile("h2")
    with pytest.raises(ValueError, match="REPRO_RELAX_CHUNK_BYTES"):
        solve_many([prof] * 3, scenario, AppRequirements(0.8, 5e-3))


def test_relax_chunk_rows(monkeypatch):
    """The shared rows-per-chunk helper (one home for the max(1, budget //
    row_bytes) arithmetic used by fin, the plan IR and the population
    engine): floor division against the budget, never below one row, and
    loud on nonsensical row sizes."""
    from repro.core.bellman_ford import relax_chunk_rows

    monkeypatch.setenv("REPRO_RELAX_CHUNK_BYTES", "1000")
    assert relax_chunk_rows(100) == 10
    assert relax_chunk_rows(1000) == 1
    assert relax_chunk_rows(999) == 1
    # a single scenario larger than the whole budget still gets one row
    assert relax_chunk_rows(10_000) == 1
    monkeypatch.delenv("REPRO_RELAX_CHUNK_BYTES", raising=False)
    from repro.core.bellman_ford import _RELAX_CHUNK_BYTES_DEFAULT
    assert relax_chunk_rows(1) == _RELAX_CHUNK_BYTES_DEFAULT
    for bad in (0, -8):
        with pytest.raises(ValueError, match="bytes_per_row"):
            relax_chunk_rows(bad)


@pytest.mark.parametrize("D,n_devices,rows", [
    (1, 1, 16), (16, 1, 16), (17, 1, 32), (100, 1, 128),
    (1, 4, 4), (16, 4, 4), (17, 4, 8), (100, 4, 32), (5, 3, 8)])
def test_bucket_rows(D, n_devices, rows):
    """Device relax batches pad to a power of two of rows per device, at
    least MIN_RELAX_ROWS in all, and always hold every scenario."""
    from repro.core.bellman_ford import MIN_RELAX_ROWS, bucket_rows
    got = bucket_rows(D, n_devices)
    assert got == rows
    assert got * n_devices >= max(D, MIN_RELAX_ROWS)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_device_relax_buckets_batch_sizes(backend):
    """Every batch size up to MIN_RELAX_ROWS reuses one compiled program,
    and the padding never shows in the results."""
    from repro.core.bellman_ford import (MIN_RELAX_ROWS,
                                         _banded_relax_scan_jnp,
                                         batched_banded_relax_argmin)
    from repro.kernels.minplus.minplus import banded_minplus_chain_pallas
    jitted = {"jnp": _banded_relax_scan_jnp,
              "pallas": banded_minplus_chain_pallas}[backend]
    rng = np.random.default_rng(5)
    D, L, N, Gp1 = MIN_RELAX_ROWS, 3, 5, 13
    steep = np.where(rng.random((D, L, N, N)) < 0.6,
                     rng.integers(0, 4, (D, L, N, N)).astype(float), np.inf)
    E = rng.random((D, L, N, N))
    init = np.where(rng.random((D, N, Gp1)) < 0.4,
                    rng.random((D, N, Gp1)), np.inf)
    h_all, p_all = batched_banded_relax_argmin(init, E, steep,
                                               backend=backend)
    n0 = jitted._cache_size()
    for b in (1, 3, 7, D):
        h, p = batched_banded_relax_argmin(init[:b], E[:b], steep[:b],
                                           backend=backend)
        assert h.shape == (b, L + 1, N, Gp1) and p.shape == (b, L, N, Gp1)
        assert np.array_equal(h, h_all[:b]) and np.array_equal(p, p_all[:b])
    assert jitted._cache_size() == n0
