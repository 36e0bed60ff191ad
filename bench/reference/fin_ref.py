"""Plain reference for the population cells: FIN placement per user.

A straightforward implementation of the placement semantics of
arXiv:2404.08060 (Sec. II-III) as the deployment file states them, with
nothing taken from the program under test:

* the network (Table V tiers, per-app slices, the calibrated uplink) and
  the early-exit DNN profiles (Tables II-IV) are rebuilt from the
  configuration file;
* a user's network is the base network with the source node's links set
  from its channel quality and attachment (the attached edge site gets the
  full channel, the other edge sites ``detach_frac`` of it);
* ``evaluate`` is the exact (3a)-(3e) evaluation of one configuration;
* ``solve`` is FIN (Alg. 1): extended graph, latency quantized into
  ``gamma`` depth steps (floor, then a ceil rescue pass), a min-energy
  dynamic program over (node, depth) states with first-occurrence ties,
  the exact post-pass over each admissible exit's end states in energy
  order, and the tightened-budget retries;
* ``FinChecker`` judges every answer of a sampled user at every tick: the
  hysteresis gate (hold the incumbent while it stays feasible and within
  ``1 + hysteresis`` of the energy it was chosen at, else re-place on the
  FIN solution) applied to the program's own previous answer.

``dtype=np.float32`` computes every number in float32: the control, which
breaks the configuration's guarantee of float64-exact decisions.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

INF = float("inf")


# --------------------------------------------------------------------------
# the deployment, rebuilt from the configuration file
# --------------------------------------------------------------------------

@dataclass
class App:
    name: str
    input_bits: float
    block_ops: List[float]
    cut_bits: List[float]
    exit_block: List[int]
    exit_ops: List[float]
    exit_out_bits: List[float]
    accuracy: List[float]
    phi: List[float]
    alpha: float
    delta: float
    sigma: float

    @property
    def L(self) -> int:
        return len(self.block_ops)

    @property
    def K(self) -> int:
        return len(self.exit_block)

    def phi_eff(self, k: int) -> List[float]:
        tot = 0.0
        for p in self.phi:
            tot += p
        ph = [p / tot for p in self.phi]
        out = ph[:k + 1]
        rest = 0.0
        for p in ph[k + 1:]:
            rest += p
        out[k] = out[k] + rest
        return out

    def surv_after(self, i: int, k: int) -> float:
        ph = self.phi_eff(k)
        gone = 0.0
        for e in range(k + 1):
            if self.exit_block[e] <= i:
                gone += ph[e]
        return max(0.0, 1.0 - gone)

    def surv_in(self, i: int, k: int) -> float:
        return 1.0 if i == 0 else self.surv_after(i - 1, k)

    def ops_with_exit(self, i: int, k: int) -> float:
        o = self.block_ops[i]
        for e in range(self.K):
            if self.exit_block[e] == i and e <= k:
                o += self.exit_ops[e]
        return o


def _np_phi(app: App):
    """Tables over (block, final exit) in float64, built once per app."""
    L, K = app.L, app.K
    ops = np.array([[app.ops_with_exit(i, k) for k in range(K)]
                    for i in range(L)])
    s_in = np.array([[app.surv_in(i, k) for k in range(K)]
                     for i in range(L)])
    s_out = np.array([[app.surv_after(i, k) for k in range(K)]
                      for i in range(L)])
    return ops, s_in, s_out


class Deployment:
    """Network + apps of a ``fin`` configuration file, in plain arrays."""

    def __init__(self, cfg: dict, dtype=np.float64):
        self.cfg = cfg
        self.dtype = dtype
        net = cfg["network"]
        tiers = net["tiers"]
        layout: List[str] = []
        for tier, count in net["layout"]:
            layout += [tier] * int(count)
        self.tiers = layout
        self.N = N = len(layout)
        self.src = 0
        compute_ops = [tiers[t]["tops"] * 1e12 for t in layout]
        frac = np.asarray([net["slice_frac"][t] for t in layout],
                          dtype=np.float64)
        self.compute = np.array(compute_ops) * frac
        self.p_act = np.array([tiers[t]["compute_power_w"] for t in layout])
        e_bit = [tiers[t]["e_bit_nj"] * 1e-9 for t in layout]
        self.e_tx = np.array(e_bit)
        self.e_rx = np.array(e_bit)
        link = [tiers[t]["link_gbps"] * 1e9 for t in layout]
        bw = np.zeros((N, N))
        for i in range(N):
            for j in range(N):
                if i != j:
                    bw[i, j] = min(link[i], link[j])
        np.fill_diagonal(bw, np.inf)
        self.uplink_bps = float(net["uplink_bps"])
        bw[0, 1:] = self.uplink_bps
        bw[1:, 0] = self.uplink_bps
        np.fill_diagonal(bw, np.inf)
        self.bw = bw
        self.link_bps = np.array(link)
        self.edge_nodes = [n for n, t in enumerate(layout)
                           if t == "edge" and n != self.src]
        self.detach_frac = float(cfg["population"]["detach_frac"])
        self.hysteresis = float(cfg["population"]["hysteresis"])
        sol = cfg["solver"]
        self.gamma = int(sol["gamma"])
        self.quantize = sol["quantize"]
        self.max_tighten = int(sol["max_tighten"])
        self.tighten_factor = float(sol["tighten_factor"])
        bpf = int(cfg["bits_per_feature"])
        self.apps: Dict[str, App] = {}
        for name, a in cfg["apps"].items():
            m = cfg["models"][a["model"]]
            self.apps[name] = App(
                name=name,
                input_bits=m["input_features"] * bpf,
                block_ops=[b[1] * 1e6 for b in m["blocks"]],
                cut_bits=[b[0] * bpf for b in m["blocks"]],
                exit_block=list(m["exit_blocks"]),
                exit_ops=[e[1] * 1e6 for e in m["exits"]],
                exit_out_bits=[e[0] * bpf for e in m["exits"]],
                accuracy=[x / 100.0 for x in a["accuracy_pct"]],
                phi=[p / 100.0 for p in m["phi_pct"]],
                alpha=float(a["alpha"]), delta=float(a["delta_s"]),
                sigma=float(a["sigma"]))
        self.app_names = list(self.apps)
        self._tabs = {n: _np_phi(a) for n, a in self.apps.items()}

    # ------------------------------------------------------- user channels
    def uplink_vectors(self, q: np.ndarray, att: np.ndarray) -> np.ndarray:
        """(S, N) source-link bandwidths of users with channel quality ``q``
        attached to edge slot ``att`` (the source column is inf)."""
        S, N = len(q), self.N
        full = self.uplink_bps * q
        det = full * self.detach_frac
        vec = np.broadcast_to(full[:, None], (S, N)).copy()
        if self.edge_nodes:
            edge = np.zeros(N, dtype=bool)
            edge[self.edge_nodes] = True
            node = np.asarray(self.edge_nodes)[att % len(self.edge_nodes)]
            detached = edge[None, :] & (np.arange(N)[None, :] != node[:, None])
            vec[detached] = np.broadcast_to(det[:, None], (S, N))[detached]
        vec[:, self.src] = np.inf
        return vec

    def base_vector(self) -> np.ndarray:
        """The source-link bandwidths of a user no channel report reached."""
        v = self.bw[self.src].copy()
        v[self.src] = np.inf
        return v

    def user_bw(self, vec: np.ndarray) -> np.ndarray:
        bw = self.bw.copy()
        bw[self.src, :] = vec
        bw[:, self.src] = vec
        bw[self.src, self.src] = np.inf
        return bw

    # ---------------------------------------------------------- evaluation
    def evaluate(self, app: str, vec: np.ndarray, dead: np.ndarray,
                 place: np.ndarray, k: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact (3a)-(3e) evaluation of one configuration per row.

        vec (S, N) source-link bandwidths, dead (S, N) failed nodes, place
        (S, L) node per block (entries past the final exit's block are
        ignored), k (S,) final exit.  Returns (feasible (S,), energy (S,));
        the energy of a placement on a failed node is inf.  Each addition
        is the scalar formula's, in its order, applied row-wise."""
        a = self.apps[app]
        ops_t, sin_t, sout_t = self._tabs[app]
        f = self.dtype
        src = self.src
        S = len(k)
        rows = np.arange(S)
        k = np.asarray(k, dtype=np.int64)
        place = np.asarray(place, dtype=np.int64)[:, :a.L]
        vec = np.asarray(vec, dtype=f)
        comp = self.compute.astype(f)
        p_act = self.p_act.astype(f)
        e_tx, e_rx = self.e_tx.astype(f), self.e_rx.astype(f)
        base = self.bw.astype(f)
        sigma = f(a.sigma)
        last = np.asarray(a.exit_block)[k]
        lat = np.zeros(S, f)
        ecomp = np.zeros(S, f)
        ecomm = np.zeros(S, f)
        ok = np.ones(S, bool)
        zero = f(0.0)
        p0 = place[:, 0]
        off = p0 != src
        b_in = np.where(off, vec[rows, p0], f(INF))
        bad = off & (b_in <= 0)
        ok &= ~bad
        b_in = np.where(bad, f(INF), b_in)
        in_bits = f(a.input_bits)
        lat = lat + np.where(off, in_bits / b_in, zero)
        ecomm = ecomm + np.where(off, (e_tx[src] + e_rx[p0]) * in_bits, zero)
        ok &= ~(off & (sigma * in_bits > b_in))
        L = a.L
        for i in range(L):
            act = i <= last
            n = np.where(act, place[:, i], 0)
            ops = ops_t[i, k].astype(f)
            s_in = sin_t[i, k].astype(f)
            c = comp[n]
            badc = act & (c <= 0)
            ok &= ~badc
            c = np.where(badc, f(INF), c)
            t = ops / c
            lat = lat + np.where(act, t, zero)
            ecomp = ecomp + np.where(act, s_in * p_act[n] * t, zero)
            ok &= ~(act & (sigma * s_in * ops > c))
            if i < L - 1:
                cross_ok = i < last
                n2 = np.where(cross_ok, place[:, i + 1], 0)
                cross = cross_ok & (n != n2)
                d = f(a.cut_bits[i])
                s_out = sout_t[i, k].astype(f)
                b = np.where(n == src, vec[rows, n2],
                             np.where(n2 == src, vec[rows, n], base[n, n2]))
                badb = cross & (b <= 0)
                ok &= ~badb
                b = np.where(badb, f(INF), b)
                lat = lat + np.where(cross, d / b, zero)
                ecomm = ecomm + np.where(
                    cross, s_out * (e_tx[n] + e_rx[n2]) * d, zero)
                ok &= ~(cross & (sigma * s_out * d > b))
        acc = np.asarray(a.accuracy)[k]
        ok &= ~(lat > f(a.delta) * f(1 + 1e-12))
        ok &= ~(acc < a.alpha - 1e-12)
        blocks = np.arange(L)[None, :] <= last[:, None]
        on_dead = (np.take_along_axis(np.asarray(dead, bool),
                                      np.where(blocks, place, 0), axis=1)
                   & blocks).any(axis=1)
        energy = ecomp + ecomm
        energy = np.where(on_dead, f(INF), energy)
        return ok & ~on_dead, energy

    # ------------------------------------------------------------- the DP
    def _graph(self, app: str, bw: np.ndarray):
        """Extended graph of one user: energies E (L-1, N, N), edge
        latencies TT, admissibility, and the source edges."""
        a = self.apps[app]
        f = self.dtype
        N, L, src = self.N, a.L, self.src
        kmax = a.K - 1
        ops = np.array([a.ops_with_exit(i, kmax) for i in range(L)], f)
        s_in = np.array([a.surv_in(i, kmax) for i in range(L)], f)
        s_out = np.array([a.surv_after(i, kmax) for i in range(L)], f)
        cut = np.asarray(a.cut_bits, f)
        bw = bw.astype(f)
        comp = np.where(self.compute > 0, self.compute, np.inf).astype(f)
        p_act = self.p_act.astype(f)
        e_tx, e_rx = self.e_tx.astype(f), self.e_rx.astype(f)
        sigma = f(a.sigma)
        eye = np.eye(N, dtype=bool)
        C = ops[:, None] / comp[None, :]
        link_ok = (bw > 0) | eye
        bw_eff = np.where(link_ok, np.where(eye, f(INF), bw), f(np.nan))
        np.fill_diagonal(bw_eff, np.inf)
        with np.errstate(invalid="ignore"):
            T = cut[:-1, None, None] / bw_eff[None]
        T = np.where(np.isnan(T), f(INF), T)
        T[:, eye] = 0.0
        pair = e_tx[:, None] + e_rx[None, :]
        comm = s_out[:-1, None, None] * cut[:-1, None, None] * pair[None]
        comm[:, eye] = 0.0
        compE = s_in[1:, None] * p_act[None, :] * C[1:, :]
        E = comm + compE[:, None, :]
        TT = T + C[1:, :][:, None, :]
        load = sigma * s_out[:-1, None, None] * cut[:-1, None, None]
        fits = load <= np.where(eye, f(INF), bw)[None]
        fits |= eye[None]
        cfits = (sigma * s_in[1:, None] * ops[1:, None]) <= comp[None, :]
        mask = link_ok[None] & fits & cfits[:, None, :]
        in_bits = f(a.input_bits)
        b_src = np.where(np.arange(N) == src, f(INF), bw[src])
        with np.errstate(invalid="ignore", divide="ignore"):
            init_T = in_bits / np.where(b_src > 0, b_src, f(np.nan)) + C[0]
        init_T = np.where(np.isnan(init_T), f(INF), init_T)
        init_comm = np.where(np.arange(N) == src, f(0.0),
                             (e_tx[src] + e_rx) * in_bits)
        init_E = init_comm + s_in[0] * p_act * C[0]
        init_mask = ((b_src > 0) & (sigma * in_bits <= b_src)
                     & (sigma * s_in[0] * ops[0] <= comp))
        return E, TT, mask, init_T, init_E, init_mask

    def _quantize(self, x: np.ndarray, mode: str, delta: float):
        f = self.dtype
        y = f(self.gamma) * x / f(delta)
        with np.errstate(invalid="ignore"):
            if mode == "floor":
                q = np.floor(y + f(1e-12))
            elif mode == "ceil":
                q = np.ceil(y - f(1e-12))
            else:
                raise ValueError(mode)
        return np.where(np.isfinite(y), q, np.inf)

    def _steep(self, graph, mode: str, delta: float, dead: np.ndarray):
        E, TT, mask, init_T, init_E, init_mask = graph
        G = self.gamma
        st = self._quantize(TT, mode, delta)
        st = np.where(mask, st, np.inf)
        st = np.where(st <= G, st, np.inf)
        d0 = self._quantize(init_T, mode, delta)
        d0 = np.where(init_mask, d0, np.inf)
        d0 = np.where(d0 <= G, d0, np.inf)
        if dead.any():
            st[:, dead, :] = np.inf
            st[:, :, dead] = np.inf
            d0[dead] = np.inf
        return st, d0

    def _relax(self, init_E, d0, E, st) -> np.ndarray:
        """(L, N, G+1) min energies over (node, depth) after each block."""
        N, Gp1 = self.N, self.gamma + 1
        f = self.dtype
        grid = np.full((N, Gp1), np.inf, f)
        ok = np.isfinite(d0)
        n_idx = np.nonzero(ok)[0]
        grid[n_idx, d0[n_idx].astype(np.int64)] = init_E[n_idx]
        hist = [grid]
        g = np.arange(Gp1)
        for l in range(E.shape[0]):
            s = st[l]                                   # (src, dst)
            fin = np.isfinite(s)
            gsrc = g[None, None, :] - np.where(fin, s, 0).astype(
                np.int64)[..., None]                    # (src, dst, G+1)
            valid = fin[..., None] & (gsrc >= 0)
            prev = hist[-1]
            cand = np.where(valid, prev[np.arange(N)[:, None, None],
                                        np.where(valid, gsrc, 0)]
                            + E[l][..., None], np.inf)
            hist.append(cand.min(axis=0))
        return np.stack(hist)

    def _parent(self, prev, E_l, st_l, n: int, g: int) -> Tuple[int, int]:
        s = st_l[:, n]
        fin = np.isfinite(s)
        si = np.where(fin, s, 0).astype(np.int64)
        gs = g - si
        ok = fin & (gs >= 0)
        cand = np.where(ok, prev[np.arange(len(s)), np.where(ok, gs, 0)]
                        + E_l[:, n], np.inf)
        pn = int(np.argmin(cand))
        return pn, g - int(si[pn])

    def _best(self, app: str, vec, dead, hist, E, st, admissible):
        a = self.apps[app]
        found = None
        for k in admissible:
            b = a.exit_block[k]
            d = hist[b]
            order = np.argsort(d, axis=None, kind="stable")
            vals = d.ravel()[order]
            for j in range(len(order)):
                if not np.isfinite(vals[j]):
                    break
                n, g = divmod(int(order[j]), d.shape[1])
                place = [n]
                for i in range(b, 0, -1):
                    n, g = self._parent(hist[i - 1], E[i - 1], st[i - 1],
                                        n, g)
                    place.append(n)
                place = place[::-1]
                row = np.full((1, a.L), -1, np.int64)
                row[0, :len(place)] = place
                feas, en = self.evaluate(app, vec[None], dead[None], row,
                                         np.array([k]))
                if feas[0]:
                    if found is None or en[0] < found[2]:
                        found = (place, k, en[0])
                    break
        return found

    def solve(self, app: str, vec: np.ndarray, dead: np.ndarray):
        """FIN for one user: (placement list, final exit, energy) or None
        when no feasible configuration exists."""
        a = self.apps[app]
        adm = [k for k in range(a.K) if a.accuracy[k] >= a.alpha - 1e-12]
        if not adm:
            return None
        graph = self._graph(app, self.user_bw(vec))
        E, _, _, _, init_E, _ = graph

        def once(mode, d_eff):
            st, d0 = self._steep(graph, mode, d_eff, dead)
            hist = self._relax(init_E, d0, E, st)
            return self._best(app, vec, dead, hist, E, st, adm)

        d_eff = a.delta
        best = None
        for _ in range(self.max_tighten + 1):
            best = once(self.quantize, d_eff)
            if best is not None:
                break
            d_eff *= self.tighten_factor
        if self.quantize != "ceil":
            alt = once("ceil", a.delta)
            if alt is not None and (best is None or alt[2] < best[2]):
                best = alt
        return best


# --------------------------------------------------------------------------
# judging the answers
# --------------------------------------------------------------------------

@dataclass
class Answers:
    """One tick's answers of the sampled users: (S,) found flags, (S, L)
    placements (-1 padded), (S,) exits and (S,) reported energies."""
    found: np.ndarray
    place: np.ndarray
    exit: np.ndarray
    energy: np.ndarray


class FinChecker:
    """Judges each sampled user's answer at each tick against the plain
    reference, given the answer the same source gave at the tick before.

    ``mismatches`` counts answers that differ in kind from the reference:
    held where the gate re-places or re-placed where it holds, found where
    FIN finds nothing or the reverse, or an answer that is infeasible under
    the tick's network.  ``energy_gap`` is the largest relative gap between
    a reported energy and the reference's (the exact energy of a held
    configuration, the FIN optimum of a re-placed one)."""

    def __init__(self, dep: Deployment, apps: Sequence[str]):
        self.dep = dep
        self.apps = np.asarray(apps)
        self.mismatches = 0
        self.energy_gap = 0.0
        self.answers = 0
        self.replaced = 0
        self.examples: List[str] = []

    def _gap(self, got: np.ndarray, want: np.ndarray) -> None:
        both = np.isfinite(got) & np.isfinite(want)
        if both.any():
            g = np.abs(got[both] - want[both]) / np.abs(want[both])
            self.energy_gap = max(self.energy_gap, float(g.max()))

    def _miss(self, n: int, why: str) -> None:
        self.mismatches += int(n)
        if n and len(self.examples) < 8:
            self.examples.append(why)

    def start(self, vec: np.ndarray, dead: np.ndarray,
              ans: Answers) -> None:
        """The answers before the first judged tick: each found one must be
        feasible, with its reported energy exact; returns nothing."""
        self.ref = np.full(len(ans.found), np.inf)
        for app in np.unique(self.apps):
            sel = np.nonzero((self.apps == app) & ans.found)[0]
            if not len(sel):
                continue
            feas, en = self.dep.evaluate(app, vec[sel], dead[sel],
                                         ans.place[sel], ans.exit[sel])
            self._miss(int((~feas).sum()), f"start: infeasible {app}")
            self._gap(ans.energy[sel], en)
            self.ref[sel] = en

    def tick(self, t: int, vec: np.ndarray, dead: np.ndarray,
             prev: Answers, ans: Answers) -> None:
        dep = self.dep
        h = dep.hysteresis
        S = len(ans.found)
        self.answers += S
        for app in np.unique(self.apps):
            rows = np.nonzero(self.apps == app)[0]
            resolve = np.ones(len(rows), bool)
            held_e = np.full(len(rows), np.inf)
            had = prev.found[rows]
            if had.any():
                r = rows[had]
                feas, en = dep.evaluate(app, vec[r], dead[r], prev.place[r],
                                        prev.exit[r])
                keep = feas & ~(en > self.ref[r] * (1.0 + h))
                resolve[had] = ~keep
                held_e[had] = en
            held = rows[~resolve]
            if len(held):
                same = ((ans.exit[held] == prev.exit[held])
                        & (ans.place[held] == prev.place[held]).all(axis=1)
                        & ans.found[held])
                self._miss(int((~same).sum()),
                           f"tick {t} {app}: answer moved while the gate "
                           f"holds (users {held[~same][:4].tolist()})")
                self._gap(ans.energy[held], held_e[~resolve])
            for u in rows[resolve]:
                self.replaced += 1
                sol = dep.solve(app, vec[u], dead[u])
                if sol is None:
                    if ans.found[u]:
                        self._miss(1, f"tick {t} user {u}: found where FIN "
                                      f"finds nothing")
                    self.ref[u] = np.inf
                    continue
                if not ans.found[u]:
                    self._miss(1, f"tick {t} user {u}: nothing found where "
                                  f"FIN finds energy {sol[2]!r}")
                    self.ref[u] = sol[2]
                    continue
                feas, en = dep.evaluate(app, vec[u][None], dead[u][None],
                                        ans.place[u][None],
                                        ans.exit[u][None])
                if not feas[0]:
                    self._miss(1, f"tick {t} user {u}: re-placed on an "
                                  f"infeasible configuration")
                self._gap(np.array([ans.energy[u]]), np.array([sol[2]]))
                self._gap(np.asarray(en, np.float64), np.array([sol[2]]))
                self.ref[u] = sol[2]


class ReferenceSource:
    """The reference itself as a source of answers: tracks its own
    incumbents through the hysteresis gate.  In ``dtype=np.float32`` it is
    the control put in the program's place."""

    def __init__(self, dep: Deployment, apps: Sequence[str]):
        self.dep = dep
        self.apps = np.asarray(apps)
        S = len(self.apps)
        L = max(a.L for a in dep.apps.values())
        self.ans = Answers(np.zeros(S, bool), np.full((S, L), -1, np.int64),
                           np.full(S, -1, np.int64), np.full(S, np.inf))
        self.ref = np.full(S, np.inf)

    def step(self, vec: np.ndarray, dead: np.ndarray,
             first: bool = False) -> Answers:
        dep, a = self.dep, self.ans
        new = Answers(a.found.copy(), a.place.copy(), a.exit.copy(),
                      a.energy.copy())
        for app in np.unique(self.apps):
            rows = np.nonzero(self.apps == app)[0]
            resolve = np.ones(len(rows), bool)
            had = a.found[rows] & (not first)
            if had.any():
                r = rows[had]
                feas, en = dep.evaluate(app, vec[r], dead[r], a.place[r],
                                        a.exit[r])
                keep = feas & ~(en > self.ref[r] * (1.0 + dep.hysteresis))
                resolve[had] = ~keep
                new.energy[r[keep]] = en[keep]
            for u in rows[resolve]:
                sol = dep.solve(app, vec[u], dead[u])
                new.place[u] = -1
                if sol is None:
                    new.found[u], new.exit[u] = False, -1
                    new.energy[u] = self.ref[u] = np.inf
                    continue
                place, k, e = sol
                new.found[u], new.exit[u] = True, k
                new.place[u, :len(place)] = place
                new.energy[u] = self.ref[u] = float(e)
        self.ans = new
        return new
