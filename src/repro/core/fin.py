"""FIN solver (Alg. 1): feasible-graph construction + min-cost traversal.

The traversal is a layered dynamic program over states (node, depth): exact
minimum-energy path in the feasible graph.  The feasible graph is *banded*
in depth — an edge only connects depth g to g + steep(n, n') — so the DP
runs natively over the compact (N, G+1) distance grid as a shift-by-steep
gather + min over source nodes: O(N^2 G) per layer instead of the
O(N^2 G^2) dense (S, S) flattened-state relaxation.  Backends (see
``bellman_ford.py`` for the engines):

  ``python``   the original triple-nested loop DP — kept verbatim as the
               bit-for-bit oracle for the vectorized backends;
  ``minplus``  banded numpy relaxation (default; alias ``banded``) —
               bit-exact float64, lazy argmin parents;
  ``dense``    the dense flattened-state numpy relaxation over (S, S)
               matrices (alias ``numpy``) — kept for equivalence testing
               (including as the k-best oracle for the banded k-slot
               engines);
  ``jnp``      jitted banded relaxation (float32) for large instances;
  ``pallas``   the banded ``minplus`` TPU kernel (kernels/minplus).

One DP pass yields the best configuration for *every* candidate final exit
(the DP prefix costs at each exit block), so accuracy filtering (3c) is a
post-pass.  ``solve_many`` stacks per-scenario banded tensors into one
(B, L, N, N) relaxation so whole scenario sweeps (apps x delta targets x
uplink settings; the Fig. 5-7 grids, multi-app placement) run as a single
batched call instead of a Python loop over ``solve_fin`` — extended and
feasible graphs are likewise built in batched array ops
(``build_extended_graphs`` / ``build_feasible_graphs``).

Quantization undershoot ("floor" mode, see feasible_graph.py) is handled by
an exact post-check of the selected configuration and, if the true latency
violates (3b), re-solving with a geometrically tightened effective delta —
at most ``max_tighten`` rounds.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from .bellman_ford import (_RELAX_CHUNK_BYTES_DEFAULT,
                           batched_banded_relax_argmin,
                           batched_banded_relax_kbest,
                           batched_banded_relax_kbest_pallas,
                           batched_banded_relax_min,
                           batched_layered_relax_kbest,
                           batched_layered_relax_min, banded_parent_np,
                           relax_chunk_bytes, relax_chunk_rows)
from .dnn_profile import DNNProfile
from .extended_graph import (ExtendedGraph, build_extended_graph,
                             build_extended_graphs)
from .feasible_graph import (FeasibleGraph, batch_banded_tensors,
                             batch_layer_tensors, build_feasible_graph,
                             build_feasible_graphs)
from .problem import AppRequirements, Config, ConfigEval, Solution, evaluate_config
from .system_model import Network
from .tolerances import dist_tol

#: solver backend -> relaxation engine ("python" stays the legacy oracle).
#: ``banded`` engines relax the compact (N, G+1) grid; ``numpy`` is the dense
#: flattened-state (S, S) path, kept for equivalence testing.
DP_BACKENDS: Dict[str, str] = {
    "minplus": "banded",
    "banded": "banded",
    "numpy": "numpy",
    "dense": "numpy",
    "jnp": "jnp",
    "pallas": "pallas",
}

#: chunking budget now lives in ``bellman_ford`` (shared with the plan IR
#: and the population engine); these aliases keep the historical import
#: paths (``fin._relax_chunk_bytes``) working.
_relax_chunk_bytes = relax_chunk_bytes


def _dist_tol(backend: str) -> float:
    """Exit-prune guard for a user-facing backend name (see tolerances.py)."""
    return dist_tol(DP_BACKENDS.get(backend))


def _validate_n_best(n_best: int) -> int:
    """``n_best`` is the k-best slot count — a silent ``max(1, n_best)``
    clamp would turn a caller's typo'd 0 or -3 into the single-best DP."""
    if n_best < 1:
        raise ValueError(f"n_best must be >= 1, got {n_best}")
    return int(n_best)


@dataclass
class _DPResult:
    """k-best layered DP over states (block, node, depth).

    dist[i, n, g, k] = k-th cheapest energy reaching that state; parents give
    (node, depth, rank) of the predecessor.  n_best=1 is the paper's DP;
    n_best>1 is our beyond-paper fix for quantizer state collisions: with a
    coarse gamma two different placements can land on the same (n, g) state,
    and keeping only the cheapest can drop the only *exactly-feasible* path
    (observed at gamma=3 — EXPERIMENTS §Reproduction).  Keeping the k
    cheapest restores the 1+1/gamma behaviour at small gamma for k ~ 4.
    """
    dist: np.ndarray       # (L, N, G+1, K)
    par_n: np.ndarray      # (L, N, G+1, K)
    par_g: np.ndarray      # (L, N, G+1, K)
    par_k: np.ndarray      # (L, N, G+1, K)

    def parent(self, i: int, n: int, g: int, k: int) -> Tuple[int, int, int]:
        pn = int(self.par_n[i, n, g, k])
        assert pn >= 0
        return pn, int(self.par_g[i, n, g, k]), int(self.par_k[i, n, g, k])


class _FlatDP:
    """DP result over flat states with lazily recovered parents (K=1).

    The vectorized numpy engine relaxes distances only; a parent is
    recomputed on demand with one argmin column scan per backtracked step.
    Only a handful of end states per solve are ever traced back, so this
    skips materializing the full (L, S) argmin tensor entirely.  ``dist`` is
    a (L, N, G+1, 1) reshaped view of the distance history, interface-
    compatible with :class:`_DPResult`.
    """
    __slots__ = ("hist", "Ws", "G", "dist", "_dmin")

    def __init__(self, hist: np.ndarray, Ws: np.ndarray, N: int, G: int):
        self.hist = hist               # (L, S)
        self.Ws = Ws                   # (L-1, S, S)
        self.G = G
        self.dist = hist.reshape(hist.shape[0], N, G + 1, 1)

    def parent(self, i: int, n: int, g: int, k: int) -> Tuple[int, int, int]:
        t = n * (self.G + 1) + g
        # first-occurrence argmin matches the stored-parent backends' tie
        # order; dist[i, t] was computed as exactly this column's min
        s = int(np.argmin(self.hist[i - 1] + self.Ws[i - 1, :, t]))
        return s // (self.G + 1), s % (self.G + 1), 0


class _BandedDP:
    """Banded DP result with lazily recovered parents (K=1).

    ``hist`` is the compact (L, N, G+1) distance grid of the banded numpy
    engine; a parent is recomputed on demand with one O(N) candidate scan
    over source nodes per backtracked step (``banded_parent_np``) — the
    banded analogue of :class:`_FlatDP`, with the same first-occurrence tie
    order as the dense flat-state argmin (states are node-major and each
    source node contributes at most one candidate depth per target).
    """
    __slots__ = ("hist", "E", "steep", "lo", "dist", "_dmin")

    def __init__(self, hist: np.ndarray, E: np.ndarray, steep: np.ndarray,
                 lo: Optional[int]):
        self.hist = hist               # (L, N, G+1)
        self.E = E                     # (L-1, N, N)
        self.steep = steep             # (L-1, N, N)
        self.lo = lo
        self.dist = hist[..., None]    # (L, N, G+1, 1) _DPResult-compatible

    def parent(self, i: int, n: int, g: int, k: int) -> Tuple[int, int, int]:
        pn, pg = banded_parent_np(self.hist[i - 1], self.E[i - 1],
                                  self.steep[i - 1], n, g, self.lo)
        return pn, pg, 0


class _BandedArgDP:
    """Banded DP result with stored argmin-source-node parents (jnp/pallas).

    ``par_n[i-1, n, g]`` is the argmin source node of state (n, g) at block
    i; the parent depth is implied by the band: g - steep[i-1, pn, n].
    """
    __slots__ = ("hist", "par_n", "steep", "dist", "_dmin")

    def __init__(self, hist: np.ndarray, par_n: np.ndarray, steep: np.ndarray):
        self.hist = hist               # (L, N, G+1)
        self.par_n = par_n             # (L-1, N, G+1)
        self.steep = steep             # (L-1, N, N)
        self.dist = hist[..., None]

    def parent(self, i: int, n: int, g: int, k: int) -> Tuple[int, int, int]:
        pn = int(self.par_n[i - 1, n, g])
        assert pn >= 0
        return pn, g - int(self.steep[i - 1, pn, n]), 0


class _BandedKDP:
    """Banded k-best DP result with stored (node, rank) parents.

    ``hist`` is the (L, N, G+1, K) k-slot distance grid of the banded
    k-best engines (``bellman_ford.batched_banded_relax_kbest`` and its
    pallas chain variant); the parent depth is implied by the band:
    g_src = g - steep[i-1, par_n, n].  Slot order (hence every backtrack)
    is identical to the dense ``_DPResult`` k-best path, and this is the
    DP state the Pareto-frontier subsystem's k-best rows come from.
    """
    __slots__ = ("dist", "par_n", "par_k", "steep", "_dmin")

    def __init__(self, hist: np.ndarray, par_n: np.ndarray,
                 par_k: np.ndarray, steep: np.ndarray):
        self.dist = hist               # (L, N, G+1, K)
        self.par_n = par_n             # (L-1, N, G+1, K)
        self.par_k = par_k             # (L-1, N, G+1, K)
        self.steep = steep             # (L-1, N, N)

    def parent(self, i: int, n: int, g: int, k: int) -> Tuple[int, int, int]:
        pn = int(self.par_n[i - 1, n, g, k])
        assert pn >= 0
        return (pn, g - int(self.steep[i - 1, pn, n]),
                int(self.par_k[i - 1, n, g, k]))


def _banded_dp_kbest(fgs: Sequence[FeasibleGraph], K: int,
                     engine: str) -> List["_BandedKDP"]:
    """Batched banded k-best DPs for a same-shape group of scenarios.

    ``banded``/``jnp`` relax through the float64 numpy k-best engine
    (bit-exact vs the dense k-best path); ``pallas`` through the chained
    k-slot kernel (f32 distances, identical slot order)."""
    f0 = fgs[0]
    gE, gst, ginit = batch_banded_tensors(list(fgs))
    lo = f0.depth_window_lo
    if engine == "pallas":
        hist, pn, pk = batched_banded_relax_kbest_pallas(ginit, gE, gst, K,
                                                         lo)
    else:
        hist, pn, pk = batched_banded_relax_kbest(ginit, gE, gst, K, lo)
    return [_BandedKDP(hist[j], pn[j], pk[j], gst[j])
            for j in range(len(fgs))]


def _banded_dp_single(fg: FeasibleGraph, engine: str) -> "_DPState":
    """One scenario through a banded engine (no (S, S) materialization)."""
    E, steep = fg.banded_tensors()
    init = fg.init_grid()
    lo = fg.depth_window_lo
    if engine == "banded":
        hist = batched_banded_relax_min(init[None], E[None], steep[None], lo)
        return _BandedDP(hist[0], E, steep, lo)
    hist, par = batched_banded_relax_argmin(init[None], E[None], steep[None],
                                            lo, backend=engine)
    return _BandedArgDP(hist[0], par[0], steep)


def _run_dp(fg: FeasibleGraph, n_best: int = 1) -> _DPResult:
    """Legacy pure-Python DP — the oracle behind ``backend="python"``."""
    ext = fg.ext
    N, L, G = ext.n_nodes, ext.n_blocks, fg.gamma
    K = max(1, n_best)
    dist = np.full((L, N, G + 1, K), np.inf)
    par_n = np.full((L, N, G + 1, K), -1, dtype=np.int32)
    par_g = np.full((L, N, G + 1, K), -1, dtype=np.int32)
    par_k = np.full((L, N, G + 1, K), -1, dtype=np.int32)

    for n in range(N):
        d0 = fg.init_depth[n]
        if np.isfinite(d0):
            dist[0, n, int(d0), 0] = ext.init_E[n]

    lo = fg.gamma - fg.lam

    def push(i, n2, g2, cand, pn, pg, pk):
        row = dist[i, n2, g2]
        if cand >= row[-1]:
            return
        j = int(np.searchsorted(row, cand))
        dist[i, n2, g2, j + 1:] = row[j:-1]
        par_n[i, n2, g2, j + 1:] = par_n[i, n2, g2, j:-1]
        par_g[i, n2, g2, j + 1:] = par_g[i, n2, g2, j:-1]
        par_k[i, n2, g2, j + 1:] = par_k[i, n2, g2, j:-1]
        dist[i, n2, g2, j] = cand
        par_n[i, n2, g2, j] = pn
        par_g[i, n2, g2, j] = pg
        par_k[i, n2, g2, j] = pk

    for i in range(L - 1):
        st = fg.steep[i]          # (N, N)
        ew = ext.E[i]             # (N, N)
        for n in range(N):
            for n2 in range(N):
                s = st[n, n2]
                if not np.isfinite(s):
                    continue
                s = int(s)
                cost = ew[n, n2]
                for g in range(G + 1 - s):
                    g2 = g + s
                    if fg.lam < fg.gamma and g2 != g and not (lo <= g2 <= G):
                        continue  # lambda-proximity window (Alg. 1, Fn II)
                    for k in range(K):
                        d = dist[i, n, g, k]
                        if not np.isfinite(d):
                            break
                        push(i + 1, n2, g2, d + cost, n, g, k)
    return _DPResult(dist=dist, par_n=par_n, par_g=par_g, par_k=par_k)


def _dp_from_flat(hist: np.ndarray, par_s: np.ndarray, par_k: np.ndarray,
                  N: int, G: int) -> _DPResult:
    """Reshape flat-state relaxation output (L, S, K) back into a _DPResult.

    par_s/par_k cover layers 1..L-1 ((L-1, S, K)); layer 0 has no parents.
    """
    L, S, K = hist.shape
    dist = hist.reshape(L, N, G + 1, K)
    par_n = np.full((L, S, K), -1, dtype=np.int32)
    par_g = np.full((L, S, K), -1, dtype=np.int32)
    par_k_ = np.full((L, S, K), -1, dtype=np.int32)
    if L > 1:
        valid = par_s >= 0
        np.floor_divide(par_s, G + 1, out=par_n[1:], where=valid,
                        casting="unsafe")
        np.remainder(par_s, G + 1, out=par_g[1:], where=valid,
                     casting="unsafe")
        np.copyto(par_k_[1:], par_k, where=valid, casting="unsafe")
    shape = (L, N, G + 1, K)
    return _DPResult(dist=dist, par_n=par_n.reshape(shape),
                     par_g=par_g.reshape(shape), par_k=par_k_.reshape(shape))


def _run_dp_batch(fgs: Sequence[FeasibleGraph], n_best: int = 1,
                  backend: str = "minplus") -> List["_DPState"]:
    """Batched relaxation for a list of feasible graphs.

    Same-shape scenarios (e.g. a delta sweep over one app) are grouped: each
    group's banded tensors are stacked and relaxed in one (D, L-1, N, N)
    batched banded chain (dense engines scatter (D, L-1, S, S) instead) — no
    padding buffers and no cross-shape copies, so mixed-size batches cost
    exactly the sum of their homogeneous groups.  Distances match
    per-scenario solves bit-for-bit on the float64 numpy engines.
    """
    K = _validate_n_best(n_best)
    if backend == "python":
        return [_run_dp(fg, n_best=n_best) for fg in fgs]
    engine = DP_BACKENDS.get(backend)
    if engine is None:
        raise ValueError(f"unknown FIN backend {backend!r} "
                         f"(expected python or one of {sorted(DP_BACKENDS)})")
    if K == 1 and engine == "pallas":
        # the K=1 pallas kernel launches once per (scenario, layer) — fall
        # back to a per-scenario pass
        return [_run_dp_single(fg, n_best=n_best, backend=backend)
                for fg in fgs]

    groups: Dict[Tuple[int, int, int, int], List[int]] = {}
    for j, fg in enumerate(fgs):
        groups.setdefault((fg.ext.n_blocks, fg.ext.n_nodes, fg.gamma, fg.lam),
                          []).append(j)
    out: List[Optional["_DPState"]] = [None] * len(fgs)
    banded = engine in ("banded", "jnp")
    if K > 1:
        # k-best rides the banded k-slot engines batched per shape group;
        # only the dense backend keeps the per-scenario dense k-best pass
        # (its (S, S) scatter is the equivalence oracle).
        if not banded and engine != "pallas":
            return [_run_dp_single(fg, n_best=n_best, backend=backend)
                    for fg in fgs]
        for (L, N, G, lam), idxs in groups.items():
            chunk = relax_chunk_rows(N * N * (G + 1) * K * 16)
            for start in range(0, len(idxs), chunk):
                part = idxs[start:start + chunk]
                for pos, dp in zip(part, _banded_dp_kbest(
                        [fgs[j] for j in part], K, engine)):
                    out[pos] = dp
        return out
    for (L, N, G, lam), idxs in groups.items():
        S = N * (G + 1)
        window = G - lam if lam < G else None
        # keep the relaxation's working set cache-resident: beyond ~L2/L3
        # size the broadcast turns memory-bound and batched throughput
        # collapses, so large groups run as resident chunks.  The banded
        # per-scenario set is the compact (N, N, G+1) f64 candidate plus
        # the all-layer (L-1, N, N, G+1) int32 gather indices — still
        # (gamma+1)x smaller than the dense (S, S) candidate per layer.
        cand_bytes = (N * N * (G + 1) * (8 + max(L - 1, 1) * 4) if banded
                      else S * S * 8)
        chunk = relax_chunk_rows(cand_bytes)
        for start in range(0, len(idxs), chunk):
            part = idxs[start:start + chunk]
            if banded:
                gE, gst, ginit = batch_banded_tensors(
                    [fgs[j] for j in part])
                if engine == "banded":
                    hist = batched_banded_relax_min(ginit, gE, gst, window)
                    for pos, j in enumerate(part):
                        out[j] = _BandedDP(hist[pos], gE[pos], gst[pos],
                                           window)
                else:
                    hist, par = batched_banded_relax_argmin(
                        ginit, gE, gst, window, backend=engine)
                    for pos, j in enumerate(part):
                        out[j] = _BandedArgDP(hist[pos], par[pos], gst[pos])
                continue
            gWs, ginit = batch_layer_tensors([fgs[j] for j in part])
            hist = batched_layered_relax_min(ginit, gWs)
            for pos, j in enumerate(part):
                out[j] = _FlatDP(hist[pos], gWs[pos], N, G)
    return out


def _run_dp_single(fg: FeasibleGraph, n_best: int = 1,
                   backend: str = "minplus") -> "_DPState":
    """Vectorized DP for one scenario (dispatches on ``backend``)."""
    K = _validate_n_best(n_best)
    if backend == "python":
        return _run_dp(fg, n_best=n_best)
    engine = DP_BACKENDS.get(backend)
    if engine is None:
        raise ValueError(f"unknown FIN backend {backend!r} "
                         f"(expected python or one of {sorted(DP_BACKENDS)})")
    ext = fg.ext
    N, G = ext.n_nodes, fg.gamma
    if engine in ("banded", "jnp", "pallas"):
        if K == 1:
            return _banded_dp_single(fg, engine)
        return _banded_dp_kbest([fg], K, engine)[0]
    Ws = fg.layer_matrices()
    init = fg.init_vector()
    if K == 1:
        hist = batched_layered_relax_min(init[None], Ws[None])
        return _FlatDP(hist[0], Ws, N, G)
    # k-best keeps the K cheapest slots per state (dense numpy relaxation,
    # the equivalence oracle for the banded k-slot engines).
    hist, ps, pk = batched_layered_relax_kbest(init[None], Ws[None], K)
    return _dp_from_flat(hist[0], ps[0], pk[0], N, G)


def _exit_dmin(dp, block: int) -> float:
    """Memoized min DP distance at a block (the exit-prune bound).

    Cached per DP grid: the incremental ``Plan`` layer re-scans the SAME
    grid across churn ticks whenever only the true bandwidth moved (the
    quantized tensors are piecewise-constant in the channel), so the
    per-exit minima are computed once per relaxation, not once per scan.
    """
    cache = getattr(dp, "_dmin", None)
    if cache is None:
        cache = {}
        try:
            dp._dmin = cache
        except AttributeError:      # foreign DP object without the slot
            return float(dp.dist[block].min())
    v = cache.get(block)
    if v is None:
        v = cache[block] = float(dp.dist[block].min())
    return v


def _backtrack(dp, block: int, node: int, depth: int,
               rank: int) -> List[int]:
    place = [node]
    i, n, g, r = block, node, depth, rank
    while i > 0:
        n, g, r = dp.parent(i, n, g, r)
        place.append(n)
        i -= 1
    return place[::-1]


def _configs_at_exit(dp: "_DPState", profile: DNNProfile, k: int
                     ) -> List[Tuple[Config, float]]:
    """Seed-faithful eager scan: ALL DP end-states at exit k's block, sorted
    by energy, every path backtracked up front.  Only the ``python`` oracle
    backend uses this — it preserves the original solver pipeline that the
    vectorized lazy post-pass is validated to reproduce
    (``tests/test_fin_backends.py``)."""
    block = profile.exits[k].block
    d = dp.dist[block]                      # (N, G+1, K)
    flat = np.argsort(d, axis=None)
    out: List[Tuple[Config, float]] = []
    for idx in flat:
        n, g, r = np.unravel_index(idx, d.shape)
        if not np.isfinite(d[n, g, r]):
            break
        cfg = Config(placement=_backtrack(dp, block, int(n), int(g), int(r)),
                     final_exit=k)
        out.append((cfg, float(d[n, g, r])))
    return out


def _iter_configs_at_exit(dp: "_DPState", profile: DNNProfile, k: int
                          ) -> Iterator[Tuple[Config, float]]:
    """DP end-states (x ranks) at exit k's block, lazily, in energy order.

    Energy weights are *not* quantized (only latency is), so the DP distance
    is the exact expected energy of the backtracked path; scanning states in
    energy order and exact-checking each yields the minimum-energy feasible
    path representable in the feasible graph.  Lazy: the caller stops at the
    first exactly-feasible configuration, so almost all backtracks are never
    materialized.
    """
    block = profile.exits[k].block
    d = dp.dist[block]                      # (N, G+1, K)
    # fast path: the cheapest state first, without sorting — np.argmin and a
    # stable ascending argsort share the first-occurrence-of-min tie order,
    # so consuming only one candidate (the overwhelmingly common case: the
    # min-energy config is exactly feasible) skips the argsort entirely
    j0 = int(np.argmin(d))
    v0 = float(d.ravel()[j0])
    if not np.isfinite(v0):
        return
    n0, g0, r0 = np.unravel_index(j0, d.shape)
    yield (Config(placement=_backtrack(dp, block, int(n0), int(g0), int(r0)),
                  final_exit=k), v0)
    order = np.argsort(d, axis=None, kind="stable")
    vals = d.ravel()[order]
    n_finite = int(np.searchsorted(vals, np.inf))
    ns_, gs_, rs_ = np.unravel_index(order[:n_finite], d.shape)
    for j in range(1, n_finite):            # order[0] == j0, already yielded
        cfg = Config(placement=_backtrack(dp, block, int(ns_[j]), int(gs_[j]),
                                          int(rs_[j])),
                     final_exit=k)
        yield cfg, float(vals[j])


def _best_feasible(network: Network, profile: DNNProfile,
                   req: AppRequirements, dp: "_DPState",
                   admissible_exits: Sequence[int],
                   check_aggregate_load: bool,
                   oracle: bool = False,
                   bound_energy: Optional[float] = None,
                   bound: Optional[Tuple[Config, ConfigEval]] = None,
                   dist_tol: float = 1e-9,
                   candidates=None
                   ) -> Optional[Tuple[Config, ConfigEval]]:
    """Exact (3a)-(3e) post-pass: cheapest feasible config over all exits.

    ``oracle=True`` reproduces the seed pipeline exactly (eager per-exit
    config lists, no pruning).  Otherwise configs are backtracked lazily and
    exits are skipped when their cheapest graph state cannot beat the
    incumbent (or ``bound_energy``, the already-found best of an earlier
    quantizer pass): the graph distance IS the exact path energy (energy
    weights are not quantized), so an exit whose minimum is clearly above
    the bound cannot yield a better feasible config — the ``dist_tol``
    relative guard keeps float-rounding near-ties evaluated exactly.
    Callers must widen ``dist_tol`` to the engine's distance error (the
    float32 jnp/pallas relaxations carry ~1e-7 relative error even though
    their histories are stored as float64).

    ``bound`` optionally carries the bounding pass's (config, eval) pair —
    when a scanned candidate IS that configuration, its (deterministic)
    evaluation is reused instead of recomputed: the ceil rescue pass
    usually lands on exactly the main pass's selection.

    ``candidates`` optionally replaces the lazy per-exit candidate
    iteration: a callable ``k -> iterator of (Config, graph_energy)`` that
    MUST yield exactly the sequence ``_iter_configs_at_exit(dp, profile,
    k)`` would.  The population engine passes a per-state cached factory so
    users sharing a quantized DP state share one backtrack instead of
    re-deriving identical configurations per user.
    """
    if bound is not None and bound_energy is None:
        bound_energy = bound[1].energy
    found: Optional[Tuple[Config, ConfigEval]] = None
    for k in admissible_exits:
        if not oracle:
            best_e = found[1].energy if found is not None else bound_energy
            if best_e is not None:
                if _exit_dmin(dp, profile.exits[k].block) \
                        > best_e * (1 + dist_tol):
                    continue
        if oracle:
            configs = _configs_at_exit(dp, profile, k)
        elif candidates is not None:
            configs = candidates(k)
        else:
            configs = _iter_configs_at_exit(dp, profile, k)
        for cfg, _graph_e in configs:
            if (bound is not None and cfg.final_exit == bound[0].final_exit
                    and cfg.placement == bound[0].placement):
                ev = bound[1]
            else:
                ev = evaluate_config(
                    network, profile, req, cfg,
                    check_aggregate_load=check_aggregate_load)
            if ev.feasible:
                if found is None or ev.energy < found[1].energy:
                    found = (cfg, ev)
                break  # states are energy-sorted: first feasible is best at k
    return found


def solve_fin(network: Network, profile: DNNProfile, req: AppRequirements,
              *, gamma: int = 10, lam: Optional[int] = None,
              quantize: str = "floor", max_tighten: int = 6,
              tighten_factor: float = 0.85, n_best: int = 1,
              backend: str = "minplus",
              check_aggregate_load: bool = False) -> Solution:
    """FIN (Alg. 1).  Returns the min-energy feasible configuration.

    ``backend`` selects the DP engine (``minplus`` vectorized numpy default,
    ``jnp``/``pallas`` accelerated, ``python`` legacy oracle); all return the
    same configuration.  ``n_best>1`` keeps the k cheapest paths per (node,
    depth) state — our beyond-paper fix for small-gamma quantizer collisions
    (see _DPResult) and the slot count behind ``Plan.frontier()``'s k-best
    Pareto rows (core/frontier.py)."""
    t0 = time.perf_counter()
    _validate_n_best(n_best)
    ext = build_extended_graph(network, profile, req)

    admissible_exits = [k for k in range(profile.n_exits)
                        if profile.accuracy_of(k) >= req.alpha - 1e-12]
    if not admissible_exits:
        return Solution(config=None, eval=None,
                        solve_time=time.perf_counter() - t0, solver="fin",
                        meta={"reason": "no exit meets alpha (3c)"})

    def _solve_once(q: str, d_eff: float,
                    bound: Optional[Tuple[Config, ConfigEval]] = None
                    ) -> Optional[Tuple[Config, ConfigEval]]:
        fg = build_feasible_graph(ext, gamma, lam=lam, quantize=q,
                                  delta_eff=d_eff)
        dp = _run_dp_single(fg, n_best=n_best, backend=backend)
        return _best_feasible(network, profile, req, dp, admissible_exits,
                              check_aggregate_load,
                              oracle=(backend == "python"),
                              bound=bound,
                              dist_tol=_dist_tol(backend))

    delta_eff = req.delta
    best: Optional[Tuple[Config, ConfigEval]] = None
    meta = {"gamma": gamma, "quantize": quantize, "tighten_rounds": 0,
            "backend": backend}
    for round_ in range(max_tighten + 1):
        best = _solve_once(quantize, delta_eff)
        if best is not None:
            break
        # quantization undershoot: tighten the effective latency budget
        delta_eff *= tighten_factor
        meta["tighten_rounds"] = round_ + 1
    if quantize != "ceil":
        # conservative pass: ceil quantization is feasible-by-construction and
        # can rescue state-collision misses of the optimistic quantizer.  The
        # floor-pass energy bounds the scan (vectorized backends only).
        alt = _solve_once("ceil", req.delta, best)
        if alt is not None and (best is None or alt[1].energy < best[1].energy):
            best = alt
            meta["used_ceil_pass"] = True

    dt = time.perf_counter() - t0
    if best is None:
        return Solution(config=None, eval=None, solve_time=dt, solver="fin",
                        meta={**meta, "reason": "no feasible path"})
    cfg, ev = best
    meta["delta_eff"] = delta_eff
    meta["n_feasible_states"] = int(np.isfinite(ev.energy))
    return Solution(config=cfg, eval=ev, solve_time=dt, solver="fin", meta=meta)


def _broadcast_scenarios(profiles, networks, requirements
                         ) -> Tuple[List[DNNProfile], List[Network],
                                    List[AppRequirements]]:
    def listify(x, single) -> list:
        return list(x) if not isinstance(x, single) else [x]

    ps = listify(profiles, DNNProfile)
    ns = listify(networks, Network)
    rs = listify(requirements, AppRequirements)
    B = max(len(ps), len(ns), len(rs))
    out = []
    for name, xs in (("profiles", ps), ("networks", ns),
                     ("requirements", rs)):
        if len(xs) == 1:
            xs = xs * B
        if len(xs) != B:
            raise ValueError(f"solve_many: {name} has length {len(xs)}, "
                             f"expected 1 or {B}")
        out.append(xs)
    return tuple(out)


def solve_many(profiles: Union[DNNProfile, Sequence[DNNProfile]],
               networks: Union[Network, Sequence[Network]],
               requirements: Union[AppRequirements, Sequence[AppRequirements]],
               *, gamma: int = 10, lam: Optional[int] = None,
               quantize: str = "floor", max_tighten: int = 6,
               tighten_factor: float = 0.85, n_best: int = 1,
               backend: str = "minplus",
               check_aggregate_load: bool = False) -> List[Solution]:
    """Batched FIN: solve B scenarios as one stacked (B, L, S, S) relaxation.

    Arguments broadcast: each of ``profiles`` / ``networks`` /
    ``requirements`` may be a single object or a length-B sequence (length-1
    sequences repeat).  Returns one ``Solution`` per scenario, equal to what
    ``solve_fin`` returns for that scenario with the same ``backend`` — the
    batched path shares the exact-evaluation post-pass, the tighten loop
    (re-batched over the still-unsolved scenarios each round) and the ceil
    rescue pass.  Extended graphs are deduplicated across scenarios that
    share (network, profile, sigma) — a delta/alpha sweep builds each graph
    once.  Scenarios of different sizes are grouped by shape and each group
    relaxes as its own stacked chain (see ``_run_dp_batch``).
    """
    t0 = time.perf_counter()
    _validate_n_best(n_best)
    profs, nets, reqs = _broadcast_scenarios(profiles, networks, requirements)
    B = len(profs)

    # batched stage-1 construction: unique (network, profile, sigma)
    # scenarios are stacked per profile group and built in one vectorized
    # pass (a 1000-user population is a handful of array ops, not 1000
    # per-scenario builds); duplicates share the same ExtendedGraph object.
    exts = build_extended_graphs(nets, profs, reqs)

    admissible: List[List[int]] = [
        [k for k in range(pf.n_exits)
         if pf.accuracy_of(k) >= rq.alpha - 1e-12]
        for pf, rq in zip(profs, reqs)]

    metas = [{"gamma": gamma, "quantize": quantize, "tighten_rounds": 0,
              "backend": backend, "batch_size": B} for _ in range(B)]
    best: List[Optional[Tuple[Config, ConfigEval]]] = [None] * B

    oracle = backend == "python"

    def _scan(b: int, dp: "_DPState",
              bound: Optional[Tuple[Config, ConfigEval]] = None
              ) -> Optional[Tuple]:
        return _best_feasible(nets[b], profs[b], reqs[b], dp, admissible[b],
                              check_aggregate_load, oracle=oracle,
                              bound=bound,
                              dist_tol=_dist_tol(backend))

    def _fgs(bs: List[int], qmode: str, d_effs: List[float]
             ) -> List[FeasibleGraph]:
        # batched stage-2 construction: one vectorized quantization per
        # same-shape group instead of a per-scenario Python loop
        return build_feasible_graphs([exts[b] for b in bs], gamma, lam=lam,
                                     quantize=qmode, delta_effs=d_effs)

    active = [b for b in range(B) if admissible[b]]
    delta_eff = [rq.delta for rq in reqs]
    pending = list(active)
    ceil_dps: Dict[int, "_DPState"] = {}
    for round_ in range(max_tighten + 1):
        if not pending:
            break
        fgs = _fgs(pending, quantize, [delta_eff[b] for b in pending])
        if round_ == 0 and quantize != "ceil":
            # the ceil rescue pass never depends on the tighten loop (it runs
            # at the un-tightened delta), so its DPs ride in the same batched
            # relaxation as round 0 — one (2B, L-1, N, N) group per shape.
            fgs += _fgs(active, "ceil", [reqs[b].delta for b in active])
        dps = _run_dp_batch(fgs, n_best=n_best, backend=backend)
        if round_ == 0 and quantize != "ceil":
            ceil_dps = dict(zip(active, dps[len(pending):]))
        found = [_scan(b, dp) for b, dp in zip(pending, dps[:len(pending)])]
        still = []
        for b, f in zip(pending, found):
            if f is not None:
                best[b] = f
            else:
                delta_eff[b] *= tighten_factor
                metas[b]["tighten_rounds"] = round_ + 1
                still.append(b)
        pending = still
    for b in active:
        if quantize == "ceil":
            break
        f = _scan(b, ceil_dps[b], best[b])
        if f is not None and (best[b] is None
                              or f[1].energy < best[b][1].energy):
            best[b] = f
            metas[b]["used_ceil_pass"] = True

    dt = time.perf_counter() - t0
    out: List[Solution] = []
    for b in range(B):
        if not admissible[b]:
            out.append(Solution(config=None, eval=None, solve_time=dt / B,
                                solver="fin",
                                meta={"reason": "no exit meets alpha (3c)",
                                      "batch_size": B, "batch_time": dt}))
            continue
        meta = {**metas[b], "batch_time": dt}
        if best[b] is None:
            out.append(Solution(config=None, eval=None, solve_time=dt / B,
                                solver="fin",
                                meta={**meta, "reason": "no feasible path"}))
            continue
        cfg, ev = best[b]
        meta["delta_eff"] = delta_eff[b]
        meta["n_feasible_states"] = int(np.isfinite(ev.energy))
        out.append(Solution(config=cfg, eval=ev, solve_time=dt / B,
                            solver="fin", meta=meta))
    return out
